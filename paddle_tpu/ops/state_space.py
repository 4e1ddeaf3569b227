"""State-space layers: the scalar-decay scan of Mamba-2 ("SSD") as a chunked
scan, two Pallas kernels.

Per head (width P, state N) the layer keeps one [P, N] float32 state S:

    S_t = a_t S_(t-1) + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    a_t = exp(g_t),  g_t = -exp(A_log) dt_t  (one scalar a head and token)

with B_t, C_t [N] shared by every head of a group. Token by token it is
``seq`` dependent steps of vector work. Here a sequence is cut into chunks of
C tokens; with gamma the running sum of g inside a chunk and the decay table
L[i, j] = exp(gamma_i - gamma_j) for j <= i, a chunk that starts from S_0 is

    Y   = exp(gamma) (C S_0^T) + (L * (C B^T)) (dt X)
    S_C = exp(gamma_C) S_0 + ((exp(gamma_C - gamma) dt X)^T B)

against the gated delta rule (``ops/linear_attention.py``): a scalar decay a
head, no delta correction and so no inverse, and C B^T is one [C, C] table
for all heads of the group, made once a chunk.

``ssd_scan_fwd``  walks a grid of (batch, blocks of chunks, lane blocks of
    heads): x is read where the conv left it ([batch, seq, heads * P]: a lane
    block of 128 holds 128 / P heads), B and C likewise ([batch, seq, groups
    * N]: a lane block reads its group's N lanes, the heads of a group being
    whole lane blocks side by side); the masked C B^T of the grid step's
    chunks is made at a group's first lane block and kept in VMEM for the
    group's others; S^T [N, 128] of every lane block stays in float32 VMEM
    scratch over a sequence's chunks. Writes y and, for the backward, the
    state each chunk starts from (in the operands' type).
``ssd_scan_bwd``  the chunks in reverse with dS in VMEM: makes the tables
    again, carries the cotangents through the four products, sums d(C B^T)
    over a group's lane blocks in scratch and turns it to the group's dB, dC
    at its last one; writes dx, d dt, dg, dB, dC.

Operands of the products are in the type x comes in (bf16 under AMP-O2); the
state, the decays, dt, the tables and every sum in float32. Shapes the
kernels refuse (``ssd_chunks.why``) take the same chunked form as
``jax.numpy`` expressions under ``lax.scan``, differentiated by JAX, and are
counted (``ssd_scan_fallbacks``). Off the TPU the kernels run interpreted.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
STEP_ROWS = 512  # rows of a sequence one grid step of the kernels holds
_0 = np.int32(0)  # index-map literal; Python ints trace to i64 under x64
_NN = ((1,), (0,))  # a @ b
_NT = ((1,), (1,))  # a @ b.T
_TN = ((0,), (0,))  # a.T @ b


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _mm(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=F32)


# ---------------------------------------------------------------------------
# what a chunk is made of, in VMEM
# ---------------------------------------------------------------------------
class _Tables(NamedTuple):
    """One head's gates over a chunk, float32."""
    dt: jax.Array     # [C, 1]
    e: jax.Array      # [C, 1] exp(gamma)
    el: jax.Array     # [C, 1] exp(gamma_C - gamma)
    dec: jax.Array    # [1, 1] exp(gamma_C)
    decay: jax.Array  # [C, C] exp(gamma_i - gamma_j) on and under the diagonal


def _masks(c):
    ri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return ci <= ri, ci == ri


def _to_col(row, eye):
    """[1, C] -> [C, 1], exactly (no transpose unit, no product)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _to_row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _tables(dt_row, g_row, under, eye):
    """A head's tables from its [1, C] rows of dt and of the log decay."""
    gam = jnp.sum(jnp.where(under, g_row, 0.0), axis=1, keepdims=True)
    last = jnp.sum(g_row, axis=1, keepdims=True)
    gap = jnp.where(under, gam - _to_row(gam, eye), 0.0)
    return _Tables(_to_col(dt_row, eye), jnp.exp(gam), jnp.exp(last - gam),
                   jnp.exp(last), jnp.where(under, jnp.exp(gap), 0.0))


def _head_lanes(heads, width=128):
    """[1, width] masks: the lanes of each of ``heads`` heads side by side."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    p = width // heads
    return [(lane >= k * p) & (lane < (k + 1) * p) for k in range(heads)]


def _by_head(lanes, values):
    """One [rows, 128] (or [1, 128]) array out of a value per head: head k's
    lanes take ``values[k]``."""
    out = values[0]
    for mask, value in zip(lanes[1:], values[1:]):
        out = jnp.where(mask, value, out)
    return out + jnp.zeros((1, lanes[0].shape[1]), out.dtype)


def _head_rowsum(lanes, x):
    """Per head, the [rows, 1] sums of x [rows, 128] over the head's lanes."""
    if len(lanes) == 1:
        return [jnp.sum(x, axis=1, keepdims=True)]
    return [jnp.sum(jnp.where(mask, x, 0.0), axis=1, keepdims=True)
            for mask in lanes]


# A kernel holds a grid step's chunks unrolled; what a chunk does is a jitted
# function of values, traced once and not once a chunk (a step's trace is part
# of every run's set-up).
@jax.jit
def _chunk_scores(bm, cm):
    """C B^T [C, C] float32 of a chunk, the same for every head."""
    return _mm(cm, bm, _NT)


@jax.jit
def _chunk_forward(x, bm, cm, scores, dt_rows, g_rows, s):
    """(y, the state as the products see it, the next chunk's state) of one
    chunk of a lane block's heads that starts from the float32 ``s`` (S^T,
    [N, 128]). x [C, 128], bm, cm [C, N], the gates a [1, C] row a head."""
    cdt, heads = x.dtype, len(dt_rows)
    under, eye = _masks(x.shape[0])
    lanes = _head_lanes(heads)
    tabs = [_tables(dt_row, g_row, under, eye)
            for dt_row, g_row in zip(dt_rows, g_rows)]
    xd = (x.astype(F32) * _by_head(lanes, [t.dt for t in tabs])).astype(cdt)
    xe = (xd.astype(F32) * _by_head(lanes, [t.el for t in tabs])).astype(cdt)
    sb = s.astype(cdt)
    within = _by_head(lanes, [_mm((t.decay * scores).astype(cdt), xd)
                              for t in tabs])
    y = _mm(cm, sb) * _by_head(lanes, [t.e for t in tabs]) + within
    s = s * _by_head(lanes, [t.dec for t in tabs]) + _mm(bm, xe, _TN)
    return y.astype(cdt), sb, s


@jax.jit
def _chunk_backward(x, bm, cm, scores, dt_rows, g_rows, s0, dy, ds):
    """One chunk's cotangents from d loss / d y and d loss / d the state the
    NEXT chunk starts from (``ds``, float32): (dx; d dt and dg as a [1, C]
    row a head; the lane block's share of d(C B^T) [C, C], of dB and of dC
    [C, N] beside it; d loss / d the state this chunk starts from)."""
    cdt, heads = x.dtype, len(dt_rows)
    under, eye = _masks(x.shape[0])
    lanes = _head_lanes(heads)
    tabs = [_tables(dt_row, g_row, under, eye)
            for dt_row, g_row in zip(dt_rows, g_rows)]
    dt, e, el = (_by_head(lanes, [getattr(t, name) for t in tabs])
                 for name in ("dt", "e", "el"))
    dec = _by_head(lanes, [t.dec for t in tabs])
    xf, dyf = x.astype(F32), dy.astype(F32)
    xdf = (xf * dt).astype(cdt).astype(F32)  # as the forward rounded it
    xd, xe = xdf.astype(cdt), (xdf * el).astype(cdt)
    dye, dsb = (dyf * e).astype(cdt), ds.astype(cdt)

    # through y = e (C S0) + (L * scores) xd and S1 = dec S0 + B^T xe
    from_state = _mm(cm, s0)          # [C, 128]
    to_state = _mm(bm, dsb)           # [C, 128]
    dc = _mm(dye, s0, _NT)            # [C, N], the block's heads summed
    db = _mm(xe, dsb, _NT)
    ddec = jnp.sum(ds * s0.astype(F32), axis=0, keepdims=True)  # [1, 128]
    ds_before = _mm(cm, dye, _TN) + ds * dec

    dxd, dscores, dtable = [], jnp.zeros(scores.shape, F32), []
    for mask, t in zip(lanes, tabs):
        m = (t.decay * scores).astype(cdt)
        dy_k = dy if heads == 1 else jnp.where(mask, dy, jnp.zeros_like(dy))
        dm = _mm(dy_k, xd, _NT)       # [C, C]
        dxd.append(_mm(m, dy, _TN))
        dscores = dscores + t.decay * dm
        dtable.append(dm * scores * t.decay)  # d decay * decay
    dxd = _by_head(lanes, dxd) + to_state * el
    ddt_direct = _head_rowsum(lanes, dxd * xf)
    from_e = _head_rowsum(lanes, dyf * from_state * e)
    from_el = _head_rowsum(lanes, to_state * xdf * el)
    from_dec = _head_rowsum(lanes, ddec * dec)
    ddt, dg = [], []
    for k, table in enumerate(dtable):
        # gamma: from exp(gam), exp(last - gam), exp(last) and the table (row
        # sums minus column sums); g from gamma by the reversed running sum
        dgam = (from_e[k] - from_el[k]
                + jnp.sum(table, axis=1, keepdims=True)
                - _to_col(jnp.sum(table, axis=0, keepdims=True), eye))
        dlast = jnp.sum(from_el[k], axis=0, keepdims=True) + from_dec[k]
        dg.append(jnp.sum(jnp.where(under, dgam, 0.0), axis=0,
                          keepdims=True) + dlast)
        ddt.append(_to_row(ddt_direct[k], eye))
    return ((dxd * dt).astype(cdt), tuple(ddt), tuple(dg), dscores, db, dc,
            ds_before)


@jax.jit
def _scores_backward(bm, cm, dscores, db, dc):
    """(dB, dC) [C, N] float32 of a chunk: their own shares and d(C B^T)'s."""
    d = dscores.astype(bm.dtype)
    return db + _mm(d, cm, _TN), dc + _mm(d, bm)


# ---------------------------------------------------------------------------
# the two kernels
# ---------------------------------------------------------------------------
def _gate_rows(ref, c):
    """A chunk's [1, C] row of each head of the block."""
    return tuple(ref[k, c] for k in range(ref.shape[0]))


def _in_group(block, per_group):
    """The lane block's place in its B / C group."""
    return jax.lax.rem(block, np.int32(per_group))


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, g_ref, y_ref, h_ref,
                s_scr, scores_scr, *, chunk, per_step, per_group):
    block = pl.program_id(2)  # of 128 lanes: the heads it holds

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[block] = jnp.zeros(s_scr.shape[1:], F32)

    # C B^T: once for all heads of a group
    @pl.when(block == 0 if per_group is None
             else _in_group(block, per_group) == 0)
    def _():
        for c in range(per_step):
            rows = slice(c * chunk, (c + 1) * chunk)
            scores_scr[rows, :] = _chunk_scores(b_ref[0, rows, :],
                                                c_ref[0, rows, :])

    s = s_scr[block]
    for c in range(per_step):
        rows = slice(c * chunk, (c + 1) * chunk)
        # h: the state this chunk starts from, for the backward
        y_ref[0, rows, :], h_ref[0, c], s = _chunk_forward(
            x_ref[0, rows, :], b_ref[0, rows, :], c_ref[0, rows, :],
            scores_scr[rows, :], _gate_rows(dt_ref, c), _gate_rows(g_ref, c),
            s)
    s_scr[block] = s


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, g_ref, h_ref, dy_ref,
                dx_ref, ddt_ref, dg_ref, db_ref, dc_ref,
                ds_scr, scores_scr, dscores_scr, db_scr, dc_scr, *,
                chunk, per_step, per_group):
    block, blocks = pl.program_id(2), pl.num_programs(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[block] = jnp.zeros(ds_scr.shape[1:], F32)

    @pl.when(block == 0 if per_group is None
             else _in_group(block, per_group) == 0)
    def _():
        for c in range(per_step):
            rows = slice(c * chunk, (c + 1) * chunk)
            scores_scr[rows, :] = _chunk_scores(b_ref[0, rows, :],
                                                c_ref[0, rows, :])
        dscores_scr[:] = jnp.zeros_like(dscores_scr)
        db_scr[:] = jnp.zeros_like(db_scr)
        dc_scr[:] = jnp.zeros_like(dc_scr)

    ds = ds_scr[block]
    for c in reversed(range(per_step)):
        rows = slice(c * chunk, (c + 1) * chunk)
        dx_ref[0, rows, :], ddt, dg, dscores, db, dc, ds = _chunk_backward(
            x_ref[0, rows, :], b_ref[0, rows, :], c_ref[0, rows, :],
            scores_scr[rows, :], _gate_rows(dt_ref, c), _gate_rows(g_ref, c),
            h_ref[0, c], dy_ref[0, rows, :], ds)
        for k in range(len(ddt)):
            ddt_ref[k, c], dg_ref[k, c] = ddt[k], dg[k]
        dscores_scr[rows, :] += dscores
        db_scr[rows, :] += db
        dc_scr[rows, :] += dc
    ds_scr[block] = ds

    # dB, dC: summed over the heads of the group
    @pl.when(block == blocks - 1 if per_group is None
             else _in_group(block, per_group) == per_group - 1)
    def _():
        for c in range(per_step):
            rows = slice(c * chunk, (c + 1) * chunk)
            db, dc = _scores_backward(
                b_ref[0, rows, :], c_ref[0, rows, :], dscores_scr[rows, :],
                db_scr[rows, :], dc_scr[rows, :])
            db_ref[0, rows, :] = db.astype(db_ref.dtype)
            dc_ref[0, rows, :] = dc.astype(dc_ref.dtype)


# ---------------------------------------------------------------------------
# their calls: one grid and one set of block maps for both
# ---------------------------------------------------------------------------
class _Geometry(NamedTuple):
    chunk: int
    per_step: int  # chunks one grid step holds
    heads: int     # heads side by side in one block of 128 lanes
    blocks: int    # lane blocks of x: heads / ``heads``
    state: int     # N
    per_group: int = None  # lane blocks of a B / C group; None: one group


def _call(kernel, name, geo, batch, n_chunks, ins, outs, scratch,
          reverse=False):
    """``kernel`` over the grid (batch, blocks of chunks, lane blocks).
    ``ins`` are (kind, array) pairs, ``outs`` (kind, shape, dtype); a kind is
    the block a grid step gets: "x" rows of one lane block out of [batch,
    seq, heads * P], "bc" rows of [batch, seq, N] (of its group's N lanes
    out of [batch, seq, groups * N] with ``per_group``), "gate" the block's
    heads out of [batch * heads, chunks, 1, chunk], "h" the states of its
    chunks out of [batch * lane blocks, chunks, N, 128]."""
    rows = geo.chunk * geo.per_step
    n_blocks = n_chunks // geo.per_step
    blocks = np.int32(geo.blocks)

    def at(j):
        return np.int32(n_blocks - 1) - j if reverse else j

    def group(r):
        if geo.per_group is None:
            return _0
        return jax.lax.div(r, np.int32(geo.per_group))

    specs = {
        "x": pl.BlockSpec((1, rows, 128), lambda i, j, r: (i, at(j), r)),
        "bc": pl.BlockSpec((1, rows, geo.state),
                           lambda i, j, r: (i, at(j), group(r))),
        "gate": pl.BlockSpec((geo.heads, geo.per_step, 1, geo.chunk),
                             lambda i, j, r: (i * blocks + r, at(j), _0, _0)),
        "h": pl.BlockSpec((1, geo.per_step, geo.state, 128),
                          lambda i, j, r: (i * blocks + r, at(j), _0, _0)),
    }
    return pl.pallas_call(
        functools.partial(kernel, chunk=geo.chunk, per_step=geo.per_step,
                          per_group=geo.per_group),
        name=name,
        grid=(batch, n_blocks, geo.blocks),
        in_specs=[specs[kind] for kind, _ in ins],
        out_specs=[specs[kind] for kind, _, _ in outs],
        out_shape=[jax.ShapeDtypeStruct(shape, dtype)
                   for _, shape, dtype in outs],
        scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(*(x for _, x in ins))


# jitted: the model traces the scan once a layer and once more under its
# recomputation
@functools.partial(jax.jit, static_argnums=(5,))
def _forward(x, bm, cm, dt, g, geo):
    batch, n = x.shape[0], dt.shape[1]
    rows = geo.chunk * geo.per_step
    return _call(
        _fwd_kernel, "ssd_scan_fwd", geo, batch, n,
        [("x", x), ("bc", bm), ("bc", cm), ("gate", dt), ("gate", g)],
        [("x", x.shape, x.dtype),
         ("h", (batch * geo.blocks, n, geo.state, 128), x.dtype)],
        [pltpu.VMEM((geo.blocks, geo.state, 128), F32),
         pltpu.VMEM((rows, geo.chunk), F32)])


@functools.partial(jax.jit, static_argnums=(7,))
def _backward(x, bm, cm, dt, g, h, dy, geo):
    batch, n = x.shape[0], dt.shape[1]
    rows = geo.chunk * geo.per_step
    return tuple(_call(
        _bwd_kernel, "ssd_scan_bwd", geo, batch, n,
        [("x", x), ("bc", bm), ("bc", cm), ("gate", dt), ("gate", g),
         ("h", h), ("x", dy)],
        [("x", x.shape, x.dtype), ("gate", dt.shape, F32),
         ("gate", g.shape, F32), ("bc", bm.shape, bm.dtype),
         ("bc", cm.shape, cm.dtype)],
        [pltpu.VMEM((geo.blocks, geo.state, 128), F32),
         pltpu.VMEM((rows, geo.chunk), F32),
         pltpu.VMEM((rows, geo.chunk), F32),
         pltpu.VMEM((rows, geo.state), F32),
         pltpu.VMEM((rows, geo.state), F32)],
        reverse=True))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan_vmem(x, bm, cm, dt, g, geo):
    """y as x, from x [batch, seq, heads * P], B, C [batch, seq, groups * N]
    and the gates [batch * heads, chunks, 1, chunk] float32."""
    return _forward(x, bm, cm, dt, g, geo)[0]


def _scan_vmem_fwd(x, bm, cm, dt, g, geo):
    y, h = _forward(x, bm, cm, dt, g, geo)
    return y, (x, bm, cm, dt, g, h)


def _scan_vmem_bwd(geo, res, dy):
    x, bm, cm, dt, g, h = res
    dx, ddt, dg, db, dc = _backward(x, bm, cm, dt, g, h, dy.astype(x.dtype),
                                    geo)
    return dx, db, dc, ddt, dg


_scan_vmem.defvjp(_scan_vmem_fwd, _scan_vmem_bwd)


def _scan_xla(x, bm, cm, dt, g, chunk):
    """The same chunks as ``jax.numpy`` expressions, float32, one chunk of
    all heads at a time under ``lax.scan``. x [b, seq, H, P], B, C [b, seq,
    G, N], dt, g [b, seq, H]; head h reads group h // (H / G)."""
    b, seq, heads, p = x.shape
    groups, n_state = bm.shape[2], bm.shape[3]
    n, rep = seq // chunk, heads // groups

    def chunks(a, *tail):  # [b, seq, ...] -> [n, b, chunk, ...]
        return jnp.moveaxis(a.astype(F32).reshape(b, n, chunk, *tail), 1, 0)

    under = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(s, inputs):  # s [b, G, rep, P, N]
        xc, bc, cc, dtc, gc = inputs
        gam = jnp.cumsum(gc, axis=1)  # [b, chunk, G, rep]
        last = gam[:, -1:]
        gap = gam[:, :, None] - gam[:, None, :]  # [b, i, j, G, rep]
        decay = jnp.where(under[None, :, :, None, None],
                          jnp.exp(jnp.where(under[None, :, :, None, None],
                                            gap, 0.0)), 0.0)
        scores = jnp.einsum("bign,bjgn->bijg", cc, bc)
        xd = xc * dtc[..., None]
        y = (jnp.einsum("bign,bgrpn->bigrp", cc, s)
             * jnp.exp(gam)[..., None]
             + jnp.einsum("bijgr,bjgrp->bigrp",
                          decay * scores[..., None], xd))
        s = (s * jnp.exp(last[:, 0])[..., None, None]
             + jnp.einsum("bjgrp,bjgn->bgrpn",
                          xd * jnp.exp(last - gam)[..., None], bc))
        return s, y

    s0 = jnp.zeros((b, groups, rep, p, n_state), F32)
    _, y = jax.lax.scan(one, s0, (
        chunks(x, groups, rep, p), chunks(bm, groups, n_state),
        chunks(cm, groups, n_state), chunks(dt, groups, rep),
        chunks(g, groups, rep)))
    return jnp.moveaxis(y, 0, 1).reshape(b, seq, heads, p).astype(x.dtype)


def _refusal(heads, p, groups, n_state, chunk):
    """Why the kernels cannot take these shapes, or None where they can."""
    if p > 128 or 128 % p or (heads * p) % 128:
        return "heads_not_blocks_of_128_lanes"
    if (heads // groups * p) % 128:
        return "group_not_blocks_of_128_lanes"
    if n_state % 128:
        return "state_not_blocks_of_128_lanes"
    if chunk % 128:
        return "chunk_not_blocks_of_128_lanes"
    return None


def _per_step(n_chunks, chunk):
    want = max(1, STEP_ROWS // chunk)
    return want if n_chunks % want == 0 else 1


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------
def ssd_scan(x, dt, A_log, B, C, D, *, chunk=256, heads_published=None):
    """The Mamba-2 scan over whole sequences, state zero at the start.

    x [batch, seq, heads, P] as the conv leaves it; dt [batch, seq, heads]
    the step AFTER its softplus; ``A_log`` and ``D`` [heads]; B, C [batch,
    seq, groups, N], head h reading group ``h // (heads / groups)``. Returns
    y [batch, seq, heads, P] in x's type: S_t = exp(-exp(A_log) dt_t)
    S_(t-1) + dt_t x_t B_t^T, y_t = S_t C_t + D x_t. The decay, dt and the
    state are float32; the skip ``D x`` is added outside the kernels.

    A sequence that is not whole chunks is padded with steps of dt = 0
    (which leave the state as it is) and the padding's rows dropped. P a
    divisor of 128 with a group's heads x P whole blocks of 128 lanes, N
    and the chunk whole blocks of 128: the two kernels ``ssd_scan_fwd`` /
    ``ssd_scan_bwd``; other shapes the chunked ``jax.numpy`` form, counted
    as ``ssd_scan_fallbacks``. Each trace leaves one ``ssd_chunks`` event in
    the flight recorder (``heads_published``: of the deployment, where this
    chip holds a share of them)."""
    b, seq, heads, p = x.shape
    groups, n_state = B.shape[2], B.shape[3]
    if heads % groups or C.shape != B.shape or dt.shape != (b, seq, heads):
        raise ValueError(
            f"ssd_scan: x {x.shape}, dt {dt.shape}, B {B.shape}, C {C.shape}")
    chunk = min(chunk, -(-seq // 8) * 8)
    pad = -seq % chunk
    why = _refusal(heads, p, groups, n_state, chunk)

    from ..core import dispatch
    dispatch._count_ssd_chunks(
        why, seq=seq, chunk=chunk, heads=heads,
        heads_published=heads_published or heads, groups=groups)

    dt = dt.astype(F32)
    g = -jnp.exp(A_log.astype(F32)) * dt
    skip = (x.astype(F32) * D.astype(F32)[:, None]).astype(x.dtype)
    if pad:
        x, B, C, dt, g = (jnp.pad(a, ((0, 0), (0, pad))
                                  + ((0, 0),) * (a.ndim - 2))
                          for a in (x, B, C, dt, g))
    n = (seq + pad) // chunk
    if why is None:
        per_lane_block = 128 // p
        blocks = heads // per_lane_block
        geo = _Geometry(chunk, _per_step(n, chunk), per_lane_block, blocks,
                        n_state, blocks // groups if groups > 1 else None)

        def gate(a):  # [b, seq, heads] -> [b * heads, chunks, 1, chunk]
            a = jnp.moveaxis(a.reshape(b, n, chunk, heads), 3, 1)
            return a.reshape(b * heads, n, 1, chunk)

        def lanes(a):  # [b, seq, groups, N] -> [b, seq, groups * N]
            if groups == 1:
                return a[:, :, 0]
            return a.reshape(b, n * chunk, groups * n_state)

        y = _scan_vmem(x.reshape(b, n * chunk, heads * p), lanes(B),
                       lanes(C), gate(dt), gate(g), geo)
        y = y.reshape(b, n * chunk, heads, p)
    else:
        y = _scan_xla(x, B, C, dt, g, chunk)
    return y[:, :seq] + skip
