"""Recurrent-state ("linear attention") layers: the gated delta rule as a
chunked scan, the short causal convolution before it, the gated norm after,
seven Pallas kernels in all.

The gated delta rule keeps one [d_k, d_v] float32 state S per head:

    S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T
    o_t = S^T q_t

Computed token by token it is 8,192 dependent steps of vector work. Here a
sequence is cut into chunks of C tokens. Inside a chunk the rule is matrix
products (the WY form): with gamma the running sum of g inside the chunk and
A[i, j] = beta_i exp(gamma_i - gamma_j) k_i.k_j for j < i, the corrections of
a chunk are U = (I + A)^-1 beta (V - exp(gamma) K S_0), its outputs
exp(gamma) Q S_0 + (mask(Q K^T) exp(gamma_i - gamma_j)) U and its last state
exp(gamma_C) S_0 + (exp(gamma_C - gamma) K)^T U.

Everything a chunk needs is made in VMEM from the chunk's own rows of q, k,
v, g, beta, by three Pallas kernels that read the heads where the layer left
them ([batch, seq, heads * d]: a head is a block of lanes; key head
``value head // rep`` by the index map) and walk a grid of (batch x key
heads, blocks of chunks). A grid step holds a key head and the ``rep`` value
heads it serves: their state chains are independent, so each head's
products are written beside the others' for the scheduler to overlap, and
what depends on the key head alone (the unit rows of q and k, Q K^T, K K^T)
is made once a chunk. The rows of q and k are taken to unit length there
too (and q scaled by d_k^-0.5): done by XLA, each norm went through HBM as
a float32 array of the rows' size.

``gated_delta_rule_fwd_inverse``  T = (I + A)^-1 of every chunk, by doubling,
    the chunks of a grid step level by level together (the products of one
    inverse wait for each other; PERF.md section 6, PR 29); no state, so a
    recomputed layer keeps T (``KEEP_NAME``) and skips it.
``gated_delta_rule_fwd``  the decay tables, w = T beta exp(gamma) K, u0 = T
    beta V, the masked scores, then the pass over the chunks with S in VMEM
    scratch; writes o and, for the backward, U and the states the chunks
    start from.
``gated_delta_rule_bwd``  the chunks in reverse with dS in VMEM: makes the
    chunk's operands again from T, carries the cotangents through them (dT
    from dw and du0, dA = -T^T dT T^T, the decay table's to dg by the
    reversed running sum of row sums minus column sums) and writes dq, dk
    (summed over the value heads of a key head inside the grid step, head 0
    first, then through the norm), dv, dg, dbeta.

Round the rule, two passes over rows, each as a forward and a backward
kernel on (rows, 128 x n) lane blocks of [batch, seq, lanes], read where the
projection or the rule left them (the conv's 8,192 channels and the gate's
4,096 out of the 12,288-wide projection by lane-block offset), all
per-channel and per-head work in VMEM in float32, each result written once:

``short_conv_silu_fwd``  y_t = silu(sum_i w[:, i] x_(t-3+i)), zeros before a
    SEQUENCE's start; the three rows before a block come from an 8-row block
    of the same array, not from a padded copy.
``short_conv_silu_bwd``  the conv's sums again, dc = dy silu'(c), dx = the
    same taps read ahead (three rows of dc after the block made from 8-row
    blocks of x and dy), dw summed in float32 in a block that stays resident
    over batch and rows.
``gated_rms_norm_fwd``  a head is one block of 128 lanes: mean of squares,
    rsqrt, gain and silu(z) in registers; no norm factor leaves VMEM.
``gated_rms_norm_bwd``  do, dz and the gain's float32 partial sums from o,
    z, dy in one pass.

Shapes that are not whole blocks of 128 lanes and rows of 8 take the same
arithmetic as ``jax.numpy`` expressions (``mixer_pass.path``: "xla"). Under
the two scopes XLA keeps: the conv's taps cast and turned to [4, channels],
the gain as [1, 128], the last sums of dw ([32, channels] -> [channels, 4])
and of the gain's gradient ([8, lanes] -> [128]), and the concatenation and
``pad``s that take the pieces of dx and dz back to the projection's width,
which it fuses with their sum.

Operands of the products are in the type the inputs come in (bf16 under
AMP-O2), sums, gates, decays, the inverse and the state in float32. The
inverse's own products (and dA's) are three bf16 passes, written out as a
split into a high and a low half; float32 inputs get float32 products there.
Off the TPU the kernels run in interpret mode.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..incubate.recompute import KEEP_NAME

F32 = jnp.float32
L2_EPSILON = 1e-6  # under the root of a q or k row's norm
STEP_ROWS = 512  # rows of a sequence one grid step of the kernels holds
# what a grid step makes of its value heads, at most: 8 heads of 128 at 512
# rows and chunk 64 fit the kernels' 16 MiB of scoped VMEM, 16 do not (AOT
# compiles for a v5e)
STEP_BYTES = 4 << 20
_0 = np.int32(0)  # index-map literal; Python ints trace to i64 under x64


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# the layers round the rule
# ---------------------------------------------------------------------------
# The short conv and the gated norm are passes over rows: every channel (conv)
# or head of 128 lanes (norm) for itself. Their kernels take (ROW_BLOCK,
# LANE_BLOCK) blocks of [batch, seq, lanes] where the projection or the rule
# left them and work through a block in strips of some rows of one 128-lane
# block, which stay in registers; the conv's three rows before a block (and,
# in its backward, after) come from 8-row blocks of the same array.
ROW_BLOCK = 512
LANE_BLOCK = 1024
CONV_STRIP = 64   # rows: the conv's backward keeps eight strips' worth live
NORM_STRIP = 128  # the norm's sums over 128 lanes wait on the cross-lane
#                   unit, and a longer strip has more to do meanwhile
HALO = 8


class _Walk(NamedTuple):
    rows: int   # of a grid step's block,
    lanes: int  # its lanes,
    sub: int    # and the rows of a strip


def _walk(seq, strip, lanes, *starts):
    """(how the kernels walk ``lanes`` lanes of rows [batch, seq, wider] that
    are read or written from the lanes ``starts`` on, None), or (None, why
    they cannot)."""
    if lanes % 128 or any(start % 128 for start in starts):
        return None, "lanes_not_blocks_of_128"
    if seq % HALO:
        return None, "seq_not_rows_of_8"
    rows = max(r for r in range(HALO, min(seq, ROW_BLOCK) + 1, HALO)
               if seq % r == 0)
    lane_block = max(n for n in range(128, LANE_BLOCK + 1, 128)
                     if not any(x % n for x in (lanes, *starts)))
    sub = max(n for n in (8, 16, 32, strip) if rows % n == 0)
    return _Walk(rows, lane_block, sub), None


def _pass_event(site, walk, why, batch, seq, lanes):
    from ..profiler import trace
    trace.emit("mixer_pass", site=site, path="xla" if why else "vmem",
               rows=batch * seq, lanes=lanes,
               row_block=walk.rows if walk else 0,
               **({"why": why} if why else {}))


def _over_strips(walk, strip):
    """``strip(lanes)`` for each 128-lane block of the grid step's block,
    as a loop: the strip's work is traced once a kernel, not once a block
    (a step's trace is part of every run's set-up)."""
    def body(_, k):
        strip(pl.ds(pl.multiple_of(k * 128, 128), 128))
        return k + np.int32(1)

    jax.lax.fori_loop(0, walk.lanes // 128, body, _0)


def _strip_rows(walk, j):
    return pl.ds(pl.multiple_of(j * walk.sub, walk.sub), walk.sub)


def _over_strip_rows(walk, step, carry, start=0):
    """``carry = step(j, carry)`` for the strips ``start``.. of a block, j an
    int32 of the loop's own (under x64 fori_loop's index is an i64 in the
    jaxpr and an i32 in Mosaic)."""
    def body(_, counted):
        j, carry = counted
        return j + np.int32(1), step(j, carry)

    return jax.lax.fori_loop(start, walk.rows // walk.sub, body,
                             (np.int32(start), carry))[1]


def _sigmoid(x):
    """1 / (1 + exp(-x)) as one pass through the transcendental unit and two
    vector operations (the quotient written out costs ten)."""
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def _silu_slope(x, sig):
    """d silu(x) / dx from x and sigmoid(x)."""
    return sig * (1.0 + x * (1.0 - sig))


def _fold(x):
    """[n, 128] -> [8, 128]: the sum of its 8-row tiles (a sum over rows
    that is finished outside the kernel)."""
    total = x[:8]
    for i in range(8, x.shape[0], 8):
        total = total + x[i:i + 8]
    return total


def _behind(before, x, by):
    """Row t of ``x`` [n, 128] replaced by row t - by, the first rows by the
    last of ``before`` [8, 128]; float32 (odd shifts of packed rows are
    awkward)."""
    if by == 0:
        return x
    return pltpu.roll(jnp.concatenate([before, x], axis=0), np.int32(by),
                      axis=0)[HALO:]


def _ahead(x, after, by):
    """Row t of ``x`` replaced by row t + by, the last by the first of
    ``after`` [8, 128]."""
    if by == 0:
        return x
    n = x.shape[0]
    return pltpu.roll(jnp.concatenate([x, after], axis=0),
                      np.int32(n + HALO - by), axis=0)[:n]


def _conv_sums(before, x, w, biased=False):
    """(the causal conv's sums for rows ``x`` that follow ``before``, the
    operand of each tap); ``w`` the taps as [1, 128] rows and, ``biased``,
    the bias as one more."""
    k = len(w) - biased
    operands = [_behind(before, x, k - 1 - i) for i in range(k)]
    total = operands[0] * w[0]
    for operand, wi in zip(operands[1:], w[1:k]):
        total = total + operand * wi
    if biased:
        total = total + w[k]
    return total, operands


def _conv_fwd_kernel(x_ref, before_ref, w_ref, y_ref, *, walk, biased=False):
    start = pl.program_id(2) == 0  # of a sequence: zeros before it

    def strip(lanes):
        w = [w_ref[i:i + 1, lanes] for i in range(w_ref.shape[0])]

        def step(j, before):
            rows = _strip_rows(walk, j)
            x = x_ref[0, rows, lanes].astype(F32)
            c = _conv_sums(before, x, w, biased)[0]
            y_ref[0, rows, lanes] = (c * _sigmoid(c)).astype(y_ref.dtype)
            return x[-HALO:]

        _over_strip_rows(walk, step, jnp.where(
            start, 0.0, before_ref[0, :, lanes].astype(F32)))

    _over_strips(walk, strip)


def _conv_bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                     w_ref, dx_ref, dw_ref, *, walk, biased=False):
    """dx of a strip's rows needs dc three rows ahead: the walk writes the
    rows of strip j - 1 when it has made dc of strip j, and those of the
    last from the 8 rows after the block. ``biased``: the last row of
    ``w_ref`` is the bias, and the last sums of ``dw_ref`` its gradient's."""
    batch, block, last = (pl.program_id(1), pl.program_id(2),
                          pl.num_programs(2) - 1)
    k, steps = w_ref.shape[0] - biased, walk.rows // walk.sub

    @pl.when((batch == 0) & (block == 0))
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, F32)

    def strip(lanes):
        w = [w_ref[i:i + 1, lanes] for i in range(w_ref.shape[0])]

        def dconv(before, x, dy):
            c, operands = _conv_sums(before, x, w, biased)
            return dy * _silu_slope(c, _sigmoid(c)), operands

        def read(ref, j):
            return ref[0, _strip_rows(walk, j), lanes].astype(F32)

        def write_dx(j, dc, dc_after):
            dx = _ahead(dc, dc_after, k - 1) * w[0]
            for i in range(1, k):
                dx = dx + _ahead(dc, dc_after, k - 1 - i) * w[i]
            dx_ref[0, _strip_rows(walk, j), lanes] = dx.astype(dx_ref.dtype)

        def first(j, before):
            x = read(x_ref, j)
            dc, operands = dconv(before, x, read(dy_ref, j))
            return x[-HALO:], dc, ([_fold(dc * o) for o in operands]
                                   + [_fold(dc)] * biased)

        def step(j, carry):
            before, dc_behind, sums = carry
            before, dc, more = first(j, before)
            write_dx(j - 1, dc_behind, dc[:HALO])
            return before, dc, [s + m for s, m in zip(sums, more)]

        before, dc, sums = _over_strip_rows(walk, step, first(_0, jnp.where(
            block == 0, 0.0, before_ref[0, :, lanes].astype(F32))), start=1)
        dc_after = jnp.where(
            block == last, 0.0,
            dconv(before, after_ref[0, :, lanes].astype(F32),
                  dy_after_ref[0, :, lanes].astype(F32))[0])
        write_dx(np.int32(steps - 1), dc, dc_after)
        for i, total in enumerate(sums):
            dw_ref[i * 8:(i + 1) * 8, lanes] += total

    _over_strips(walk, strip)


def _norm_parts(o_ref, z_ref, rows, lanes, eps):
    """(o, its rows' 1 / rms, z, sigmoid(z)) of a strip of one head, float32."""
    of = o_ref[0, rows, lanes].astype(F32)
    zf = z_ref[0, rows, lanes].astype(F32)
    r = jax.lax.rsqrt(jnp.mean(of * of, axis=1, keepdims=True) + eps)
    return of, r, zf, _sigmoid(zf)


def _norm_fwd_kernel(o_ref, z_ref, w_ref, y_ref, *, walk, eps):
    w = w_ref[...]

    def strip(lanes):
        def step(j, carry):
            rows = _strip_rows(walk, j)
            of, r, zf, sig = _norm_parts(o_ref, z_ref, rows, lanes, eps)
            y_ref[0, rows, lanes] = (of * r * w * (zf * sig)).astype(
                y_ref.dtype)
            return carry

        _over_strip_rows(walk, step, _0)

    _over_strips(walk, strip)


def _norm_bwd_kernel(o_ref, z_ref, dy_ref, w_ref, do_ref, dz_ref, dw_ref, *,
                     walk, eps):
    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, F32)

    w = w_ref[...]

    def strip(lanes):
        def step(j, total):
            rows = _strip_rows(walk, j)
            of, r, zf, sig = _norm_parts(o_ref, z_ref, rows, lanes, eps)
            dy = dy_ref[0, rows, lanes].astype(F32)
            unit = of * r
            dn = dy * (zf * sig)  # of the normalised rows times the gain
            dunit = dn * w
            # r is a row's own factor, so the second mean is taken of the
            # rows as they came and does not wait for the first
            along = r * jnp.mean(dunit * of, axis=1, keepdims=True)
            do_ref[0, rows, lanes] = (r * (dunit - unit * along)).astype(
                do_ref.dtype)
            dz_ref[0, rows, lanes] = (
                dy * (unit * w) * _silu_slope(zf, sig)).astype(dz_ref.dtype)
            return total + _fold(dn * unit)

        dw_ref[:, lanes] += _over_strip_rows(
            walk, step, jnp.zeros((8, 128), F32))

    _over_strips(walk, strip)


def _row_call(kernel, name, walk, shape, ins, outs, sums=False):
    """``kernel`` over the grid (lane blocks, batch, row blocks) of ``shape``
    [batch, seq, lanes]. ``ins`` are (kind, array, its first lane), ``outs``
    (kind, shape, dtype); a kind is the block a grid step gets: "rows" its
    (rows, lanes) block, "before" and "after" the 8 rows on either side of
    it (the sequence's own first or last 8 at its ends, where the kernels
    put zeros), "lane" all rows of its lanes out of a small [n, lanes]
    array, "all" a small array whole. With ``sums`` the "lane" outputs stay
    resident over batch and rows and gather sums."""
    batch, seq, lanes = shape
    per, n_halos = np.int32(walk.rows // HALO), np.int32(seq // HALO)

    def spec(kind, of, offset=0):
        off = np.int32(offset // walk.lanes)
        if kind == "rows":
            return pl.BlockSpec((1, walk.rows, walk.lanes),
                                lambda c, b, r: (b, r, c + off))
        if kind == "before":
            return pl.BlockSpec(
                (1, HALO, walk.lanes),
                lambda c, b, r: (b, jnp.maximum(r * per - 1, 0), c + off))
        if kind == "after":
            return pl.BlockSpec(
                (1, HALO, walk.lanes),
                lambda c, b, r: (b, jnp.minimum((r + 1) * per, n_halos - 1),
                                 c + off))
        if kind == "lane":
            return pl.BlockSpec((of[0], walk.lanes),
                                lambda c, b, r: (_0, c + off))
        return pl.BlockSpec(of, lambda c, b, r: (_0, _0))

    return pl.pallas_call(
        kernel, name=name,
        grid=(lanes // walk.lanes, batch, seq // walk.rows),
        in_specs=[spec(kind, x.shape, offset) for kind, x, offset in ins],
        out_specs=[spec(kind, of) for kind, of, _ in outs],
        out_shape=[jax.ShapeDtypeStruct(of, dtype) for _, of, dtype in outs],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            ("arbitrary" if sums else "parallel",) * 3)),
        interpret=_interpret(),
    )(*(x for _, x, _ in ins))


# jitted, as the rule's calls are: the model traces a mixer once a layer
@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _conv_forward(x, taps, walk, start, width, biased=False):
    """The conv of x's lanes ``start``.. by the same lanes of ``taps``
    [kernel, channels] float32 (``biased``: and the bias, one row more),
    ``width`` of them."""
    shape = (x.shape[0], x.shape[1], width)
    return _row_call(
        functools.partial(_conv_fwd_kernel, walk=walk, biased=biased),
        "short_conv_silu_fwd",
        walk, shape,
        [("rows", x, start), ("before", x, start), ("lane", taps, start)],
        [("rows", shape, x.dtype)])[0]


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _conv_backward(x, taps, dy, walk, start, biased=False):
    """(dx, d taps [kernel, width] float32) of the lanes ``dy`` is the
    cotangent of; ``biased``: the bias and its gradient are the last row."""
    k, shape = taps.shape[0], dy.shape
    dx, dw = _row_call(
        functools.partial(_conv_bwd_kernel, walk=walk, biased=biased),
        "short_conv_silu_bwd",
        walk, shape,
        [("rows", x, start), ("before", x, start), ("after", x, start),
         ("rows", dy, 0), ("after", dy, 0), ("lane", taps, start)],
        [("rows", shape, x.dtype), ("lane", (8 * k, shape[2]), F32)],
        sums=True)
    # the kernel leaves each tap's sum as 8 partial rows
    return dx, dw.reshape(k, 8, shape[2]).sum(1)


def _pieces(widths):
    """(first lane, width) of each."""
    return list(zip(np.cumsum((0,) + widths[:-1]).tolist(), widths))


def _taps(weight, bias):
    """[kernel, channels] float32 rows of taps; the bias, if any, one more."""
    taps = weight.astype(F32).T
    if bias is None:
        return taps
    return jnp.concatenate([taps, bias.astype(F32)[None]], axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_vmem(x, weight, bias, walk, widths):
    taps = _taps(weight, bias)
    return tuple(_conv_forward(x, taps, walk, start, width, bias is not None)
                 for start, width in _pieces(widths))


def _conv_vmem_fwd(x, weight, bias, walk, widths):
    return _conv_vmem(x, weight, bias, walk, widths), (x, weight, bias)


def _conv_vmem_bwd(walk, widths, res, dys):
    x, weight, bias = res
    taps = _taps(weight, bias)
    dxs, dws = zip(*(_conv_backward(x, taps, dy, walk, start,
                                    bias is not None)
                     for (start, _), dy in zip(_pieces(widths), dys)))
    # x's further lanes (what the caller keeps beside the conv's channels)
    # have no part in it
    dx = jnp.pad(jnp.concatenate(dxs, axis=-1),
                 ((0, 0), (0, 0), (0, x.shape[2] - weight.shape[0])))
    dw = jnp.concatenate(dws, axis=-1)
    if bias is None:
        return dx, dw.T.astype(weight.dtype), None
    return dx, dw[:-1].T.astype(weight.dtype), dw[-1].astype(bias.dtype)


_conv_vmem.defvjp(_conv_vmem_fwd, _conv_vmem_bwd)


def _causal_taps(x, weight, ahead=False):
    """sum_i weight[:, i] * x shifted: behind by K-1-i rows (the causal conv),
    or ``ahead`` by as many (its transpose), zeros past the ends; one fused
    pass over a padded copy, sums in float32. x [batch, seq, channels]."""
    k, seq = weight.shape[-1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (0, k - 1) if ahead else (k - 1, 0), (0, 0)))
    wf = weight.astype(F32)
    return sum(xp[:, (k - 1 - i if ahead else i):, :][:, :seq].astype(F32)
               * wf[:, i] for i in range(k))


@jax.custom_vjp
def _conv_xla(x, weight):
    return jax.nn.silu(_causal_taps(x, weight)).astype(x.dtype)


def _conv_xla_fwd(x, weight):
    return _conv_xla(x, weight), (x, weight)


def _conv_xla_bwd(res, dy):
    x, weight = res
    k, seq = weight.shape[-1], x.shape[1]
    c = _causal_taps(x, weight)
    sig = jax.nn.sigmoid(c)
    dc = (dy.astype(F32) * _silu_slope(c, sig)).astype(x.dtype)
    dx = _causal_taps(dc, weight, ahead=True).astype(x.dtype)
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    dw = jnp.stack([jnp.einsum("bsc,bsc->c", dc, xp[:, i:i + seq],
                               preferred_element_type=F32)
                    for i in range(k)], axis=-1)
    return dx, dw.astype(weight.dtype)


_conv_xla.defvjp(_conv_xla_fwd, _conv_xla_bwd)


def short_conv_silu(x, weight, splits=None, bias=None):
    """silu(causal depthwise conv) over the first ``channels`` lanes of x
    [batch, seq, channels or more] with ``weight`` [channels, kernel] and,
    where given, ``bias`` [channels]: y_t = silu(sum_i w[:, i] x_(t-K+1+i) +
    b), zeros before the sequence's start; y [batch, seq, channels] in x's
    type, sums in float32. With ``splits`` (widths that add up to
    ``channels``) y comes as that many arrays [batch, seq, width], side by
    side.

    Where the channels (and the splits) are whole blocks of 128 lanes and
    the sequence whole rows of 8, two kernels do it, ``short_conv_silu_fwd``
    and ``_bwd`` (which makes the conv again, reads the same taps ahead for
    dx and sums dw in float32): x is read where the projection left it, each
    piece of y written where its reader takes it and each piece's cotangent
    read where it was left, so XLA slices nothing out and puts nothing
    together. Other shapes take the same arithmetic as ``jax.numpy`` (a
    padded copy, and a written-out backward: derived, it is a pad and a
    reduction per tap). Each trace leaves one ``mixer_pass`` event."""
    batch, seq, channels = x.shape[0], x.shape[1], weight.shape[0]
    widths = tuple(splits or (channels,))
    pieces = _pieces(widths)
    if weight.shape[1] > HALO + 1:
        walk, why = None, "taps_over_8_rows"
    else:
        walk, why = _walk(seq, CONV_STRIP, channels,
                          *(start for start, _ in pieces))
    _pass_event("short_conv_silu", walk, why, batch, seq, channels)
    if walk is None:
        if bias is None:
            y = _conv_xla(x[..., :channels], weight)
        else:  # differentiated by JAX: a pad and a reduction per tap
            y = jax.nn.silu(_causal_taps(x[..., :channels], weight)
                            + bias.astype(F32)).astype(x.dtype)
        ys = tuple(y[..., start:start + width] for start, width in pieces)
    else:
        ys = _conv_vmem(x, weight, bias, walk, widths)
    return ys if splits else ys[0]


def l2_normalize(x, *, epsilon=L2_EPSILON):
    """x / |x| over the last axis, in float32."""
    xf = x.astype(F32)
    return (xf * jax.lax.rsqrt(jnp.square(xf).sum(-1, keepdims=True)
                               + epsilon)).astype(x.dtype)


def decay_and_beta(a, b, a_log, dt_bias):
    """(g, beta) of the gated delta rule, float32: the log decay
    g = -exp(A_log) softplus(a + dt_bias) and the step beta = sigmoid(b),
    one of each per value head and token."""
    g = -jnp.exp(a_log.astype(F32)) * jax.nn.softplus(
        a.astype(F32) + dt_bias.astype(F32))
    return g, jax.nn.sigmoid(b.astype(F32))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _norm_forward(o, z, weight, walk, eps):
    return _row_call(
        functools.partial(_norm_fwd_kernel, walk=walk, eps=eps),
        "gated_rms_norm_fwd", walk, o.shape,
        [("rows", o, 0), ("rows", z, z.shape[2] - o.shape[2]),
         ("all", weight.astype(F32)[None], 0)],
        [("rows", o.shape, o.dtype)])[0]


@functools.partial(jax.jit, static_argnums=(4, 5))
def _norm_backward(o, z, weight, dy, walk, eps):
    lanes, gate = o.shape[2], z.shape[2] - o.shape[2]
    do, dz, dw = _row_call(
        functools.partial(_norm_bwd_kernel, walk=walk, eps=eps),
        "gated_rms_norm_bwd", walk, o.shape,
        [("rows", o, 0), ("rows", z, gate), ("rows", dy, 0),
         ("all", weight.astype(F32)[None], 0)],
        [("rows", o.shape, o.dtype), ("rows", o.shape, z.dtype),
         ("lane", (8, lanes), F32)], sums=True)
    # the gain's sum is left as 8 partial rows of every head; the lanes of z
    # before the gate (what the caller keeps there) have no part in it
    return (do, jnp.pad(dz, ((0, 0), (0, 0), (gate, 0))),
            dw.reshape(-1, weight.shape[0]).sum(0).astype(weight.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _norm_vmem(o, z, weight, walk, eps):
    return _norm_forward(o, z, weight, walk, eps)


def _norm_vmem_fwd(o, z, weight, walk, eps):
    return _norm_vmem(o, z, weight, walk, eps), (o, z, weight)


def _norm_vmem_bwd(walk, eps, res, dy):
    return _norm_backward(*res, dy, walk, eps)


_norm_vmem.defvjp(_norm_vmem_fwd, _norm_vmem_bwd)


def _norm_xla(o, z, weight, epsilon):
    of = o.astype(F32).reshape(*o.shape[:-1], -1, weight.shape[0])
    var = jnp.mean(jnp.square(of), axis=-1, keepdims=True)
    y = of * jax.lax.rsqrt(var + epsilon) * weight.astype(F32)
    return (y * jax.nn.silu(z.astype(F32).reshape(of.shape))).astype(
        o.dtype).reshape(o.shape)


def _gate_then_norm_xla(o, z, weight, epsilon, group=None):
    gated = o.astype(F32) * jax.nn.silu(z.astype(F32))
    if group is not None:  # [.., groups, group]: a statistic a group
        gated = gated.reshape(*gated.shape[:-1], -1, group)
        var = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
        normed = (gated * jax.lax.rsqrt(var + epsilon)).reshape(o.shape)
        return (normed * weight.astype(F32)).astype(o.dtype)
    var = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
    return (gated * jax.lax.rsqrt(var + epsilon)
            * weight.astype(F32)).astype(o.dtype)


def gated_rms_norm(o, z, weight, *, epsilon=1e-6, gate_first=False,
                   group=None):
    """o / rms(o) * weight * silu(z), head by head: o [batch, seq, heads *
    d] as the rule leaves it, a head being ``d = len(weight)`` lanes; z the
    LAST heads * d lanes of [batch, seq, heads * d or more]. Statistics and
    the gate in float32; the gain is not zero-centred.

    ``gate_first`` is the other order: g = o * silu(z), then g / rms(g) *
    weight with ONE statistic over all of o's lanes and a gain of that
    width (a row's sum crosses the 128-lane blocks the kernels below work
    in: it runs as the ``jax.numpy`` expression, ``mixer_pass.why``
    ``statistic_over_all_lanes``); with ``group``, one statistic for each
    run of ``group`` lanes (``why`` ``statistic_per_group``), the gain
    still of all lanes.

    Where d is one block of 128 lanes and the sequence whole rows of 8,
    two kernels do it (``gated_rms_norm_fwd``, ``_bwd``: do, dz and the
    gain's gradient in one pass), a head's mean of squares staying in VMEM.
    Other shapes take the ``jax.numpy`` expression over [.., heads, d].
    Each trace leaves one ``mixer_pass`` event."""
    batch, seq, lanes = o.shape
    d, gate = weight.shape[0], z.shape[2] - lanes
    if gate_first:
        if d != lanes or (group is not None and lanes % group):
            raise ValueError(f"gate-first norm: a gain of {d} for {lanes}"
                             f" in groups of {group}")
        _pass_event("gated_rms_norm", None,
                    "statistic_over_all_lanes" if group is None
                    else "statistic_per_group", batch, seq, lanes)
        return _gate_then_norm_xla(o, z[..., gate:], weight, epsilon, group)
    if d != 128:
        walk, why = None, "head_not_128_lanes"
    else:
        walk, why = _walk(seq, NORM_STRIP, lanes, gate)
    _pass_event("gated_rms_norm", walk, why, batch, seq, lanes)
    if walk is None:
        return _norm_xla(o, z[..., gate:], weight, epsilon)
    return _norm_vmem(o, z, weight, walk, float(epsilon))


# ---------------------------------------------------------------------------
# what a chunk is made of, in VMEM
# ---------------------------------------------------------------------------
_NN = ((1,), (0,))  # a @ b
_NT = ((1,), (1,))  # a @ b.T
_TN = ((0,), (0,))  # a.T @ b


def _mm(a, b, dims=_NN, precision=None):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=F32)


def _halves(x, cdt):
    """float32 ``x`` as operands of the products' type that add up to it: a
    high and a low half in bf16 (x to 2^-16), x itself in float32."""
    if cdt == F32:
        return (x,)
    hi = x.astype(cdt)
    return hi, (x - hi.astype(F32)).astype(cdt)


def _mm_halves(a, b, dims=_NN):
    """The product of two operands given as ``_halves``, gathered in float32:
    three passes for two halves each (low x low, under 2^-16, is left out),
    the small terms first. The configuration of the benchmark's cell states
    three bf16 passes (``precision.gated_delta_rule``): not one, not six."""
    precision = jax.lax.Precision.HIGHEST if a[0].dtype == F32 else None
    total = None
    for i, j in ((1, 0), (0, 1), (0, 0)):
        if i < len(a) and j < len(b):
            term = _mm(a[i], b[j], dims, precision)
            total = term if total is None else total + term
    return total


def _unit_lower_inverses(many, eye, cdt):
    """(I + a)^-1 in float32 for each strictly lower triangular a [C, C] of
    ``many``: a is nilpotent, so the inverse is the finite sum of (-a)^k,
    gathered by doubling, (I - a)(I + a^2)(I + a^4)...: 2 log2(C) - 2
    products and no dependent loop over rows. The products of one a wait for
    each other; those of several, written level by level, fill the wait."""
    xs = [eye.astype(F32) - a for a in many]
    ps, n = [_halves(a, cdt) for a in many], 2
    while n < eye.shape[-1]:
        ps = [_halves(_mm_halves(p, p), cdt) for p in ps]
        xs = [x + _mm_halves(_halves(x, cdt), p) for x, p in zip(xs, ps)]
        n *= 2
    return xs


def _to_col(row, eye):
    """[1, C] -> [C, 1], exactly (no transpose unit, no product)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _to_row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


class _Gates(NamedTuple):
    gam: jax.Array    # [C, 1] running sum of g inside the chunk
    last: jax.Array   # [1, 1] its last entry
    beta: jax.Array   # [C, 1]
    decay: jax.Array  # [C, C] exp(gam_i - gam_j) on and under the diagonal
    under: jax.Array  # [C, C] masks: on or under the diagonal,
    eye: jax.Array    # the diagonal


def _chunk_gates(g_row, beta_row):
    """A chunk's gates from its log decays and betas, [1, C] float32 rows."""
    c = g_row.shape[-1]
    ri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    under, eye = ci <= ri, ci == ri
    gam = jnp.sum(jnp.where(under, g_row, 0.0), axis=1, keepdims=True)
    gap = jnp.where(under, gam - _to_row(gam, eye), 0.0)
    return _Gates(gam, jnp.sum(g_row, axis=1, keepdims=True),
                  _to_col(beta_row, eye),
                  jnp.where(under, jnp.exp(gap), 0.0), under, eye)


def _strict(gt, x):
    return jnp.where(gt.under & ~gt.eye, x, 0.0)


def _unit_rows(x, scale=1.0):
    """``l2_normalize(x)`` times ``scale`` for rows [rows, d], with the two
    roundings to x's type the layer made on the way through HBM before."""
    y = l2_normalize(x)
    return y if scale == 1.0 else (y.astype(F32) * scale).astype(x.dtype)


def _unit_rows_bwd(x, dy, scale=1.0):
    """d loss / d x from d loss / d ``_unit_rows(x, scale)``, float32."""
    xf, dy = x.astype(F32), dy * scale
    r = jax.lax.rsqrt(jnp.sum(xf * xf, axis=1, keepdims=True) + L2_EPSILON)
    y = xf * r
    return r * (dy - y * jnp.sum(y * dy, axis=1, keepdims=True))


class _Operands(NamedTuple):
    """What a chunk needs beside the state it starts from; float32 where it
    is a factor of sums, the products' type where it is a product's."""
    e: jax.Array     # [C, 1] exp(gam)
    el: jax.Array    # [C, 1] exp(last - gam)
    kb: jax.Array    # [C, dk] beta exp(gam) K
    vb: jax.Array    # [C, dv] beta V
    w: jax.Array     # [C, dk] T kb
    qk: jax.Array    # [C, C] Q K^T, float32
    attn: jax.Array  # [C, C] decay Q K^T
    qg: jax.Array    # [C, dk] exp(gam) Q
    kd: jax.Array    # [C, dk] exp(last - gam) K


def _chunk_keys(q, k):
    """What the value heads of a key head share in a chunk: its rows of q
    (scaled by d_k^-0.5) and k at unit length, and Q K^T in float32."""
    q, k = _unit_rows(q, q.shape[-1] ** -0.5), _unit_rows(k)
    return q, k, _mm(q, k, _NT)


def _chunk_operands(q, k, qk, v, t, gt):
    cdt = v.dtype
    e, el = jnp.exp(gt.gam), jnp.exp(gt.last - gt.gam)
    kf = k.astype(F32)
    kb = (kf * (gt.beta * e)).astype(cdt)
    return _Operands(
        e, el, kb, (v.astype(F32) * gt.beta).astype(cdt),
        _mm(t, kb).astype(cdt), qk, (qk * gt.decay).astype(cdt),
        (q.astype(F32) * e).astype(cdt), (kf * el).astype(cdt))


# ---------------------------------------------------------------------------
# the three kernels
# ---------------------------------------------------------------------------
# A kernel holds a grid step's chunks unrolled, so that the scheduler can lay
# one chunk's preparation under another's pass; what a chunk does is a jitted
# function of values, traced once and not once a chunk (a step's trace is
# part of every run's set-up). A grid step holds a key head and the value
# heads it serves: what depends on the key head alone is made once a chunk,
# and the heads' products are written stage by stage side by side, so that
# each head's chain of products that carry its state has the others' work to
# overlap with (the scheduler follows source order).
@jax.jit
def _chunk_inverses(k, g_rows, beta_rows):
    """T [m C, C] in k's type of the m chunks of a grid step, one for each
    value head, from the rows of k [m C, dk] and each head's gates [m, C]."""
    m, c = g_rows[0].shape
    rows = [_unit_rows(k[i * c:(i + 1) * c]) for i in range(m)]
    kks = [_mm(ki, ki, _NT) for ki in rows]
    gates = [_chunk_gates(g[i:i + 1], b[i:i + 1])
             for g, b in zip(g_rows, beta_rows) for i in range(m)]
    many = [_strict(gt, gt.beta * gt.decay * kk)
            for gt, kk in zip(gates, kks * len(g_rows))]
    xs = _unit_lower_inverses(many, gates[0].eye, k.dtype)
    return [jnp.concatenate(xs[h:h + m], axis=0).astype(k.dtype)
            for h in range(0, len(xs), m)]


@jax.jit
def _chunk_forward(q, k, vs, ts, g_rows, beta_rows, ss):
    """(o, U, the state as the products see it, the next chunk's state) of
    one chunk, a list of each over the value heads, each head starting from
    its float32 state in ``ss``."""
    cdt = vs[0].dtype
    q, k, qk = _chunk_keys(q, k)
    gts = [_chunk_gates(g, b) for g, b in zip(g_rows, beta_rows)]
    ops = [_chunk_operands(q, k, qk, v, t, gt)
           for v, t, gt in zip(vs, ts, gts)]
    sbs = [s.astype(cdt) for s in ss]
    u0s = [_mm(t, op.vb).astype(cdt) for t, op in zip(ts, ops)]
    ubs = [(u0.astype(F32) - _mm(op.w, sb)).astype(cdt)
           for u0, op, sb in zip(u0s, ops, sbs)]
    # the next states first: the chain to the next chunk goes through them
    nexts = [s * jnp.exp(gt.last) + _mm(op.kd, ub, _TN)
             for s, gt, op, ub in zip(ss, gts, ops, ubs)]
    os = [(_mm(op.qg, sb) + _mm(op.attn, ub)).astype(cdt)
          for op, sb, ub in zip(ops, sbs, ubs)]
    return os, ubs, sbs, nexts


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


class _ThroughState(NamedTuple):
    """A head's cotangents through the four products that carry the state."""
    dub: jax.Array    # of U, the products' type
    dwb: jax.Array    # of w, the products' type
    dattn: jax.Array  # of the masked scores
    dqg: jax.Array    # of exp(gam) Q
    dkd: jax.Array    # of exp(last - gam) K
    ddec: jax.Array   # [1, 1] of exp(last) through the state, times it
    ds: jax.Array     # of the state the chunk starts from


# A head's backward of a chunk in three stages, which the kernel issues for
# all its heads in turn: through the state (the chain from chunk to chunk),
# through T (the chain of dA's four products), then to the rows and gates.
def _back_through_state(op, gt, s0, ub, dob, ds):
    cdt = ub.dtype
    dsb, dec = ds.astype(cdt), jnp.exp(gt.last)
    dub = (_mm(op.attn, dob, _TN) + _mm(op.kd, dsb)).astype(cdt)
    ddec = jnp.sum(_rowsum(ds * s0.astype(F32)), axis=0, keepdims=True)
    return _ThroughState(
        dub, (-_mm(dub, s0, _NT)).astype(cdt), _mm(dob, ub, _NT),
        _mm(dob, s0, _NT), _mm(ub, dsb, _NT), ddec * dec,
        _mm(op.qg, dob, _TN) + ds * dec - _mm(op.w, dub, _TN))


def _back_through_inverse(t, op, gt, st):
    """(d kb, d vb, dA under the diagonal) through w = T kb, u0 = T vb and
    T = (I + A)^-1: dA = -T^T dT T^T (T is exact in its own type: one
    half)."""
    cdt = t.dtype
    dt = _mm(st.dwb, op.kb, _NT) + _mm(st.dub, op.vb, _NT)
    dkb, dvb = _mm(t, st.dwb, _TN), _mm(t, st.dub, _TN)
    da = _mm_halves(_halves(_mm_halves((t,), _halves(dt, cdt), _TN), cdt),
                    (t,), _NT)
    return dkb, dvb, _strict(gt, -da)


def _back_to_inputs(q, k, kk, v, op, gt, st, dkb, dvb, dm):
    """A head's (dq, dk of the normalised rows, float32; dv; dg and dbeta as
    [1, C] rows); ``dm`` is d loss / d beta decay K K^T."""
    cdt = v.dtype
    qf, kf, vf = q.astype(F32), k.astype(F32), v.astype(F32)
    dmd = dm * gt.decay
    dkk = (dmd * gt.beta).astype(cdt)
    dqk = (st.dattn * gt.decay).astype(cdt)
    ddecay = (dm * gt.beta * kk + st.dattn * op.qk) * gt.decay
    dq = st.dqg * op.e + _mm(dqk, k)
    dk = (st.dkd * op.el + dkb * (gt.beta * op.e) + _mm(dqk, q, _TN)
          + _mm(dkk, k) + _mm(dkk, k, _TN))
    dkb_k = _rowsum(dkb * kf)
    dbeta = _rowsum(dmd * kk) + dkb_k * op.e + _rowsum(dvb * vf)
    # gamma: from exp(gam), exp(last - gam), exp(last) and the table (row
    # sums minus column sums); g from gamma by the reversed running sum
    dkd_k = _rowsum(st.dkd * kf) * op.el
    dlast = jnp.sum(dkd_k, axis=0, keepdims=True) + st.ddec
    dgam = ((_rowsum(st.dqg * qf) + dkb_k * gt.beta) * op.e - dkd_k
            + _rowsum(ddecay)
            - _to_col(jnp.sum(ddecay, axis=0, keepdims=True), gt.eye))
    dg = jnp.sum(jnp.where(gt.under, dgam, 0.0), axis=0, keepdims=True)
    return (dq, dk, (dvb * gt.beta).astype(cdt), dg + dlast,
            _to_row(dbeta, gt.eye))


@jax.jit
def _chunk_backward(q, k, vs, ts, g_rows, beta_rows, s0s, ubs, dobs, dss):
    """One chunk's cotangents, each value head's from d loss / d its o
    (``dobs``) and d loss / d the state its NEXT chunk starts from (``dss``,
    float32): dq and dk of the normalised rows, float32, summed over the
    heads, head 0 first; and lists over the heads of dv, dg and dbeta as
    [1, C] rows, and d loss / d the state this chunk starts from."""
    qn, kn, qk = _chunk_keys(q, k)
    kk = _mm(kn, kn, _NT)
    gts = [_chunk_gates(g, b) for g, b in zip(g_rows, beta_rows)]
    ops = [_chunk_operands(qn, kn, qk, v, t, gt)
           for v, t, gt in zip(vs, ts, gts)]
    sts = [_back_through_state(*a)
           for a in zip(ops, gts, s0s, ubs, dobs, dss)]
    dts = [_back_through_inverse(*a) for a in zip(ts, ops, gts, sts)]
    heads = [_back_to_inputs(qn, kn, kk, v, op, gt, st, *dt)
             for v, op, gt, st, dt in zip(vs, ops, gts, sts, dts)]
    dqs, dks, dvs, dgs, dbetas = zip(*heads)
    return (sum(dqs[1:], dqs[0]), sum(dks[1:], dks[0]), dvs, dgs, dbetas,
            [st.ds for st in sts])


def _value_rows(geo, r, rows):
    """Where value head ``r`` of a grid step keeps ``rows`` in a block of
    the "value" kind (see ``_call``)."""
    if geo.in_lanes:
        return 0, rows, slice(r * geo.dv, (r + 1) * geo.dv)
    return r, rows, slice(None)


def _inverse_kernel(k_ref, g_ref, beta_ref, t_ref, *, geo):
    heads = range(geo.heads)
    ts = _chunk_inverses(k_ref[0], [g_ref[r] for r in heads],
                         [beta_ref[r] for r in heads])
    for r in heads:
        t_ref[r] = ts[r]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, t_ref,
                o_ref, u_ref, h_ref, s_scr, *, geo):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, F32)

    heads = range(geo.heads)
    ss = [s_scr[r] for r in heads]
    for c in range(geo.per_step):
        rows = slice(c * geo.chunk, (c + 1) * geo.chunk)
        at = [_value_rows(geo, r, rows) for r in heads]
        # h: the states this chunk starts from, for the backward
        os, us, hs, ss = _chunk_forward(
            q_ref[0, rows, :], k_ref[0, rows, :], [v_ref[a] for a in at],
            [t_ref[r, rows, :] for r in heads],
            [g_ref[r, c:c + 1, :] for r in heads],
            [beta_ref[r, c:c + 1, :] for r in heads], ss)
        for r in heads:
            o_ref[at[r]], u_ref[at[r]], h_ref[r, c] = os[r], us[r], hs[r]
    for r in heads:
        s_scr[r] = ss[r]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, t_ref, u_ref, h_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                ds_scr, *, geo):
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[...] = jnp.zeros(ds_scr.shape, F32)

    heads = range(geo.heads)
    dss, dqs, dks = [ds_scr[r] for r in heads], [], []
    for c in reversed(range(geo.per_step)):
        rows = slice(c * geo.chunk, (c + 1) * geo.chunk)
        at = [_value_rows(geo, r, rows) for r in heads]
        dq, dk, dvs, dgs, dbetas, dss = _chunk_backward(
            q_ref[0, rows, :], k_ref[0, rows, :], [v_ref[a] for a in at],
            [t_ref[r, rows, :] for r in heads],
            [g_ref[r, c:c + 1, :] for r in heads],
            [beta_ref[r, c:c + 1, :] for r in heads],
            [h_ref[r, c] for r in heads], [u_ref[a] for a in at],
            [do_ref[a] for a in at], dss)
        dqs.insert(0, dq)
        dks.insert(0, dk)
        for r in heads:
            dv_ref[at[r]] = dvs[r]
            dg_ref[r, c:c + 1, :], dbeta_ref[r, c:c + 1, :] = dgs[r], dbetas[r]
    for r in heads:
        ds_scr[r] = dss[r]
    dq_ref[0] = _unit_rows_bwd(q_ref[0], jnp.concatenate(dqs),
                               q_ref.shape[-1] ** -0.5).astype(dq_ref.dtype)
    dk_ref[0] = _unit_rows_bwd(k_ref[0], jnp.concatenate(dks)).astype(
        dk_ref.dtype)


# ---------------------------------------------------------------------------
# their calls: one grid and one set of block maps for the three
# ---------------------------------------------------------------------------
class _Geometry(NamedTuple):
    chunk: int
    per_step: int    # chunks one grid step holds
    rep: int         # value heads a key head serves
    heads: int       # of them, one grid step holds: all, where they fit
    key_lanes: int   # key heads side by side in the lanes of q, k: all, or 1
    in_lanes: bool   # heads read where they lie in the lanes, or moved
    dk: int
    dv: int

    @property
    def groups(self):  # grid steps a key head's value heads take
        return self.rep // self.heads


def _per_step(n_chunks, chunk):
    """Chunks one grid step holds: STEP_ROWS rows where that tiles the
    sequence into whole (8, 128) blocks of the per-chunk gates, else all."""
    want = max(1, STEP_ROWS // chunk)
    return want if n_chunks % want == 0 and want % 8 == 0 else n_chunks


def _heads_per_step(rep, rows, chunk, dv):
    """Value heads one grid step holds: all ``rep`` of a key head, or the
    most that divide rep and keep what a step makes of each (its [rows, C]
    tables and [rows, dv] rows in float32, lanes padded to 128) within
    STEP_BYTES."""
    def lanes(n):
        return -(-n // 128) * 128

    head = 4 * rows * (lanes(chunk) + lanes(dv))
    return max(h for h in range(1, rep + 1)
               if rep % h == 0 and (h == 1 or h * head <= STEP_BYTES))


def _call(kernel, name, geo, ins, outs, scratch=(), reverse=False):
    """``kernel`` over the grid (key heads of all batches x their groups of
    ``geo.heads`` value heads, blocks of chunks); key head i of all batches
    serves value heads i * rep .. i * rep + rep - 1, and with one group (all
    shapes the VMEM holds) a grid step holds all of them. ``ins`` are (kind,
    array) pairs, ``outs`` (kind, shape, dtype); a kind is the block a grid
    step gets: "key" rows of one head out of [B, seq, lanes * dk] (head i is
    lane block i % lanes of row i // lanes), "value" rows of the step's
    value heads: the one (rows, heads * dv) lane block of [B, seq, value
    heads * dv] they lie in side by side, or (heads, rows, dv) of [value
    heads, seq, dv] where heads are moved; "t" (heads, rows, chunk) of
    [value heads, seq, chunk], "gate" of [value heads, chunks, chunk], "h"
    of [value heads, chunks, dk, dv]; "partial" a group's float32 rows of
    [groups, B, seq, lanes * dk], summed by the caller."""
    rows = geo.chunk * geo.per_step
    key_heads = ins[0][1].shape[0] * geo.key_lanes
    n_blocks = dict(ins)["gate"].shape[1] // geo.per_step
    lanes, groups = np.int32(geo.key_lanes), np.int32(geo.groups)

    def block(j):
        return np.int32(n_blocks - 1) - j if reverse else j

    def key(i, j):
        i = jax.lax.div(i, groups)
        return jax.lax.div(i, lanes), block(j), jax.lax.rem(i, lanes)

    def lane_block(i, j):  # the step's value heads, side by side
        return (jax.lax.div(i, lanes * groups), block(j),
                jax.lax.rem(i, lanes * groups))

    def heads(i, j):
        return i, block(j), _0

    h = geo.heads
    specs = {
        "key": pl.BlockSpec((1, rows, geo.dk), key),
        "value": pl.BlockSpec((1, rows, h * geo.dv), lane_block)
        if geo.in_lanes else pl.BlockSpec((h, rows, geo.dv), heads),
        "t": pl.BlockSpec((h, rows, geo.chunk), heads),
        "gate": pl.BlockSpec((h, geo.per_step, geo.chunk), heads),
        "h": pl.BlockSpec((h, geo.per_step, geo.dk, geo.dv),
                          lambda i, j: heads(i, j) + (_0,)),
        "partial": pl.BlockSpec(
            (None, 1, rows, geo.dk),
            lambda i, j: (jax.lax.rem(i, groups),) + key(i, j)),
    }
    return pl.pallas_call(
        functools.partial(kernel, geo=geo),
        name=name,
        grid=(key_heads * geo.groups, n_blocks),
        in_specs=[specs[kind] for kind, _ in ins],
        out_specs=[specs[kind] for kind, _, _ in outs],
        out_shape=[jax.ShapeDtypeStruct(shape, dtype)
                   for _, shape, dtype in outs],
        scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(*(x for _, x in ins))


# jitted: the model traces the rule once a layer and once more under its
# recomputation, and a kernel of eight unrolled chunks is a long trace
@functools.partial(jax.jit, static_argnums=(3, 4))
def _inverse(k, g, beta, geo, dtype):
    heads, n = g.shape[0], g.shape[1]
    return _call(_inverse_kernel, "gated_delta_rule_fwd_inverse", geo,
                 [("key", k), ("gate", g), ("gate", beta)],
                 [("t", (heads, n * geo.chunk, geo.chunk), dtype)])[0]


@functools.partial(jax.jit, static_argnums=(6,))
def _forward(q, k, v, g, beta, t, geo):
    heads, n = g.shape[0], g.shape[1]
    return _call(
        _fwd_kernel, "gated_delta_rule_fwd", geo,
        [("key", q), ("key", k), ("value", v), ("gate", g), ("gate", beta),
         ("t", t)],
        [("value", v.shape, v.dtype), ("value", v.shape, v.dtype),
         ("h", (heads, n, geo.dk, geo.dv), v.dtype)],
        [pltpu.VMEM((geo.heads, geo.dk, geo.dv), F32)])


@functools.partial(jax.jit, static_argnums=(9,))
def _backward(q, k, v, g, beta, t, u, h, do, geo):
    # dq, dk: written once a key head, or as each group's part, summed here
    dqk = [("key", x.shape, x.dtype) if geo.groups == 1
           else ("partial", (geo.groups,) + x.shape, F32) for x in (q, k)]
    dq, dk, dv, dg, dbeta = _call(
        _bwd_kernel, "gated_delta_rule_bwd", geo,
        [("key", q), ("key", k), ("value", v), ("gate", g), ("gate", beta),
         ("t", t), ("value", u), ("h", h), ("value", do)],
        dqk + [("value", v.shape, v.dtype), ("gate", g.shape, F32),
               ("gate", beta.shape, F32)],
        [pltpu.VMEM((geo.heads, geo.dk, geo.dv), F32)], reverse=True)
    if geo.groups > 1:
        dq, dk = dq.sum(0).astype(q.dtype), dk.sum(0).astype(k.dtype)
    return dq, dk, dv, dg, dbeta


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, geo):
    """o as v, from q, k [B, seq, lanes * dk], v [B', seq, lanes * dv] and
    the gates [value heads of all batches, chunks, chunk] float32."""
    t = _inverse(k, g, beta, geo, v.dtype)
    return _forward(q, k, v, g, beta, t, geo)[0]


def _rule_fwd(q, k, v, g, beta, geo):
    # the name is on the residual itself: a recomputed layer keeps the
    # inverse and makes only the pass over the chunks again
    t = checkpoint_name(_inverse(k, g, beta, geo, v.dtype), KEEP_NAME)
    o, u, h = _forward(q, k, v, g, beta, t, geo)
    return o, (q, k, v, g, beta, t, u, h)


def _rule_bwd(geo, res, do):
    q, k, v, g, beta, t, u, h = res
    return _backward(q, k, v, g, beta, t, u, h, do.astype(v.dtype), geo)


_rule.defvjp(_rule_fwd, _rule_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------
def gated_delta_rule(q, k, v, g, beta, *, chunk=64):
    """The gated delta rule over whole sequences, state zero at the start.

    q, k [batch, seq, key heads, d_k] as the conv leaves them: the kernels
    take each head's row to unit length (``l2_normalize``, in VMEM) and
    scale q by d_k^-0.5. v [batch, seq, value heads, d_v], g (log decay,
    <= 0) and beta [batch, seq, value heads]; key head i serves value heads
    i * rep .. (i+1) * rep-1.
    Returns o [batch, seq, value heads, d_v] in v's type. ``seq`` must be a
    multiple of ``chunk`` (or shorter than it).

    Heads whose widths are whole blocks of 128 lanes are read where they lie
    and o is written so; other widths are moved to [heads, seq, d] first (a
    copy each way). Each trace leaves one ``gdn_chunks`` event in the flight
    recorder."""
    b, seq, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    chunk = min(chunk, seq)
    if seq % chunk or hv % hk or k.shape != q.shape:
        raise ValueError(
            f"gated_delta_rule: seq {seq} is not a multiple of chunk {chunk}, "
            f"or {hv} value heads are not a multiple of {hk} key heads")
    n = seq // chunk
    in_lanes = dk % 128 == 0 and dv % 128 == 0
    per_step = _per_step(n, chunk)
    geo = _Geometry(chunk, per_step, hv // hk,
                    _heads_per_step(hv // hk, per_step * chunk, chunk, dv),
                    hk if in_lanes else 1, in_lanes, dk, dv)

    from ..profiler import trace
    trace.emit("gdn_chunks", site="gated_delta_rule", seq=seq, chunk=chunk,
               chunks_per_step=geo.per_step, rep=geo.rep,
               heads_per_step=geo.heads, heads_in_lanes=in_lanes,
               prepared="vmem")

    def heads(x):  # [b, seq, h, d] -> [B, seq, lanes * d]
        if in_lanes:
            return x.reshape(b, seq, -1)
        return jnp.moveaxis(x, 2, 1).reshape(-1, seq, x.shape[-1])

    def gate(x):  # [b, seq, hv] -> [b * hv, chunks, chunk]
        x = x.astype(F32).reshape(b, n, chunk, hv)
        return jnp.moveaxis(x, 3, 1).reshape(b * hv, n, chunk)

    o = _rule(heads(q), heads(k), heads(v), gate(g), gate(beta), geo)
    if in_lanes:
        return o.reshape(b, seq, hv, dv)
    return jnp.moveaxis(o.reshape(b, hv, seq, dv), 1, 2)
