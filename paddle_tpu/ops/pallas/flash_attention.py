"""Flash attention as a Pallas TPU kernel (forward + backward).

Reference analogue: paddle/fluid/operators/fused/fused_attention_op.cu and
fmha_ref.h — the reference's fused CUDA attention. TPU-native design: an
online-softmax streaming kernel (Flash-Attention-2 style) tiled to the MXU:

  forward   grid (B*H, S/Bq, S/Bk), k-blocks innermost; running (m, l, acc)
            live in VMEM scratch across k steps; O and the row logsumexp are
            written on the last k step. Memory is O(S·D) instead of O(S²).
  backward  two kernels sharing the saved (O, lse): one accumulates dK/dV
            (k-block resident, streaming q), one accumulates dQ (q-block
            resident, streaming k). delta = rowsum(dO·O) is precomputed.

Inside the [Bq, Bk] block a grid step holds resident, all three kernels cut
the block into [sub_q, sub_k] sub-tiles and compute only those on or under the
causal diagonal: one strip of scores for each q sub-block over every key it
attends to (dkv: for each key sub-block over every q row that attends to it),
with a mask built only on the strip's sub-tiles that the diagonal crosses. A
block wholly under the diagonal is one strip; a grid step wholly above it is
skipped and fetches nothing. Where one grid step covers a head's whole key
(or q) range, the running state never leaves the step: no scratch, no
rescaling. A strip's score products are issued one strip ahead of its
softmax (``_ahead``), and dq, dk and dv, which are only head_dim wide, are
multiplied with head_dim as the streamed rows where that fills the MXU
better (``_times``). The walk's bounds and ``causal_tile_counts`` come from
the same two counting rules (``_ends_le`` / ``_starts_le``). Block and
sub-tile sizes follow the shape; the sweep they were chosen from is PERF.md
§5, "flash attention sub-tile sweep". Accumulation is always f32 regardless
of input dtype (bf16 in → bf16 out, f32 math). Off the TPU (tests/dev on
CPU) the kernel runs in interpret mode.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = np.float32(-1e30)


def _default_block_q(seq_len: int) -> int:
    """bq=1024 up to seq 4096: on the v5e the 1024 x 1024 block beat 512 x 1024
    and 512 x 512 at seq 1024, and 512 x 1024 at seq 4096 (PERF.md §5, "flash
    attention sub-tile sweep"). 512 past 4096 is not measured. Seqs in
    (2048, 4096] that 1024 does not divide (2560, 3584...) keep 512 — the
    wider default must never SHRINK the eligible set. Shared by
    flash_attention and supports() so eligibility always mirrors the
    kernel."""
    if seq_len <= 2048:
        return 1024
    if seq_len <= 4096 and seq_len % 1024 == 0:
        return 1024
    return 512


_SUB_TILE = 128  # of 128, 256 and 512 the sweep of PERF.md §5 prefers it


def _default_sub_tiles(bq: int, bk: int):
    """(sub_q, sub_k) for a [bq, bk] block: the swept size where it divides
    the block, else the block itself (one masked sub-tile, as for seq 200 or
    600). Follows the block, which follows the sequence — never the model."""
    def pick(block):
        return _SUB_TILE if block % _SUB_TILE == 0 else block

    return pick(bq), pick(bk)


# ---------------------------------------------------------------------------
# which tiles the causal diagonal leaves to compute
# ---------------------------------------------------------------------------
# One axis is cut into n tiles [base + t*step, base + (t+1)*step). Every bound
# of the walk — which grid steps run, which sub-tiles run, which of those
# build a mask — is one of two counts over such a cut. Positions are Python
# ints inside a kernel (every bound is static and the walk unrolls) and traced
# int32 in an index map.
def _clip(v, n):
    if isinstance(v, int):
        return min(max(v, 0), n)
    return jnp.clip(v, np.int32(0), np.int32(n))


def _div(a, b):
    # floor for ints, truncation for traced values: the same wherever a >= 0,
    # and every caller clips a negative quotient to 0
    return a // b if isinstance(a, int) else jax.lax.div(a, np.int32(b))


def _ends_le(x, base, step, n):
    """How many of the n tiles end at or before position x."""
    return _clip(_div(x - base + 1, step), n)


def _starts_le(x, base, step, n):
    """How many of the n tiles start at or before position x."""
    return _clip(_div(x - base + step, step), n)


def _key_walk(row0, rows, col_base, step, n, causal):
    """For q rows row0 .. row0+rows-1 and n key tiles of `step` columns from
    col_base: (n_full, n_run). Tiles [0, n_full) lie wholly on or under the
    diagonal (last column <= first row: no mask), [n_full, n_run) are crossed
    by it (first column <= last row: masked), the rest are skipped."""
    if not causal:
        return n, n
    return (_ends_le(row0, col_base, step, n),
            _starts_le(row0 + rows - 1, col_base, step, n))


def _query_walk(col0, cols, row_base, step, n):
    """The transposed (causal) walk, for key columns col0 .. col0+cols-1 and n
    q tiles of `step` rows from row_base: (r_first, r_full). Tiles [0, r_first)
    are skipped (last row < first column), [r_first, r_full) are crossed by
    the diagonal, [r_full, n) lie wholly on or under it."""
    return (_ends_le(col0 - 1, row_base, step, n),
            _starts_le(col0 + cols - 2, row_base, step, n))


def causal_tile_counts(seq_len, block_q, block_k, sub_q, sub_k, causal):
    """(run, masked, total) sub-tiles of one head's seq_len x seq_len score
    square as the kernels walk it: computed, computed with a mask, and all.
    `run / total` is how far the causal skip engages (1.0 = not at all)."""
    n_q, n_k = seq_len // block_q, seq_len // block_k
    n_sq, n_sk = block_q // sub_q, block_k // sub_k
    run = masked = 0
    for j in range(n_q):
        _, k_steps = _key_walk(j * block_q, block_q, 0, block_k, n_k, causal)
        for kk in range(k_steps):
            for i in range(n_sq):
                n_full, n_run = _key_walk(j * block_q + i * sub_q, sub_q,
                                          kk * block_k, sub_k, n_sk, causal)
                run += n_run
                masked += n_run - n_full
    return run, masked, n_q * n_sq * n_k * n_sk


_0 = np.int32(0)  # index-map literal; Python ints trace to i64 under x64


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _compiler_params(dims):
    return pltpu.CompilerParams(dimension_semantics=dims)


def _at_block_offset(n_q, n_k, q_axis, k_axis, bq, bk, causal, walk,
                     q_wraps=False):
    """Call walk(off) with off = the resident block's first row less its first
    column as a Python int, or None for a block wholly under the diagonal, so
    that every bound of the walk inside the block is static and the walk
    unrolls. Where the grid has several blocks there is one predicated copy
    of the walk for each offset at which the diagonal crosses a block, one
    for all blocks wholly under it, and none for a block above it (skipped).
    ``q_wraps``: the q axis walks the q blocks once for each query head of a
    group (dkv under grouped-query heads), so the block is its index mod n_q."""
    if not causal:
        walk(None)
        return
    offs = {j * bq - kk * bk for j in range(n_q) for kk in range(n_k)}
    if len(offs) == 1:
        walk(0)
        return
    j = pl.program_id(q_axis)
    if q_wraps:
        j = jax.lax.rem(j, np.int32(n_q))
    off = j * bq - pl.program_id(k_axis) * bk
    for d in sorted(d for d in offs if -bq < d < bk - 1):
        pl.when(off == d)(functools.partial(walk, d))
    if max(offs) >= bk - 1:
        pl.when(off >= bk - 1)(functools.partial(walk, None))


def _q_strips(off, bq, bk, sq, sk):
    """The forward's and dq's walk of a resident [bq, bk] block: one strip of
    scores for each q sub-block, over every key it attends to. Yields (rows,
    keys, first masked column). A block wholly under the diagonal (off None)
    is one strip."""
    if off is None:
        yield slice(0, bq), bk, bk
        return
    for i in range(bq // sq):
        n_full, n_run = _key_walk(off + i * sq, sq, 0, sk, bk // sk, True)
        if n_run:  # else this block has no key at or before these rows
            yield slice(i * sq, (i + 1) * sq), n_run * sk, n_full * sk


def _k_strips(off, bq, bk, sq, sk):
    """dkv's walk, transposed: one strip for each key sub-block, over every q
    row that attends to it. Yields (columns, first row, first unmasked row)."""
    if off is None:
        yield slice(0, bk), 0, 0
        return
    for c in range(bk // sk):
        r_first, r_full = _query_walk(c * sk, sk, off, sq, bq // sq)
        if r_first < bq // sq:  # else every row here is above these keys
            yield slice(c * sk, (c + 1) * sk), r_first * sq, r_full * sq


def _kv_index(causal, bq, bk, n_k, group=1):
    """Index map of a k/v block under grid (head, q block j, k block kk). A
    step above the diagonal is skipped in the kernel: give it the index of
    the last step that runs, so that Pallas sees no change and copies
    nothing. Under grouped-query heads (``group`` query heads on one KV
    head) query head i reads KV head i // group: k and v are never copied
    out to the query heads."""
    def index(i, j, kk):
        if causal and n_k > 1:
            _, k_steps = _key_walk(j * bq, bq, 0, bk, n_k, causal)
            kk = jnp.minimum(kk, k_steps - 1)
        if group > 1:
            i = jax.lax.div(i, np.int32(group))
        return (i, kk, _0)
    return index


def _causal_mask(s, row0, col0, keys_first=False):
    """Mask a score tile whose corner is (row0, col0) to the causal region
    (shared by all 3 kernels); `keys_first` for a tile with the keys down
    the sublanes."""
    q_dim, k_dim = (1, 0) if keys_first else (0, 1)
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_dim)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, k_dim)
    return jnp.where(cols <= rows, s, NEG_INF)


def _mask_lanes(s, lo, hi, row0, col0, keys_first=False):
    """Mask lanes [lo, hi) of a score strip, the sub-tiles the diagonal
    crosses; the lanes beside them lie wholly under it and pass untouched."""
    if lo == hi:
        return s
    parts = [s[:, :lo],
             _causal_mask(s[:, lo:hi], row0 + (lo if keys_first else 0),
                          col0 + (0 if keys_first else lo), keys_first),
             s[:, hi:]]
    parts = [x for x in parts if x.shape[1]]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _mm(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))  # a @ b.T
_NN = ((1,), (0,))  # a @ b


def _times(ref):
    """f(x, rows) = x @ ref[0, rows, :] in f32, for the products whose result
    is head_dim wide. Where that is narrower than the MXU's 128 columns the
    product is made the other way round, ref.T @ x.T with head_dim as the
    rows that stream through, and turned back: half the MXU's passes at
    head_dim 64. The block is turned once (through f32, which the transpose
    unit takes), whatever the number of strips."""
    if ref.shape[-1] >= 128:
        return lambda x, rows: _mm(x, ref[0, rows, :], _NN)
    turned = ref[0].astype(jnp.float32).T.astype(ref.dtype)  # [head_dim, n]
    return lambda x, rows: _mm(turned[:, rows], x, _NT).T


def _ahead(strips, products):
    """(strip, products(strip)) for each strip, with the next strip's products
    issued before this strip's are handed out: the MXU then works on them
    while the vector units are busy with this strip's softmax."""
    strips = list(strips)
    nxt = products(strips[0]) if strips else None
    for i, strip in enumerate(strips):
        cur = nxt
        nxt = products(strips[i + 1]) if i + 1 < len(strips) else None
        yield strip, cur


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _finish(m, l, acc, o_ref, lse_ref, rows):
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, rows, :] = (acc / safe_l).astype(o_ref.dtype)
    # lse is one value a row, down the sublanes; it is stored along the lanes.
    # Spread over a lane tile it turns in the transpose unit, which costs a
    # quarter of the forward less than letting the store reshape it
    lse = m + jnp.log(safe_l)
    lse_ref[0, :, rows] = jnp.broadcast_to(lse, (lse.shape[0], 128)).T[:1]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                scale, causal, bq, bk, sq, sk, n_q, n_k):
    scale = np.float32(scale)
    if scratch:  # (m, l, acc) carried from one k step to the next
        m_scr, l_scr, acc_scr = scratch

        @pl.when(pl.program_id(2) == 0)
        def _():
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

    def scores(strip):
        rows, keys, _ = strip
        return _mm(q_ref[0, rows, :], k_ref[0, :keys, :], _NT) * scale

    def walk(off):
        for (rows, keys, masked), s in _ahead(_q_strips(off, bq, bk, sq, sk),
                                              scores):
            n = rows.stop - rows.start
            s = _mask_lanes(s, masked, keys, (off or 0) + rows.start, 0)
            m = jnp.max(s, axis=-1, keepdims=True)
            if scratch:
                m_prev = m_scr[rows, :1]
                m = jnp.maximum(m_prev, m)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            v = v_ref[0, :keys, :]
            acc = _mm(p.astype(v.dtype), v, _NN)
            if scratch:
                alpha = jnp.exp(m_prev - m)
                m_scr[rows, :] = jnp.broadcast_to(m, (n, m_scr.shape[1]))
                l_scr[rows, :] = jnp.broadcast_to(
                    alpha * l_scr[rows, :1] + l, (n, l_scr.shape[1]))
                acc_scr[rows, :] = acc_scr[rows, :] * alpha + acc
            else:
                _finish(m, l, acc, o_ref, lse_ref, rows)

    _at_block_offset(n_q, n_k, 1, 2, bq, bk, causal, walk)

    if scratch:
        @pl.when(pl.program_id(2) == n_k - 1)
        def _():
            _finish(m_scr[:, :1], l_scr[:, :1], acc_scr[:], o_ref, lse_ref,
                    slice(None))


def _fwd(q, k, v, scale, causal, bq, bk, sq, sk, group=1):
    bh, s, d = q.shape
    n_q, n_k = s // bq, s // bk
    kv_index = _kv_index(causal, bq, bk, n_k, group)
    # one k step: the running (m, l, acc) never leave the step's registers
    scratch = [] if n_k == 1 else [
        pltpu.VMEM((bq, 128), jnp.float32),
        pltpu.VMEM((bq, 128), jnp.float32),
        pltpu.VMEM((bq, d), jnp.float32),
    ]
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, sq=sq, sk=sk, n_q=n_q, n_k=n_k),
        name="flash_attention_fwd",
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, _0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, _0)),
            pl.BlockSpec((1, 1, bq), lambda i, j, kk: (i, _0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *scratch,
                    scale, causal, bq, bk, sq, sk, n_q, n_k, group=1):
    scale = np.float32(scale)
    if scratch:  # (dk, dv) carried from one q step to the next, and from one
        # query head of the group to the next: they come out summed over it
        dk_scr, dv_scr = scratch

        @pl.when(pl.program_id(2) == 0)
        def _():
            dk_scr[:] = jnp.zeros_like(dk_scr)
            dv_scr[:] = jnp.zeros_like(dv_scr)

    def products(strip):
        # scores with the keys down the sublanes, [keys, rows]: lse and delta
        # are rows as they lie in memory, and nothing is transposed
        cols, r0, _ = strip
        return (_mm(k_ref[0, cols, :], q_ref[0, r0:, :], _NT) * scale,
                _mm(v_ref[0, cols, :], do_ref[0, r0:, :], _NT))

    def walk(off):
        times_do, times_q = _times(do_ref), _times(q_ref)
        for (cols, r0, r_full), (st, dpt) in _ahead(
                _k_strips(off, bq, bk, sq, sk), products):
            rows = slice(r0, bq)
            st = _mask_lanes(st, 0, r_full - r0, (off or 0) + r0, cols.start,
                             keys_first=True)
            pt = jnp.exp(st - lse_ref[0, :, rows])
            dst = pt * (dpt - delta_ref[0, :, rows])
            dv = times_do(pt.astype(do_ref.dtype), rows)
            dk = times_q((dst * scale).astype(q_ref.dtype), rows)
            if scratch:
                dk_scr[cols, :] += dk
                dv_scr[cols, :] += dv
            else:
                dk_ref[0, cols, :] = dk.astype(dk_ref.dtype)
                dv_ref[0, cols, :] = dv.astype(dv_ref.dtype)

    _at_block_offset(n_q, n_k, 2, 1, bq, bk, causal, walk, q_wraps=group > 1)

    if scratch:
        @pl.when(pl.program_id(2) == group * n_q - 1)
        def _():
            dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *scratch, scale, causal, bq, bk, sq, sk, n_q, n_k):
    scale = np.float32(scale)
    if scratch:  # dq carried from one k step to the next
        dq_scr, = scratch

        @pl.when(pl.program_id(2) == 0)
        def _():
            dq_scr[:] = jnp.zeros_like(dq_scr)

    def products(strip):
        rows, keys, _ = strip
        return (_mm(q_ref[0, rows, :], k_ref[0, :keys, :], _NT) * scale,
                _mm(do_ref[0, rows, :], v_ref[0, :keys, :], _NT))

    def walk(off):
        times_k = _times(k_ref)
        for (rows, keys, masked), (s, dp) in _ahead(
                _q_strips(off, bq, bk, sq, sk), products):
            s = _mask_lanes(s, masked, keys, (off or 0) + rows.start, 0)
            p = jnp.exp(s - lse_ref[0, 0, rows][:, None])
            ds = p * (dp - delta_ref[0, 0, rows][:, None])
            dq = times_k((ds * scale).astype(k_ref.dtype), slice(0, keys))
            if scratch:
                dq_scr[rows, :] += dq
            else:
                dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)

    _at_block_offset(n_q, n_k, 1, 2, bq, bk, causal, walk)

    if scratch:
        @pl.when(pl.program_id(2) == n_k - 1)
        def _():
            dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv(q, k, v, do, lse, delta, *, scale, causal, bq, bk, sq, sk, group=1):
    bh, s, d = k.shape  # the grid's heads are the KV heads
    n_q, n_k = s // bq, s // bk

    def q_index(kk, j):
        # a q block whose every row is above this k block's first column is
        # skipped: name the first block that runs instead, so nothing is copied
        if causal and n_q > 1:
            j_first, _ = _query_walk(kk * bk, bk, 0, bq, n_q)
            j = jnp.maximum(j, j_first)
        return j

    if group == 1:
        def q_head(i, t):
            return i

        def q_block(kk, t):
            return q_index(kk, t)
    else:
        # the last grid axis walks the group's query heads, each over its q
        # blocks: KV head i is attended by query heads i * group + t // n_q
        def q_head(i, t):
            return i * np.int32(group) + jax.lax.div(t, np.int32(n_q))

        def q_block(kk, t):
            return q_index(kk, jax.lax.rem(t, np.int32(n_q)))

    # one q step: dk and dv never leave the step's registers
    scratch = [] if group * n_q == 1 else [pltpu.VMEM((bk, d), jnp.float32),
                                           pltpu.VMEM((bk, d), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, sq=sq, sk=sk, n_q=n_q, n_k=n_k, group=group),
        name="flash_attention_bwd_dkv",
        grid=(bh, n_k, group * n_q),
        in_specs=[
            pl.BlockSpec((1, bq, d),
                         lambda i, kk, j: (q_head(i, j), q_block(kk, j), _0)),
            pl.BlockSpec((1, bk, d), lambda i, kk, j: (i, kk, _0)),
            pl.BlockSpec((1, bk, d), lambda i, kk, j: (i, kk, _0)),
            pl.BlockSpec((1, bq, d),
                         lambda i, kk, j: (q_head(i, j), q_block(kk, j), _0)),
            pl.BlockSpec((1, 1, bq),
                         lambda i, kk, j: (q_head(i, j), _0, q_block(kk, j))),
            pl.BlockSpec((1, 1, bq),
                         lambda i, kk, j: (q_head(i, j), _0, q_block(kk, j))),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda i, kk, j: (i, kk, _0)),
            pl.BlockSpec((1, bk, d), lambda i, kk, j: (i, kk, _0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)


def _dq(q, k, v, do, lse, delta, *, scale, causal, bq, bk, sq, sk, group=1):
    bh, s, d = q.shape
    n_q, n_k = s // bq, s // bk
    kv_index = _kv_index(causal, bq, bk, n_k, group)
    scratch = [] if n_k == 1 else [pltpu.VMEM((bq, d), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, sq=sq, sk=sk, n_q=n_q, n_k=n_k),
        name="flash_attention_bwd_dq",
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, _0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, _0)),
            pl.BlockSpec((1, 1, bq), lambda i, j, kk: (i, _0, j)),
            pl.BlockSpec((1, 1, bq), lambda i, j, kk: (i, _0, j)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, _0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=scratch,
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)


def _bwd(scale, causal, bq, bk, sq, sk, group, res, do):
    q, k, v, out, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, None, :]
    sizes = dict(scale=scale, causal=causal, bq=bq, bk=bk, sq=sq, sk=sk,
                 group=group)
    dk, dv = _dkv(q, k, v, do, lse, delta, **sizes)
    dq = _dq(q, k, v, do, lse, delta, **sizes)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, scale, causal, bq, bk, sq, sk, group):
    out, _ = _fwd(q, k, v, scale, causal, bq, bk, sq, sk, group)
    return out


def _flash_fwd(q, k, v, scale, causal, bq, bk, sq, sk, group):
    out, lse = _fwd(q, k, v, scale, causal, bq, bk, sq, sk, group)
    return out, (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


def supports(seq_len: int, head_dim: int, block_q: int = None, block_k: int = 1024) -> bool:
    """Shapes the kernel accepts (everything else falls back to the XLA path).

    The kernel covers the sequence either with one full-array block
    (seq <= block) or with an exact tiling — a seq that is neither would
    leave tail rows unwritten, so it must be rejected here."""
    if block_q is None:
        block_q = _default_block_q(seq_len)
    bq = min(block_q, seq_len)
    bk = min(block_k, seq_len)
    return (
        seq_len % bq == 0
        and seq_len % bk == 0
        and seq_len >= 8
        and head_dim % 8 == 0
    )


def flash_attention(q, k, v, *, scale=None, causal=True, block_q=None, block_k=1024):
    """Streaming attention over [batch, seq, heads, head_dim] inputs
    (paddle fused_attention layout, matching scaled_dot_product_attention).

    Grouped-query heads: k and v may have fewer heads than q, a divisor of
    q's; query head i attends KV head i // group through the kernels' index
    maps (no copy of k and v per query head is made), and dk and dv come out
    summed over the group.

    Default blocks and the sub-tiles inside them follow the shape; the chip
    readings they were chosen from are PERF.md §5, "flash attention sub-tile
    sweep". Each trace leaves one ``flash_tiles`` event in the flight
    recorder: how many sub-tiles of a head's score square are computed, and
    how many of those with a mask.
    """
    b, s, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv or v.shape != k.shape:
        raise ValueError(
            f"flash_attention: {h} query heads on k {tuple(k.shape)} / v "
            f"{tuple(v.shape)}: the KV heads must divide the query heads")
    group = h // h_kv
    if block_q is None:
        block_q = _default_block_q(s)
    bq = min(block_q, s)
    bk = min(block_k, s)
    if s % bq != 0 or s % bk != 0:
        raise ValueError(
            f"flash_attention: seq_len {s} is not divisible by block sizes "
            f"({bq}, {bk}) — tail rows would be left unwritten; pad the "
            "sequence or use the dense path"
        )
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    sq, sk = _default_sub_tiles(bq, bk)

    from ...profiler import trace
    run, masked, total = causal_tile_counts(s, bq, bk, sq, sk, bool(causal))
    trace.emit("flash_tiles", site="flash_attention", seq=s, block_q=bq,
               block_k=bk, sub_q=sq, sub_k=sk, run=run, masked=masked,
               total=total)

    def to_bh(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * x.shape[2], s, d)

    out = _flash(to_bh(q), to_bh(k), to_bh(v), np.float32(scale), bool(causal),
                 bq, bk, sq, sk, group)
    return jnp.swapaxes(out.reshape(b, h, s, d), 1, 2)
