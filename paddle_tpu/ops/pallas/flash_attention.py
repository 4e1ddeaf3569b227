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

The same three kernels walk the two-stream block mask of block-diffusion
training (``block_mask`` = (half, blk): a stream of ``half`` clean positions
and then ``half`` noised ones, in blocks of ``blk``). It is the causal walk
with the diagonal rounded to the mask's blocks, made by BOTH halves' q blocks
over the CLEAN half's key blocks: up to the end of a clean row's own block
(``_key_walk`` with ``blk``), up to the start of a noised row's
(``before``). The grid's q axis is the stream's (2 half / bq blocks), its k
axis the clean half's, so the quadrant clean-on-noised is no grid step and
is never fetched. What is left, a noised row on the noised keys of its own
block, lies on the block diagonal of the fourth quadrant: the noised key
block at the place of the clean block the diagonal crosses rides along as two
more operands (the same k and v arrays under a second index map; dkv gives
it two more outputs), and its sub-tiles on the diagonal (``own``) join that
step's strips, inside the same softmax. Only sub-tiles a rounded diagonal
crosses, and the own sub-tiles, build a mask (``_causal_mask``: a compare on
positions or-ed, and-ed or xor-ed with blk - 1, so blk is a power of two).
With ``block_mask`` None every index map, walk and mask is what it was:
``blk`` 1 is the plain diagonal.

A sliding window (``window`` = W: query i sees key j iff i - W < j <= i)
bounds the causal walk from below as well. The grid's key axis spans only
the key blocks a q block's band touches (dkv's q axis only the q blocks
whose rows reach a key block, down to its last column + W - 1), a step's
block being the first such block plus the step; a step past the band is
skipped and fetches nothing. Inside a block a strip starts at the first
sub-tile its rows' band reaches, and the sub-tiles that the band's lower edge
crosses build a mask as those the diagonal crosses do (``_causal_mask``
with a second compare on positions). Those kernels carry the names
``flash_attention_window_*``. With ``window`` None every index map, walk,
mask and name is what it was.
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


def _plus(x, n):
    # x + n with nothing traced where n is 0: blk 1 is the causal walk as it
    # always was, index maps included
    return x + n if n else x


def _key_walk(row0, rows, col_base, step, n, causal, blk=1, before=False):
    """For q rows row0 .. row0+rows-1 and n key tiles of `step` columns from
    col_base: (n_full, n_run). Tiles [0, n_full) lie wholly on or under the
    diagonal (last column <= first row: no mask), [n_full, n_run) are crossed
    by it (first column <= last row: masked), the rest are skipped. With
    positions in blocks of ``blk`` (row0 and rows whole blocks) the diagonal
    is rounded to them: a row sees every key up to the end of its own block
    or, with ``before``, only the blocks before its own."""
    if not causal:
        return n, n
    if before:
        return (_ends_le(row0 - 1, col_base, step, n),
                _starts_le(row0 + rows - blk - 1, col_base, step, n))
    return (_ends_le(_plus(row0, blk - 1), col_base, step, n),
            _starts_le(row0 + rows - 1, col_base, step, n))


def _query_walk(col0, cols, row_base, step, n, blk=1, before=False):
    """The transposed (causal) walk, for key columns col0 .. col0+cols-1 and n
    q tiles of `step` rows from row_base: (r_first, r_full). Tiles [0, r_first)
    are skipped (last row < first column), [r_first, r_full) are crossed by
    the diagonal, [r_full, n) lie wholly on or under it. ``blk`` and
    ``before`` round the diagonal as in ``_key_walk``."""
    if before:
        return (_ends_le(col0 + blk - 1, row_base, step, n),
                _starts_le(col0 + cols - 1, row_base, step, n))
    return (_ends_le(col0 - 1, row_base, step, n),
            _starts_le(col0 + cols - (1 + blk), row_base, step, n))


def _band_walk(row0, rows, col_base, step, n, window):
    """``_key_walk`` under a sliding window of ``window`` keys, for q rows
    row0 .. row0+rows-1: (first, lo, hi, end). Tiles [first, end) run, those
    before ``first`` lie wholly before the band of the first row; [first, lo)
    are crossed by the band's lower edge and [hi, end) by the diagonal (both
    masked). Where the two crossings meet every tile is masked (lo = hi =
    first)."""
    first = _ends_le(row0 - window, col_base, step, n)
    lo = _starts_le(row0 + rows - 1 - window, col_base, step, n)
    hi = _ends_le(row0, col_base, step, n)
    if lo > hi:
        lo = hi = first
    return first, lo, hi, _starts_le(row0 + rows - 1, col_base, step, n)


def _band_query_walk(col0, cols, row_base, step, n, window):
    """``_query_walk`` under a sliding window, for key columns col0 ..
    col0+cols-1: (r_first, r_full, r_lo, r_end). Tiles [r_first, r_end) of q
    rows run, the rows stopping at col0+cols-1 + window-1; [r_first, r_full)
    are crossed by the diagonal and [r_lo, r_end) by the band's lower edge
    (both masked). Where the two crossings meet every tile is masked."""
    first = _ends_le(col0 - 1, row_base, step, n)
    full = _starts_le(col0 + cols - 2, row_base, step, n)
    lo = _ends_le(col0 + window - 1, row_base, step, n)
    end = _starts_le(col0 + cols + window - 2, row_base, step, n)
    if lo < full:
        full = lo = end
    return first, full, lo, end


def _band_keys(j, bq, bk, n_k, window):
    """(first, last) key block the band of q block j touches."""
    return (_ends_le(j * bq - window, 0, bk, n_k),
            _starts_le(j * bq + bq - 1, 0, bk, n_k) - 1)


def _band_queries(kk, bq, bk, n_q, window):
    """(first, last) q block whose rows' band touches key block kk."""
    return (_ends_le(kk * bk - 1, 0, bq, n_q),
            _starts_le(kk * bk + bk + window - 2, 0, bq, n_q) - 1)


def _key_steps(n_q, n_k, bq, bk, window):
    """The grid's key axis: every key block, or under a window as many as
    the widest band of a q block touches."""
    if window is None:
        return n_k
    return max(b - a + 1 for a, b in (_band_keys(j, bq, bk, n_k, window)
                                      for j in range(n_q)))


def _query_steps(n_q, n_k, bq, bk, window):
    """dkv's q axis (a query head's share of it): every q block, or under a
    window as many as reach the widest key block's band."""
    if window is None:
        return n_q
    return max(b - a + 1 for a, b in (_band_queries(kk, bq, bk, n_q, window)
                                      for kk in range(n_k)))


def _overlapped(lo, hi, step):
    """How many tiles of `step` the positions [lo, hi) touch."""
    return (hi - 1) // step - lo // step + 1


def causal_tile_counts(seq_len, block_q, block_k, sub_q, sub_k, causal,
                       block_mask=None, window=None):
    """(run, masked, total) sub-tiles of one head's seq_len x seq_len score
    square as the kernels walk it: computed, computed with a mask, and all.
    `run / total` is how far the causal skip engages (1.0 = not at all).
    ``block_mask`` = (half, blk) counts the two-stream block mask's walk
    (``flash_attention``): each half's rows over the clean keys, and the
    noised rows' own blocks. ``window`` counts the sliding window's walk:
    each q block over the key blocks its band touches."""
    if window is not None:
        n_q, n_k = seq_len // block_q, seq_len // block_k
        run = masked = 0
        for j in range(n_q):
            k_first, k_last = _band_keys(j, block_q, block_k, n_k, window)
            for kk in range(k_first, k_last + 1):
                for r0 in range(j * block_q, (j + 1) * block_q, sub_q):
                    first, lo, hi, end = _band_walk(
                        r0, sub_q, kk * block_k, sub_k, block_k // sub_k,
                        window)
                    if end > first:
                        run += end - first
                        masked += lo - first + end - hi
        return run, masked, (seq_len // sub_q) * (seq_len // sub_k)
    if block_mask is not None:
        half, blk = block_mask
        n_sk = block_k // sub_k
        run = masked = 0
        for noised in (False, True):
            for r0 in range(0, half, sub_q):  # a q sub-block, in its half
                j0 = r0 // block_q * block_q
                _, k_steps = _key_walk(j0, block_q, 0, block_k,
                                       half // block_k, True)
                for kk in range(k_steps):
                    n_full, n_run = _key_walk(r0, sub_q, kk * block_k, sub_k,
                                              n_sk, True, blk, noised)
                    run += n_run
                    masked += n_run - n_full
                if noised:
                    own = _overlapped(r0, r0 + sub_q, sub_k)
                    run += own
                    masked += own  # always built: a few tiles of the walk
        return run, masked, (seq_len // sub_q) * (seq_len // sub_k)
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


def _at_band_offset(n_q, n_k, bq, bk, window, off, walk):
    """``_at_block_offset`` under a sliding window, ``off`` the step's (traced)
    first row less first column: one predicated copy of the walk for each
    offset at which the diagonal or the band's lower edge crosses a block,
    one for all blocks wholly inside the band, none for a step whose block
    the band does not touch (skipped)."""
    offs = {d for d in (j * bq - kk * bk for j in range(n_q)
                        for kk in range(n_k)) if -bq < d < window + bk - 1}
    inside = {d for d in offs if bk - 1 <= d <= window - bq}
    for d in sorted(offs - inside):
        pl.when(off == d)(functools.partial(walk, d))
    if inside:
        pl.when((off >= bk - 1) & (off <= window - bq))(
            functools.partial(walk, None))


def _at_stream_block(n_qh, n_k, q_axis, k_axis, bq, bk, walk, q_wraps=False):
    """``_at_block_offset`` under the two-stream block mask: the q axis walks
    the clean half's n_qh blocks and then the noised half's, each against the
    n_k blocks of CLEAN keys, and a block's offset is taken inside its half.
    Calls walk(off, noised): a block the rounded diagonal crosses has one
    copy of the walk for clean rows and one for noised rows (which also meet
    their own blocks' noised keys there); a block wholly under it is the
    same walk for both. Offsets are multiples of bq and bk of bq, so the
    causal thresholds hold for the rounded diagonals too."""
    offs = {j * bq - kk * bk for j in range(n_qh) for kk in range(n_k)}
    j = pl.program_id(q_axis)
    if q_wraps:
        j = jax.lax.rem(j, np.int32(2 * n_qh))
    noised = j >= n_qh
    off = jnp.where(noised, j - n_qh, j) * bq - pl.program_id(k_axis) * bk
    for d in sorted(d for d in offs if -bq < d < bk - 1):
        pl.when((off == d) & ~noised)(functools.partial(walk, d, False))
        pl.when((off == d) & noised)(functools.partial(walk, d, True))
    if max(offs) >= bk - 1:
        pl.when(off >= bk - 1)(functools.partial(walk, None, False))


def _q_strips(off, bq, bk, sq, sk, blk=1, noised=False, window=None):
    """The forward's and dq's walk of a resident [bq, bk] block: one strip of
    scores for each q sub-block, over every key it attends to. Yields (rows,
    keys, (lo, hi), own): the strip's columns, and the columns before ``lo``
    (the band's lower edge) and from ``hi`` on (the diagonal) masked. A
    block wholly under the diagonal and inside the band (off None) is one
    strip. ``own``: for noised rows of the block mask, the columns of the
    NOISED key block at this block's place that hold the rows' own blocks
    (else None); their scores join the strip's."""
    if off is None:
        yield slice(0, bq), slice(0, bk), (0, bk), None
        return
    for i in range(bq // sq):
        r0 = off + i * sq
        if window:
            first, lo, hi, n_run = _band_walk(r0, sq, 0, sk, bk // sk, window)
            own = None
        else:
            n_full, n_run = _key_walk(r0, sq, 0, sk, bk // sk, True, blk,
                                      noised)
            first, lo, hi = 0, 0, n_full
            own = slice(r0, r0 + sq) if noised and 0 <= r0 < bk else None
        if n_run > first or own:  # else no key here these rows attend to
            yield (slice(i * sq, (i + 1) * sq), slice(first * sk, n_run * sk),
                   (lo * sk, hi * sk), own)


def _k_strips(off, bq, bk, sq, sk, blk=1, noised=False, window=None):
    """dkv's walk, transposed: one strip for each key sub-block, over every q
    row that attends to it. Yields (columns, rows, (masked, lower), own): the
    strip's rows before ``masked`` are of sub-tiles the diagonal crosses,
    those from ``lower`` on of sub-tiles the band's lower edge crosses.
    ``own`` strips (noised rows of the block mask) are of the NOISED key
    block at this block's place against the rows of the same blocks, and
    always masked."""
    if off is None:
        yield slice(0, bk), slice(0, bq), (0, bq), False
        return
    for c in range(bk // sk):
        cols = slice(c * sk, (c + 1) * sk)
        if window:
            r_first, r_full, r_lo, r_end = _band_query_walk(
                c * sk, sk, off, sq, bq // sq, window)
        else:
            r_first, r_full = _query_walk(c * sk, sk, off, sq, bq // sq, blk,
                                          noised)
            r_lo = r_end = bq // sq
        if r_first < r_end:  # else no row here attends to these keys
            yield (cols, slice(r_first * sq, r_end * sq),
                   ((r_full - r_first) * sq, (r_lo - r_first) * sq), False)
        lo, hi = max(c * sk - off, 0), min((c + 1) * sk - off, bq)
        if noised and lo < hi:
            yield cols, slice(lo, hi), (hi - lo, hi - lo), True


def _kv_index(causal, bq, bk, n_k, group=1, n_qh=None, own=False,
              window=None):
    """Index map of a k/v block under grid (head, q block j, k block kk). A
    step above the diagonal is skipped in the kernel: give it the index of
    the last step that runs, so that Pallas sees no change and copies
    nothing. Under a window the k axis counts from the first key block the
    q block's band touches. Under grouped-query heads (``group`` query heads on one KV
    head) query head i reads KV head i // group: k and v are never copied
    out to the query heads. Under the block mask (``n_qh`` q blocks a half)
    a q block's place is the one inside its half and the n_k key blocks are
    the clean half's; ``own`` names the noised key block that holds the q
    block's own blocks instead, whatever the step."""
    def index(i, j, kk):
        if n_qh is not None:
            j = jax.lax.rem(j, np.int32(n_qh))
        if own:
            kk = n_k + _div(j * bq, bk)
        elif window:
            first, last = _band_keys(j, bq, bk, n_k, window)
            kk = jnp.minimum(first + kk, last)
        elif causal and n_k > 1:
            _, k_steps = _key_walk(j * bq, bq, 0, bk, n_k, causal)
            kk = jnp.minimum(kk, k_steps - 1)
        if group > 1:
            i = jax.lax.div(i, np.int32(group))
        return (i, kk, _0)
    return index


def _causal_mask(s, row0, col0, keys_first=False, blk=1, before=False,
                 own=False, window=None):
    """Mask a score tile whose corner is (row0, col0) to the causal region
    (shared by all 3 kernels); `keys_first` for a tile with the keys down
    the sublanes. With positions in blocks of ``blk`` (a power of two): to
    the keys up to the end of the row's block, with ``before`` to the blocks
    before it, with ``own`` to the row's own block. With ``window``: to the
    band of the row's last ``window`` keys."""
    q_dim, k_dim = (1, 0) if keys_first else (0, 1)
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_dim)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, k_dim)
    if own:
        seen = (rows ^ cols) < blk
    elif before:
        seen = cols < (rows & np.int32(-blk))
    elif window:
        seen = (cols <= rows) & (rows - cols < np.int32(window))
    elif blk > 1:
        seen = cols <= (rows | np.int32(blk - 1))
    else:
        seen = cols <= rows
    return jnp.where(seen, s, NEG_INF)


def _mask_lanes(s, lo, hi, row0, col0, keys_first=False, blk=1, before=False,
                window=None):
    """Mask lanes [lo, hi) of a score strip, the sub-tiles the diagonal (or
    the band's lower edge) crosses; the lanes beside them lie wholly inside
    and pass untouched."""
    if lo == hi:
        return s
    parts = [s[:, :lo],
             _causal_mask(s[:, lo:hi], row0 + (lo if keys_first else 0),
                          col0 + (0 if keys_first else lo), keys_first, blk,
                          before, window=window),
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


def _own_refs(refs, mask):
    """((kn_ref, vn_ref), the other refs) of a kernel's refs after q, k, v:
    under the block mask the noised key block at the clean block's place
    rides along."""
    return (refs[:2], refs[2:]) if mask else ((None, None), refs)


def _key_parts(ref, own_ref, keys, own):
    """[(ref, columns)] a q strip meets: the block's ``keys`` (a slice) and,
    for noised rows of the block mask, their own blocks' noised keys."""
    parts = [(ref, keys)] if keys.stop > keys.start else []
    return parts if own is None else parts + [(own_ref, own)]


def _lanes(x, lo, hi):
    return x if (lo, hi) == (0, x.shape[1]) else x[:, lo:hi]


def _across(x, parts, times):
    """sum of times(x's lanes of a part, the part): a strip's probabilities
    (or their cotangents) against each part's values (or keys)."""
    total, lo = None, 0
    for ref, cols in parts:
        n = cols.stop - cols.start
        y = times(_lanes(x, lo, lo + n), ref, cols)
        total, lo = y if total is None else total + y, lo + n
    return total


def _strip_mask(parts, keys, masked, own, row0, blk, noised, window=None):
    """A q strip's scores, part by part (``_key_parts``), as one masked
    array: the clean keys' lanes from ``masked[1]`` on under the (rounded)
    diagonal and before ``masked[0]`` inside the band's lower edge, the own
    blocks' lanes to the row's own block."""
    out = []
    if keys.stop > keys.start:
        (lo, hi), c0 = masked, keys.start
        s = _mask_lanes(parts[0], hi - c0, keys.stop - c0, row0, c0, blk=blk,
                        before=noised, window=window)
        out.append(_mask_lanes(s, 0, lo - c0, row0, c0, window=window))
    if own is not None:
        out.append(_causal_mask(parts[-1], row0, own.start, blk=blk, own=True))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


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


def _fwd_kernel(q_ref, k_ref, v_ref, *refs,
                scale, causal, bq, bk, sq, sk, n_q, n_k, mask=None,
                window=None):
    # mask = (q blocks a half, blk) or None
    (kn_ref, vn_ref), (o_ref, lse_ref, *scratch) = _own_refs(refs, mask)
    blk = mask[1] if mask else 1
    scale = np.float32(scale)
    if scratch:  # (m, l, acc) carried from one k step to the next
        m_scr, l_scr, acc_scr = scratch

        @pl.when(pl.program_id(2) == 0)
        def _():
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

    def scores(strip):
        rows, keys, _, own = strip
        return [_mm(q_ref[0, rows, :], ref[0, cols, :], _NT) * scale
                for ref, cols in _key_parts(k_ref, kn_ref, keys, own)]

    def times_v(p, ref, cols):
        v = ref[0, cols, :]
        return _mm(p.astype(v.dtype), v, _NN)

    def walk(off, noised=False):
        for (rows, keys, masked, own), s in _ahead(
                _q_strips(off, bq, bk, sq, sk, blk, noised, window), scores):
            n = rows.stop - rows.start
            s = _strip_mask(s, keys, masked, own, (off or 0) + rows.start,
                            blk, noised, window)
            m = jnp.max(s, axis=-1, keepdims=True)
            if scratch:
                m_prev = m_scr[rows, :1]
                m = jnp.maximum(m_prev, m)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            acc = _across(p, _key_parts(v_ref, vn_ref, keys, own), times_v)
            if scratch:
                alpha = jnp.exp(m_prev - m)
                m_scr[rows, :] = jnp.broadcast_to(m, (n, m_scr.shape[1]))
                l_scr[rows, :] = jnp.broadcast_to(
                    alpha * l_scr[rows, :1] + l, (n, l_scr.shape[1]))
                acc_scr[rows, :] = acc_scr[rows, :] * alpha + acc
            else:
                _finish(m, l, acc, o_ref, lse_ref, rows)

    if mask:
        _at_stream_block(mask[0], n_k, 1, 2, bq, bk, walk)
    elif window:
        j = pl.program_id(1)
        kk = _band_keys(j, bq, bk, n_k, window)[0] + pl.program_id(2)
        _at_band_offset(n_q, n_k, bq, bk, window, j * bq - kk * bk, walk)
    else:
        _at_block_offset(n_q, n_k, 1, 2, bq, bk, causal, walk)

    if scratch:
        @pl.when(pl.program_id(2)
                 == _key_steps(n_q, n_k, bq, bk, window) - 1)
        def _():
            _finish(m_scr[:, :1], l_scr[:, :1], acc_scr[:], o_ref, lse_ref,
                    slice(None))


def _grid_of(s, bq, bk, mask):
    """(q blocks, key blocks, the kernels' ``mask``): under the block mask
    (half, blk) the q blocks are the whole stream's and the key blocks the
    clean half's."""
    if mask is None:
        return s // bq, s // bk, None
    half, blk = mask
    return s // bq, half // bk, (half // bq, blk)


def _kv_specs(causal, bq, bk, d, n_k, group, mask, window=None):
    """The in_specs of k and v under grid (head, q block, k block), and of
    the noised blocks that ride along under the block mask."""
    n_qh = mask[0] if mask else None
    kv_index = _kv_index(causal, bq, bk, n_k, group, n_qh, window=window)
    specs = [pl.BlockSpec((1, bk, d), kv_index)] * 2
    if mask:
        own = _kv_index(causal, bq, bk, n_k, group, n_qh, own=True)
        specs += [pl.BlockSpec((1, bk, d), own)] * 2
    return specs


def _fwd(q, k, v, scale, causal, bq, bk, sq, sk, group=1, mask=None,
         window=None):
    bh, s, d = q.shape
    n_q, n_k, mask = _grid_of(s, bq, bk, mask)
    steps = _key_steps(n_q, n_k, bq, bk, window)
    kv = _kv_specs(causal, bq, bk, d, n_k, group, mask, window)
    # one k step: the running (m, l, acc) never leave the step's registers
    scratch = [] if steps == 1 else [
        pltpu.VMEM((bq, 128), jnp.float32),
        pltpu.VMEM((bq, 128), jnp.float32),
        pltpu.VMEM((bq, d), jnp.float32),
    ]
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, sq=sq, sk=sk, n_q=n_q, n_k=n_k, mask=mask,
                          window=window),
        name="flash_attention_window_fwd" if window else "flash_attention_fwd",
        grid=(bh, n_q, steps),
        in_specs=[pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, _0)), *kv],
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
    )(q, k, v, *((k, v) if mask else ()))
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dkv_kernel(q_ref, k_ref, v_ref, *refs,
                    scale, causal, bq, bk, sq, sk, n_q, n_k, group=1,
                    mask=None, window=None):
    # under the block mask the noised key block has gradients of its own
    # (dkn, dvn): its keys are met by the noised rows of the same blocks alone
    (kn_ref, vn_ref), (do_ref, lse_ref, delta_ref, *refs) = _own_refs(refs,
                                                                      mask)
    n_out = 4 if mask else 2
    outs, scratch = refs[:n_out], refs[n_out:]
    blk = mask[1] if mask else 1
    scale = np.float32(scale)
    if scratch:  # (dk, dv) carried from one q step to the next, and from one
        # query head of the group to the next: they come out summed over it
        @pl.when(pl.program_id(2) == 0)
        def _():
            for scr in scratch:
                scr[:] = jnp.zeros_like(scr)

    def products(strip):
        # scores with the keys down the sublanes, [keys, rows]: lse and delta
        # are rows as they lie in memory, and nothing is transposed
        cols, rows, _, own = strip
        k, v = (kn_ref, vn_ref) if own else (k_ref, v_ref)
        return (_mm(k[0, cols, :], q_ref[0, rows, :], _NT) * scale,
                _mm(v[0, cols, :], do_ref[0, rows, :], _NT))

    def walk(off, noised=False):
        times_do, times_q = _times(do_ref), _times(q_ref)
        for (cols, rows, masked, own), (st, dpt) in _ahead(
                _k_strips(off, bq, bk, sq, sk, blk, noised, window),
                products):
            if own:
                st = _causal_mask(st, off + rows.start, cols.start,
                                  keys_first=True, blk=blk, own=True)
            else:
                row0 = (off or 0) + rows.start
                st = _mask_lanes(st, 0, masked[0], row0, cols.start,
                                 keys_first=True, blk=blk, before=noised,
                                 window=window)
                st = _mask_lanes(st, masked[1], rows.stop - rows.start, row0,
                                 cols.start, keys_first=True, window=window)
            pt = jnp.exp(st - lse_ref[0, :, rows])
            dst = pt * (dpt - delta_ref[0, :, rows])
            dv = times_do(pt.astype(do_ref.dtype), rows)
            dk = times_q((dst * scale).astype(q_ref.dtype), rows)
            to = 2 if own else 0
            if scratch:
                scratch[to][cols, :] += dk
                scratch[to + 1][cols, :] += dv
            else:
                outs[to][0, cols, :] = dk.astype(outs[to].dtype)
                outs[to + 1][0, cols, :] = dv.astype(outs[to + 1].dtype)

    steps = _query_steps(n_q, n_k, bq, bk, window)
    if mask:
        _at_stream_block(mask[0], n_k, 2, 1, bq, bk, walk, q_wraps=group > 1)
    elif window:
        kk, t = pl.program_id(1), pl.program_id(2)
        if group > 1:
            t = jax.lax.rem(t, np.int32(steps))
        first, last = _band_queries(kk, bq, bk, n_q, window)
        j = first + t
        # a step past the sequence's last q block refetched that block: it
        # is given an offset the band does not touch
        off = jnp.where(j <= last, j * bq - kk * bk,
                        np.int32(window + bk - 1))
        _at_band_offset(n_q, n_k, bq, bk, window, off, walk)
    else:
        _at_block_offset(n_q, n_k, 2, 1, bq, bk, causal, walk,
                         q_wraps=group > 1)

    if scratch:
        @pl.when(pl.program_id(2) == group * steps - 1)
        def _():
            for out, scr in zip(outs, scratch):
                out[0] = scr[:].astype(out.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, *refs,
                   scale, causal, bq, bk, sq, sk, n_q, n_k, mask=None,
                   window=None):
    (kn_ref, vn_ref), (do_ref, lse_ref, delta_ref, dq_ref, *scratch) = \
        _own_refs(refs, mask)
    blk = mask[1] if mask else 1
    scale = np.float32(scale)
    if scratch:  # dq carried from one k step to the next
        dq_scr, = scratch

        @pl.when(pl.program_id(2) == 0)
        def _():
            dq_scr[:] = jnp.zeros_like(dq_scr)

    def products(strip):
        rows, keys, _, own = strip
        s = [_mm(q_ref[0, rows, :], ref[0, cols, :], _NT) * scale
             for ref, cols in _key_parts(k_ref, kn_ref, keys, own)]
        dp = [_mm(do_ref[0, rows, :], ref[0, cols, :], _NT)
              for ref, cols in _key_parts(v_ref, vn_ref, keys, own)]
        return s, dp[0] if len(dp) == 1 else jnp.concatenate(dp, axis=1)

    def walk(off, noised=False):
        times_k = _times(k_ref)
        times_kn = _times(kn_ref) if noised else None
        for (rows, keys, masked, own), (s, dp) in _ahead(
                _q_strips(off, bq, bk, sq, sk, blk, noised, window),
                products):
            s = _strip_mask(s, keys, masked, own, (off or 0) + rows.start,
                            blk, noised, window)
            p = jnp.exp(s - lse_ref[0, 0, rows][:, None])
            ds = p * (dp - delta_ref[0, 0, rows][:, None])
            dq = _across((ds * scale).astype(k_ref.dtype),
                         _key_parts(k_ref, kn_ref, keys, own),
                         lambda x, ref, cols: (
                             times_k if ref is k_ref else times_kn)(x, cols))
            if scratch:
                dq_scr[rows, :] += dq
            else:
                dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)

    if mask:
        _at_stream_block(mask[0], n_k, 1, 2, bq, bk, walk)
    elif window:
        j = pl.program_id(1)
        kk = _band_keys(j, bq, bk, n_k, window)[0] + pl.program_id(2)
        _at_band_offset(n_q, n_k, bq, bk, window, j * bq - kk * bk, walk)
    else:
        _at_block_offset(n_q, n_k, 1, 2, bq, bk, causal, walk)

    if scratch:
        @pl.when(pl.program_id(2)
                 == _key_steps(n_q, n_k, bq, bk, window) - 1)
        def _():
            dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv(q, k, v, do, lse, delta, *, scale, causal, bq, bk, sq, sk, group=1,
         mask=None, window=None):
    bh, s, d = k.shape  # the grid's heads are the KV heads
    n_q, n_k, mask = _grid_of(s, bq, bk, mask)
    steps = _query_steps(n_q, n_k, bq, bk, window)

    def q_index(kk, j):
        # a q block whose every row is above this k block's first column is
        # skipped: name the first block that runs instead, so nothing is copied
        if mask:  # in each half of the stream alike
            n_qh = np.int32(mask[0])
            j_first, _ = _query_walk(kk * bk, bk, 0, bq, mask[0])
            noised = jax.lax.div(j, n_qh)
            return jnp.maximum(j - noised * n_qh, j_first) + noised * n_qh
        if window:  # the q blocks from the first whose rows reach kk
            first, last = _band_queries(kk, bq, bk, n_q, window)
            return jnp.minimum(first + j, last)
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
        # blocks: KV head i is attended by query heads i * group + t // steps
        def q_head(i, t):
            return i * np.int32(group) + jax.lax.div(t, np.int32(steps))

        def q_block(kk, t):
            return q_index(kk, jax.lax.rem(t, np.int32(steps)))

    # one q step: dk and dv never leave the step's registers
    scratch = [] if group * steps == 1 else [pltpu.VMEM((bk, d), jnp.float32),
                                           pltpu.VMEM((bk, d), jnp.float32)]
    own = [pl.BlockSpec((1, bk, d), lambda i, kk, j: (i, n_k + kk, _0))] * 2
    out_rows = n_k * bk  # under the block mask: a half's keys an output
    got = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, sq=sq, sk=sk, n_q=n_q, n_k=n_k, group=group,
                          mask=mask, window=window),
        name=("flash_attention_window_bwd_dkv" if window
              else "flash_attention_bwd_dkv"),
        grid=(bh, n_k, group * steps),
        in_specs=[
            pl.BlockSpec((1, bq, d),
                         lambda i, kk, j: (q_head(i, j), q_block(kk, j), _0)),
            pl.BlockSpec((1, bk, d), lambda i, kk, j: (i, kk, _0)),
            pl.BlockSpec((1, bk, d), lambda i, kk, j: (i, kk, _0)),
            *(own if mask else ()),
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
        ] * (2 if mask else 1),
        out_shape=[
            jax.ShapeDtypeStruct((bh, out_rows, d), k.dtype),
            jax.ShapeDtypeStruct((bh, out_rows, d), v.dtype),
        ] * (2 if mask else 1),
        scratch_shapes=scratch * (2 if mask else 1),
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(q, k, v, *((k, v) if mask else ()), do, lse, delta)
    if mask:  # (dk, dv, dkn, dvn): the halves side by side again
        return (jnp.concatenate(got[0::2], axis=1),
                jnp.concatenate(got[1::2], axis=1))
    return got


def _dq(q, k, v, do, lse, delta, *, scale, causal, bq, bk, sq, sk, group=1,
        mask=None, window=None):
    bh, s, d = q.shape
    n_q, n_k, mask = _grid_of(s, bq, bk, mask)
    steps = _key_steps(n_q, n_k, bq, bk, window)
    scratch = [] if steps == 1 else [pltpu.VMEM((bq, d), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, sq=sq, sk=sk, n_q=n_q, n_k=n_k, mask=mask,
                          window=window),
        name=("flash_attention_window_bwd_dq" if window
              else "flash_attention_bwd_dq"),
        grid=(bh, n_q, steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, _0)),
            *_kv_specs(causal, bq, bk, d, n_k, group, mask, window),
            pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, _0)),
            pl.BlockSpec((1, 1, bq), lambda i, j, kk: (i, _0, j)),
            pl.BlockSpec((1, 1, bq), lambda i, j, kk: (i, _0, j)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, _0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=scratch,
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(q, k, v, *((k, v) if mask else ()), do, lse, delta)


def _bwd(scale, causal, bq, bk, sq, sk, group, mask, window, res, do):
    q, k, v, out, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, None, :]
    sizes = dict(scale=scale, causal=causal, bq=bq, bk=bk, sq=sq, sk=sk,
                 group=group, mask=mask, window=window)
    dk, dv = _dkv(q, k, v, do, lse, delta, **sizes)
    dq = _dq(q, k, v, do, lse, delta, **sizes)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, scale, causal, bq, bk, sq, sk, group, mask, window):
    out, _ = _fwd(q, k, v, scale, causal, bq, bk, sq, sk, group, mask, window)
    return out


def _flash_fwd(q, k, v, scale, causal, bq, bk, sq, sk, group, mask, window):
    out, lse = _fwd(q, k, v, scale, causal, bq, bk, sq, sk, group, mask,
                    window)
    return out, (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


def supports(seq_len: int, head_dim: int, block_q: int = None,
             block_k: int = 1024, window: int = None) -> bool:
    """Shapes the kernel accepts (everything else falls back to the XLA path).

    The kernel covers the sequence either with one full-array block
    (seq <= block) or with an exact tiling — a seq that is neither would
    leave tail rows unwritten, so it must be rejected here. A ``window``
    (a sliding window of that many keys) is walked at any width of 1 or
    more."""
    if window is not None and window < 1:
        return False
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


def _mask_blocks(seq_len, block_mask, block_q=None, block_k=1024):
    """(bq, bk, sq, sk) the two-stream block mask (half, blk) is walked
    with, or the short reason why the kernels cannot take it: the halves in
    whole blocks, a key block in whole q blocks, the mask's blocks a power
    of two that divides the sub-tiles."""
    half, blk = block_mask
    if seq_len != 2 * half:
        return "stream_is_not_two_halves"
    bq = min(block_q or _default_block_q(half), half)
    bk = min(block_k, half)
    if half % bq or half % bk or bk % bq:
        return "half_not_tiled"
    sq, sk = _default_sub_tiles(bq, bk)
    if blk < 1 or blk & (blk - 1) or sq % blk or sk % blk:
        return "block_length"
    return bq, bk, sq, sk


def supports_block_mask(seq_len: int, head_dim: int, block_mask) -> bool:
    """``supports`` for the two-stream block mask (half, blk)."""
    return (not isinstance(_mask_blocks(seq_len, block_mask), str)
            and block_mask[0] >= 8 and head_dim % 8 == 0)


def flash_attention(q, k, v, *, scale=None, causal=True, block_q=None,
                    block_k=1024, block_mask=None, window=None):
    """Streaming attention over [batch, seq, heads, head_dim] inputs
    (paddle fused_attention layout, matching scaled_dot_product_attention).

    ``block_mask`` = (half, blk), in the place of ``causal``: the two-stream
    mask of block-diffusion training. The sequence is a stream of two halves
    of ``half`` positions, the clean tokens and then the noised ones, each
    cut into blocks of ``blk``. A clean position attends the clean positions
    up to the end of its block; a noised position attends the clean blocks
    before its own and the noised positions of its own block; no clean
    position attends a noised one. The kernels walk the stream's q blocks
    over the clean half's key blocks as the causal walk does, the diagonal
    rounded to the mask's blocks (up to the block's end for clean rows, to
    its start for noised ones); the noised key block that holds a q block's
    own blocks rides along with the step the diagonal crosses and its
    sub-tiles on the block diagonal join that step's strips. The quadrant
    clean-on-noised is never fetched; no position-squared array exists.

    ``window`` = W, with ``causal``: a sliding window, query i attends key j
    iff i - W < j <= i (the ``transformers`` convention: W keys, its own
    included). The kernels walk only the band: key blocks before it are no
    grid step and are never fetched, and the sub-tiles its lower edge
    crosses are masked as the diagonal's are. A window of the whole
    sequence or more is the causal walk.

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
    if window is not None:
        window = int(window)
        if block_mask is not None or not causal or window < 1:
            raise ValueError(
                f"flash_attention: a window ({window}) is a causal band of "
                "one or more keys, with no block mask")
        if window >= s:
            window = None
    if block_mask is not None:
        block_mask = tuple(map(int, block_mask))
        blocks = _mask_blocks(s, block_mask, block_q, block_k)
        if isinstance(blocks, str):
            raise ValueError(
                f"flash_attention: block mask {block_mask} on a stream of "
                f"{s}: {blocks}; use the dense path")
        (bq, bk, sq, sk), causal = blocks, True
    else:
        if block_q is None:
            block_q = _default_block_q(s)
        bq = min(block_q, s)
        bk = min(block_k, s)
        if s % bq != 0 or s % bk != 0:
            raise ValueError(
                f"flash_attention: seq_len {s} is not divisible by block "
                f"sizes ({bq}, {bk}) — tail rows would be left unwritten; "
                "pad the sequence or use the dense path"
            )
        sq, sk = _default_sub_tiles(bq, bk)
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    from ...profiler import trace
    run, masked, total = causal_tile_counts(s, bq, bk, sq, sk, bool(causal),
                                            block_mask, window)
    kind = ({"mask": "block_diffusion", "half": block_mask[0],
             "block": block_mask[1]} if block_mask
            else {"mask": "window", "window": window} if window
            else {"mask": "causal" if causal else "full"})
    trace.emit("flash_tiles", site="flash_attention", seq=s, block_q=bq,
               block_k=bk, sub_q=sq, sub_k=sk, run=run, masked=masked,
               total=total, **kind)

    def to_bh(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * x.shape[2], s, d)

    out = _flash(to_bh(q), to_bh(k), to_bh(v), np.float32(scale), bool(causal),
                 bq, bk, sq, sk, group, block_mask, window)
    return jnp.swapaxes(out.reshape(b, h, s, d), 1, 2)
