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

Causal masking skips fully-masked tiles via predication. Accumulation is
always f32 regardless of input dtype (bf16 in → bf16 out, f32 math).
Off the TPU (tests/dev on CPU) the kernel runs in interpret mode.
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
    """bq=1024 up to seq 4096, 512 past it (a builder's block sweep from
    before PR 1, not in the ledger; re-measure when the benchmark has the
    s4096 cell). Seqs in (2048, 4096] that 1024 does not divide (2560,
    3584...) keep 512 — the wider default must never SHRINK the eligible
    set. Shared by flash_attention and supports() so eligibility always
    mirrors the kernel."""
    if seq_len <= 2048:
        return 1024
    if seq_len <= 4096 and seq_len % 1024 == 0:
        return 1024
    return 512


_0 = np.int32(0)  # index-map literal; Python ints trace to i64 under x64


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _compiler_params(dims):
    return pltpu.CompilerParams(dimension_semantics=dims)


def _causal_mask(s, j, kk, bq, bk):
    """Mask score tile `s` to the causal region (shared by all 3 kernels)."""
    rows = j * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(cols <= rows, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, bq, bk, n_k):
    j = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: tiles entirely above the diagonal contribute nothing
    run = True if not causal else (kk * bk <= j * bq + bq - 1)

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * np.float32(scale)  # [bq, bk]
        if causal:
            s = _causal_mask(s, j, kk, bq, bk)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kk == n_k - 1)
    def _():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:, 0] + jnp.log(safe_l[:, 0])).astype(jnp.float32)


def _fwd(q, k, v, scale, causal, bq, bk):
    bh, s, d = q.shape
    n_q, n_k = s // bq, s // bk
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk, n_k=n_k
    )
    out, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, _0)),
            pl.BlockSpec((1, bk, d), lambda i, j, kk: (i, kk, _0)),
            pl.BlockSpec((1, bk, d), lambda i, j, kk: (i, kk, _0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, _0)),
            pl.BlockSpec((1, 1, bq), lambda i, j, kk: (i, _0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, bq, bk, n_q):
    kk = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True if not causal else (kk * bk <= j * bq + bq - 1)

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0][:, None]      # [bq, 1]
        delta = delta_ref[0, 0][:, None]  # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * np.float32(scale)
        if causal:
            s = _causal_mask(s, j, kk, bq, bk)
        p = jnp.exp(s - lse)  # [bq, bk]
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * np.float32(scale)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == n_q - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, scale, causal, bq, bk, n_k):
    j = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True if not causal else (kk * bk <= j * bq + bq - 1)

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * np.float32(scale)
        if causal:
            s = _causal_mask(s, j, kk, bq, bk)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * np.float32(scale)
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kk == n_k - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd(scale, causal, bq, bk, res, do):
    q, k, v, out, lse = res
    bh, s, d = q.shape
    n_q, n_k = s // bq, s // bk
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, None, :]

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk, n_q=n_q
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_attention_bwd_dkv",
        grid=(bh, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, kk, j: (i, j, _0)),
            pl.BlockSpec((1, bk, d), lambda i, kk, j: (i, kk, _0)),
            pl.BlockSpec((1, bk, d), lambda i, kk, j: (i, kk, _0)),
            pl.BlockSpec((1, bq, d), lambda i, kk, j: (i, j, _0)),
            pl.BlockSpec((1, 1, bq), lambda i, kk, j: (i, _0, j)),
            pl.BlockSpec((1, 1, bq), lambda i, kk, j: (i, _0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda i, kk, j: (i, kk, _0)),
            pl.BlockSpec((1, bk, d), lambda i, kk, j: (i, kk, _0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk, n_k=n_k
    )
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_attention_bwd_dq",
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, _0)),
            pl.BlockSpec((1, bk, d), lambda i, j, kk: (i, kk, _0)),
            pl.BlockSpec((1, bk, d), lambda i, j, kk: (i, kk, _0)),
            pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, _0)),
            pl.BlockSpec((1, 1, bq), lambda i, j, kk: (i, _0, j)),
            pl.BlockSpec((1, 1, bq), lambda i, j, kk: (i, _0, j)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, _0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, bq, bk):
    out, _ = _fwd(q, k, v, scale, causal, bq, bk)
    return out


def _flash_fwd(q, k, v, scale, causal, bq, bk):
    out, lse = _fwd(q, k, v, scale, causal, bq, bk)
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

    Default blocks are shape-adaptive (measured on v5e): at seq <= 2048 a
    full-row q block (1024) is ~25% faster; longer sequences use bq=512,
    whose Mosaic compile is ~50x faster at equal runtime.
    """
    b, s, h, d = q.shape
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

    def to_bh(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)

    out = _flash(to_bh(q), to_bh(k), to_bh(v), np.float32(scale), bool(causal), bq, bk)
    return jnp.swapaxes(out.reshape(b, h, s, d), 1, 2)
