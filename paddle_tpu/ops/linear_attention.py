"""Recurrent-state ("linear attention") layers: the gated delta rule as a
chunked scan, the short causal convolution before it, the gated norm after.

The gated delta rule keeps one [d_k, d_v] float32 state S per head:

    S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T
    o_t = S^T q_t

Computed token by token it is 8,192 dependent steps of vector work. Here a
sequence is cut into chunks of C tokens. Inside a chunk the rule is matrix
products (the WY form): with gamma the running sum of g inside the chunk and
A[i, j] = beta_i exp(gamma_i - gamma_j) k_i.k_j for j < i, the corrections of
a chunk are U = (I + A)^-1 beta (V - exp(gamma) K S_0), its outputs
exp(gamma) Q S_0 + (mask(Q K^T) exp(gamma_i - gamma_j)) U and its last state
exp(gamma_C) S_0 + (exp(gamma_C - gamma) K)^T U. Everything that does not
need S_0 is made for all chunks at once by XLA (``_chunk_operands``; plain
jax.numpy, differentiated by JAX); what does is one pass over the chunks with
the state held in VMEM: the Pallas kernels ``gated_delta_rule_fwd`` and
``gated_delta_rule_bwd`` (``_state_pass``, a custom_vjp: the backward walks
the chunks in reverse with dS in VMEM, from the states the forward kept).

Operands of the products are in the type the inputs come in (bf16 under
AMP-O2), sums, gates, decays, the inverse and the state in float32 (the
inverse's own products in three bf16 passes). Off the TPU the kernels run in
interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..incubate.recompute import KEEP_NAME

F32 = jnp.float32
# the inverse's products: three bf16 passes (an error near 2^-16 a product,
# under the bf16 rounding of the inverse as it is handed out). On the chip the
# products are HBM-bound, so HIGHEST's six passes would take the same time
# (PERF.md section 5); the benchmark cell's limits were read at HIGH, and its
# configuration's ``precision`` states HIGH
INVERSE_PRECISION = jax.lax.Precision.HIGH
STEP_ROWS = 512  # rows of a sequence one grid step of the state pass holds


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# the layers round the rule
# ---------------------------------------------------------------------------
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
def short_conv_silu(x, weight):
    """silu(causal depthwise conv) over [batch, seq, channels] with
    ``weight`` [channels, kernel], no bias: y_t = silu(sum_i w[:, i]
    x_(t-K+1+i)), zeros before the sequence's start. Sums in float32. The
    backward is written out (the conv is made again, its transpose is the
    same taps read ahead): derived, it is a pad and a reduction per tap."""
    return jax.nn.silu(_causal_taps(x, weight)).astype(x.dtype)


def _short_conv_fwd(x, weight):
    return short_conv_silu(x, weight), (x, weight)


def _short_conv_bwd(res, dy):
    x, weight = res
    k, seq = weight.shape[-1], x.shape[1]
    c = _causal_taps(x, weight)
    sig = jax.nn.sigmoid(c)
    dc = (dy.astype(F32) * sig * (1.0 + c * (1.0 - sig))).astype(x.dtype)
    dx = _causal_taps(dc, weight, ahead=True).astype(x.dtype)
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    dw = jnp.stack([jnp.einsum("bsc,bsc->c", dc, xp[:, i:i + seq],
                               preferred_element_type=F32)
                    for i in range(k)], axis=-1)
    return dx, dw.astype(weight.dtype)


short_conv_silu.defvjp(_short_conv_fwd, _short_conv_bwd)


def l2_normalize(x, *, epsilon=1e-6):
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


def gated_rms_norm(o, z, weight, *, epsilon=1e-6):
    """o / rms(o) * weight * silu(z) over the last axis (one head), in
    float32; the gain is not zero-centred."""
    of = o.astype(F32)
    var = jnp.mean(jnp.square(of), axis=-1, keepdims=True)
    y = of * jax.lax.rsqrt(var + epsilon) * weight.astype(F32)
    return (y * jax.nn.silu(z.astype(F32))).astype(o.dtype)


# ---------------------------------------------------------------------------
# (I + A)^-1 for a strictly lower triangular A
# ---------------------------------------------------------------------------
def _mm_hi(a, b):
    return jnp.matmul(a, b, precision=INVERSE_PRECISION)


def _inverse_by_doubling(a):
    c = a.shape[-1]
    x = jnp.eye(c, dtype=a.dtype) - a
    p, n = a, 2
    while n < c:
        p = _mm_hi(p, p)
        x = x + _mm_hi(x, p)
        n *= 2
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def unit_lower_inverse(a, dtype):
    """(I + a)^-1 in ``dtype`` for strictly lower triangular ``a`` [..., C,
    C] float32: a is nilpotent, so the inverse is the finite sum of (-a)^k,
    gathered by doubling, (I - a)(I + a^2)(I + a^4)...: 2 log2(C) - 2
    products and no dependent loop over rows. The backward needs the inverse
    alone, as it was handed out: it is tagged ``KEEP_NAME``, so a recomputed
    layer does not make it twice."""
    return _inverse_by_doubling(a).astype(dtype)


def _inverse_fwd(a, dtype):
    t = checkpoint_name(_inverse_by_doubling(a).astype(dtype), KEEP_NAME)
    return t, t


def _inverse_bwd(dtype, t, dt):
    tt = jnp.swapaxes(t.astype(F32), -1, -2)
    return (-_mm_hi(_mm_hi(tt, dt.astype(F32)), tt),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


# ---------------------------------------------------------------------------
# the pass over the chunks, state in VMEM
# ---------------------------------------------------------------------------
def _mm(a, b, dims=((1,), (0,))):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=F32)


_NT = ((1,), (1,))  # a @ b.T
_TN = ((0,), (0,))  # a.T @ b


def _fwd_kernel(w_ref, u0_ref, qg_ref, attn_ref, kd_ref, dec_ref,
                o_ref, u_ref, h_ref, s_scr, *, chunk, per_step):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[:] = jnp.zeros_like(s_scr)

    cdt = w_ref.dtype
    s = s_scr[:]
    for c in range(per_step):
        rows = slice(c * chunk, (c + 1) * chunk)
        sb = s.astype(cdt)
        h_ref[0, c] = sb  # the state this chunk starts from, for the backward
        u = u0_ref[0, rows, :].astype(F32) - _mm(w_ref[0, rows, :], sb)
        ub = u.astype(cdt)
        o = _mm(qg_ref[0, rows, :], sb) + _mm(attn_ref[0, rows, :], ub)
        o_ref[0, rows, :] = o.astype(o_ref.dtype)
        u_ref[0, rows, :] = ub
        s = s * dec_ref[0, c:c + 1, :] + _mm(kd_ref[0, rows, :], ub, _TN)
    s_scr[:] = s


def _bwd_kernel(w_ref, qg_ref, attn_ref, kd_ref, dec_ref, u_ref, h_ref,
                do_ref, dw_ref, du0_ref, dqg_ref, dattn_ref, dkd_ref, ddec_ref,
                ds_scr, *, chunk, per_step):
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[:] = jnp.zeros_like(ds_scr)

    cdt = w_ref.dtype
    ds = ds_scr[:]  # d loss / d (the state the NEXT chunk starts from)
    for c in reversed(range(per_step)):
        rows = slice(c * chunk, (c + 1) * chunk)
        s0 = h_ref[0, c]
        dsb = ds.astype(cdt)
        dob, ub = do_ref[0, rows, :], u_ref[0, rows, :]
        du = _mm(attn_ref[0, rows, :], dob, _TN) + _mm(kd_ref[0, rows, :], dsb)
        dub = du.astype(cdt)
        dattn_ref[0, rows, :] = _mm(dob, ub, _NT).astype(dattn_ref.dtype)
        dqg_ref[0, rows, :] = _mm(dob, s0, _NT).astype(dqg_ref.dtype)
        dkd_ref[0, rows, :] = _mm(ub, dsb, _NT).astype(dkd_ref.dtype)
        dw_ref[0, rows, :] = (-_mm(dub, s0, _NT)).astype(dw_ref.dtype)
        du0_ref[0, rows, :] = dub
        # column j of dec scales column j of the state
        ddec_ref[0, c:c + 1, :] = jnp.sum(ds * s0.astype(F32), axis=0,
                                          keepdims=True)
        ds = (_mm(qg_ref[0, rows, :], dob, _TN) + ds * dec_ref[0, c:c + 1, :]
              - _mm(w_ref[0, rows, :], dub, _TN))
    ds_scr[:] = ds


def _per_step(n_chunks, chunk):
    """Chunks one grid step holds: STEP_ROWS rows where that tiles the
    sequence into whole (8, 128) blocks of the per-chunk decay, else all."""
    want = max(1, STEP_ROWS // chunk)
    return want if n_chunks % want == 0 and want % 8 == 0 else n_chunks


_0 = np.int32(0)  # index-map literal; Python ints trace to i64 under x64


def _specs(seq, chunk, per_step, dk, dv, reverse):
    n_blocks = seq // (chunk * per_step)

    def block(j):
        return np.int32(n_blocks - 1) - j if reverse else j

    rows = chunk * per_step
    return n_blocks, {
        "k": pl.BlockSpec((1, rows, dk), lambda i, j: (i, block(j), _0)),
        "v": pl.BlockSpec((1, rows, dv), lambda i, j: (i, block(j), _0)),
        "c": pl.BlockSpec((1, rows, chunk), lambda i, j: (i, block(j), _0)),
        "dec": pl.BlockSpec((1, per_step, dv), lambda i, j: (i, block(j), _0)),
        "h": pl.BlockSpec((1, per_step, dk, dv),
                          lambda i, j: (i, block(j), _0, _0)),
    }


def _pass_fwd(w, u0, qg, attn, kd, dec, chunk):
    bh, seq, dk = w.shape
    dv = u0.shape[-1]
    per_step = _per_step(seq // chunk, chunk)
    n_blocks, sp = _specs(seq, chunk, per_step, dk, dv, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, per_step=per_step),
        name="gated_delta_rule_fwd",
        grid=(bh, n_blocks),
        in_specs=[sp["k"], sp["v"], sp["k"], sp["c"], sp["k"], sp["dec"]],
        out_specs=[sp["v"], sp["v"], sp["h"]],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, dv), u0.dtype),
            jax.ShapeDtypeStruct((bh, seq, dv), u0.dtype),
            jax.ShapeDtypeStruct((bh, seq // chunk, dk, dv), u0.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(w, u0, qg, attn, kd, dec)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _state_pass(w, u0, qg, attn, kd, dec, chunk):
    """o [BH, L, dv] from the chunks' operands, all [BH, L, .]: w, qg, kd
    [.., dk], u0 [.., dv], attn [.., chunk]; dec [BH, L / chunk, dv] float32,
    a chunk's whole decay repeated along the last axis."""
    return _pass_fwd(w, u0, qg, attn, kd, dec, chunk)[0]


def _state_pass_fwd(w, u0, qg, attn, kd, dec, chunk):
    o, u, h = _pass_fwd(w, u0, qg, attn, kd, dec, chunk)
    return o, (w, qg, attn, kd, dec, u, h)


def _state_pass_bwd(chunk, res, do):
    w, qg, attn, kd, dec, u, h = res
    bh, seq, dk = w.shape
    dv = u.shape[-1]
    per_step = _per_step(seq // chunk, chunk)
    n_blocks, sp = _specs(seq, chunk, per_step, dk, dv, reverse=True)
    like = jax.ShapeDtypeStruct
    dw, du0, dqg, dattn, dkd, ddec = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, per_step=per_step),
        name="gated_delta_rule_bwd",
        grid=(bh, n_blocks),
        in_specs=[sp["k"], sp["k"], sp["c"], sp["k"], sp["dec"], sp["v"],
                  sp["h"], sp["v"]],
        out_specs=[sp["k"], sp["v"], sp["k"], sp["c"], sp["k"], sp["dec"]],
        out_shape=[like(w.shape, w.dtype), like(u.shape, u.dtype),
                   like(qg.shape, qg.dtype), like(attn.shape, attn.dtype),
                   like(kd.shape, kd.dtype), like(dec.shape, F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(w, qg, attn, kd, dec, u, h, do.astype(u.dtype))
    return dw, du0, dqg, dattn, dkd, ddec


_state_pass.defvjp(_state_pass_fwd, _state_pass_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------
def _chunk_operands(q, k, v, g, beta, chunk):
    """What a chunk needs beside the state it starts from, for all chunks at
    once: (w, u0, qg, attn, kd, dec), each [batch * value heads, seq, .]."""
    b, seq, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    n, rep, cdt = seq // chunk, hv // hk, v.dtype

    def chunks(x):  # [b, seq, heads, d] -> [b, heads, n, chunk, d]
        x = jnp.moveaxis(x, 2, 1)
        return x.reshape(b, x.shape[1], n, chunk, *x.shape[3:])

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    gam = jnp.cumsum(chunks(g.astype(F32)), axis=-1)  # [b, hv, n, chunk]
    bet = chunks(beta.astype(F32))
    on_or_under = jnp.tril(jnp.ones((chunk, chunk), bool))
    gap = gam[..., :, None] - gam[..., None, :]
    decay = jnp.where(on_or_under,
                      jnp.exp(jnp.where(on_or_under, gap, 0.0)), 0.0)

    def scores(x, y):  # per key head, shared by the value heads it serves
        s = jnp.einsum("bhnid,bhnjd->bhnij", x, y, preferred_element_type=F32)
        return jnp.repeat(s, rep, axis=1)

    a = jnp.tril(bet[..., :, None] * decay * scores(kc, kc), -1)
    t = unit_lower_inverse(a, cdt)
    kv = jnp.repeat(kc, rep, axis=1).astype(F32)
    qv = jnp.repeat(qc, rep, axis=1).astype(F32)
    egam, last = jnp.exp(gam), gam[..., -1:]

    def times_t(x):
        return jnp.einsum("bhnij,bhnjd->bhnid", t, x.astype(cdt),
                          preferred_element_type=F32).astype(cdt)

    w = times_t(kv * (bet * egam)[..., None])
    u0 = times_t(vc.astype(F32) * bet[..., None])
    attn = (scores(qc, kc) * decay).astype(cdt)
    qg = (qv * egam[..., None]).astype(cdt)
    kd = (kv * jnp.exp(last - gam)[..., None]).astype(cdt)
    dec = jnp.broadcast_to(jnp.exp(last), (b, hv, n, dv))

    def flat(x):
        return x.reshape(b * hv, seq, x.shape[-1])

    return (flat(w), flat(u0), flat(qg), flat(attn), flat(kd),
            dec.reshape(b * hv, n, dv))


def gated_delta_rule(q, k, v, g, beta, *, chunk=64):
    """The gated delta rule over whole sequences, state zero at the start.

    q, k [batch, seq, key heads, d_k] (normalised and scaled by the caller),
    v [batch, seq, value heads, d_v], g (log decay, <= 0) and beta [batch,
    seq, value heads]; key head i serves value heads i * rep .. (i+1) * rep-1.
    Returns o [batch, seq, value heads, d_v] in v's type. ``seq`` must be a
    multiple of ``chunk`` (or shorter than it)."""
    b, seq, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    chunk = min(chunk, seq)
    if seq % chunk or hv % hk or k.shape != q.shape:
        raise ValueError(
            f"gated_delta_rule: seq {seq} is not a multiple of chunk {chunk}, "
            f"or {hv} value heads are not a multiple of {hk} key heads")
    o = _state_pass(*_chunk_operands(q, k, v, g, beta, chunk), chunk)
    return jnp.moveaxis(o.reshape(b, hv, seq, dv), 1, 2)
