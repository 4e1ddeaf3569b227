"""The state-space / attention hybrid decoder of ``configs/granite-4.0-h-*``
in plain ``jax.numpy``: forward, loss, gradients and the AdamW update,
float32 with every product at ``Precision.HIGHEST``.

Written from the layer equations of ISSUE 32 / PERF.md §4 (the public
``config.json`` of granite-4.0-h-small gives every size). With h the
residual stream, r = ``residual_multiplier`` and plain RMSNorm gains (eps
1e-5):  h = ``embedding_multiplier`` E[ids];  h += r mixer_i(rmsnorm(h));
h += r (experts(u) + shared(u)), u = rmsnorm(h);  logits = rmsnorm(h) E^T /
``logits_scaling`` with the embedding tied; the loss the mean next-token
cross entropy.

  state space: (x B C | z) = u W_xbcz, dt = u W_dt; (x, B, C) <- silu(causal
    depthwise conv, kernel 4, + bias); dt <- softplus(dt + dt_bias), a_t =
    exp(-exp(A_log) dt_t); the TOKEN-BY-TOKEN recurrence S_t = a_t S_(t-1) +
    dt_t x_t B_t^T, y_t = S_t C_t + D x_t per head (a ``lax.scan`` over
    positions, checkpointed by blocks so that its gradient fits), B_t and C_t
    shared by the heads of a group; then rmsnorm(y * silu(z)) with ONE
    statistic over all channels held (or the ``statistic`` handed in: the
    whole layer's, where a share is checked against it); W_out.
  attention: the dense masked softmax of q k^T * ``attention_multiplier`` in
    row blocks, the KV heads shared by groups of query heads; no positions.
  experts: router logits over ALL of the router's outputs in float32, the k
    largest, softmax over those k; a sum over the HELD experts with masks
    (what the absent experts would add is left out, as in the program); the
    shared expert whole and ungated.

Every count in ``sizes`` is what this chip holds: a share of the heads gives
a share's part of the out-projection's sum. It imports nothing of
``paddle_tpu`` and takes its weights from the seed
(``lib/weights_granite_hybrid.py``). Parameters and Adam moments are STORED
in the configuration's ``param_dtype`` between steps and the gradient comes
out in it, as the program holds them; the arithmetic is float32.

``operands`` is the control's hook (see ``reference_gpt2``). ``fault`` plants
one of ``FAULTS``: ``no_decay`` (a_t = 1), ``no_skip`` (D x_t left out),
``norm_before_gate`` (rmsnorm(y) * silu(z)), ``residual_one``
(``residual_multiplier`` taken as 1), ``attention_scale`` (head_dim ** -0.5),
``capacity_drop`` (the most loaded held expert's slots beyond an even share
dropped).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import weights_granite_hybrid as seeded
from .reference_gpt2 import (_adamw, _zeros_like, exact_operands,
                             fp8_operands)
# what does not depend on the model: leaves that hold one slice per held
# expert, SwiGLU, a part's weights widened, the norms and projections read
from .reference_qwen3_next import (STACKED, _floats, _norms_and_projections,
                                   _norms_per_expert, _static, _wide, swiglu)

HI = lax.Precision.HIGHEST
F32 = jnp.float32
FAULTS = ("no_decay", "no_skip", "norm_before_gate", "residual_one",
          "attention_scale", "capacity_drop")

__all__ = ["train", "loss_and_grads", "logits", "exact_operands",
           "fp8_operands", "ssd_recurrence", "mamba_gated", "mamba_mixer",
           "attention", "experts", "FAULTS"]


def rms_norm(x, gain, eps, statistic=None):
    if statistic is None:
        statistic = jnp.square(x).mean(-1, keepdims=True)
    return x * lax.rsqrt(statistic + eps) * gain


# ---------------------------------------------------------------------------
# state space
# ---------------------------------------------------------------------------
def ssd_recurrence(x, dt, a, bm, cm):
    """The recurrence itself. x [b, s, heads, P], dt and the decay a [b, s,
    heads], B and C [b, s, groups, N]; state zero at the start. Returns
    S_t C_t like x (no skip)."""
    b, s, heads, p = x.shape
    rep = heads // bm.shape[2]
    block = math.gcd(s, 128)

    def token(state, inputs):
        xt, dtt, at, bt, ct = inputs
        bt, ct = (jnp.repeat(v, rep, axis=1) for v in (bt, ct))
        state = (state * at[..., None, None]
                 + (dtt[..., None] * xt)[..., :, None] * bt[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct, precision=HI)

    @jax.checkpoint
    def tokens(state, xs):
        return lax.scan(token, state, xs)

    def blocks(v):  # [b, s, ...] -> [s / block, block, b, ...]
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape(s // block, block, *v.shape[1:])

    state = jnp.zeros((b, heads, p, bm.shape[-1]), F32)
    _, y = lax.scan(tokens, state, tuple(map(blocks, (x, dt, a, bm, cm))))
    return jnp.moveaxis(y.reshape(s, *y.shape[2:]), 0, 1)


def mamba_gated(u, w, sizes, operands=exact_operands, fault=None):
    """(y * silu(z) [b, s, heads * P] before its norm, or with
    ``norm_before_gate`` (y, silu(z)))."""
    b, s, _ = u.shape
    heads, p = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    groups, n = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    inner, bc, channels = seeded.mamba_widths(sizes)

    def mm(a, m):
        return jnp.matmul(operands(a), operands(m), precision=HI)

    xbcz, dt = mm(u, w["xbcz_w"]), mm(u, w["dt_w"])
    xbc, z = xbcz[..., :channels], xbcz[..., channels:]
    taps = w["conv_w"].shape[-1]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, i:i + s] * w["conv_w"][:, i]
                          for i in range(taps)) + w["conv_b"])
    x = xbc[..., :inner].reshape(b, s, heads, p)
    bm = xbc[..., inner:inner + bc].reshape(b, s, groups, n)
    cm = xbc[..., inner + bc:].reshape(b, s, groups, n)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = jnp.exp(-jnp.exp(w["a_log"]) * dt)
    if fault == "no_decay":
        a = jnp.ones_like(a)
    y = ssd_recurrence(operands(x), dt, a, operands(bm), operands(cm))
    if fault != "no_skip":
        y = y + w["d_skip"][:, None] * x
    y, gate = y.reshape(b, s, inner), jax.nn.silu(z)
    return (y, gate) if fault == "norm_before_gate" else y * gate


def mamba_mixer(u, w, sizes, operands=exact_operands, fault=None,
                statistic=None):
    """``statistic`` [b, s, 1]: the mean of squares to norm by, in place of
    the held channels' own (the whole layer's, for a share)."""
    eps = sizes["rms_norm_eps"]
    gated = mamba_gated(u, w, sizes, operands, fault)
    if fault == "norm_before_gate":
        y = rms_norm(gated[0], w["gnorm"], eps) * gated[1]
    else:
        y = rms_norm(gated, w["gnorm"], eps, statistic)
    return jnp.matmul(operands(y), operands(w["out_w"]), precision=HI)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def attention(u, w, sizes, operands=exact_operands, fault=None):
    b, s, _ = u.shape
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = sizes["head_dim"]
    scale = (d ** -0.5 if fault == "attention_scale"
             else sizes["attention_multiplier"])

    def mm(a, m):
        return jnp.matmul(operands(a), operands(m), precision=HI)

    q = mm(u, w["q_w"]).reshape(b, s, heads, d)
    k = mm(u, w["k_w"]).reshape(b, s, kv, d)
    v = mm(u, w["v_w"]).reshape(b, s, kv, d)
    k, v = (operands(jnp.repeat(a, heads // kv, axis=2)) for a in (k, v))
    rows = math.gcd(s, 512)

    @jax.checkpoint
    def row_block(args):
        qb, r0 = args  # [b, rows, heads, d], the block's first row
        scores = jnp.einsum("bqnd,bknd->bnqk", operands(qb), k,
                            precision=HI) * scale
        seen = jnp.arange(s)[None, :] <= r0 + jnp.arange(rows)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bnqk,bknd->bqnd", operands(probs), v, precision=HI)

    qb = jnp.moveaxis(q.reshape(b, s // rows, rows, heads, d), 1, 0)
    out = lax.map(row_block, (qb, jnp.arange(0, s, rows)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, heads * d)
    return mm(out, w["o_w"])


# ---------------------------------------------------------------------------
# experts
# ---------------------------------------------------------------------------
def held_weights(x, router, sizes, held, operands, fault):
    """[T, count]: the weight each held expert has for each token (0 where
    it is not among the token's k), routing over ALL of the router."""
    first, count = held
    top_k = sizes["num_experts_per_tok"]
    logits = jnp.matmul(operands(x), operands(router), precision=HI)
    top, idx = lax.top_k(logits, top_k)
    wt = jax.nn.softmax(top, axis=-1)
    onehot = jax.nn.one_hot(idx - first, count, dtype=F32)  # [T, k, count]
    if fault == "capacity_drop":
        # the most loaded held expert keeps its first T k / E slots only
        taken = onehot.sum(1)  # [T, count]
        worst = jnp.argmax(taken.sum(0))
        before = jnp.cumsum(taken[:, worst]) - taken[:, worst]
        cap = x.shape[0] * top_k // router.shape[1]
        keep = jnp.where(jnp.arange(count) == worst,
                         (before < cap)[:, None], True)
        onehot = onehot * keep[:, None, :]
    return (onehot * wt[..., None]).sum(1)


def experts(x, w, sizes, operands=exact_operands, held=None, fault=None,
            shared=True):
    """[T, h] -> [T, h]: the held experts' part, and the shared expert."""
    if held is None:
        held = (sizes.get("held_first", 0), sizes["num_local_experts"])
    tokens, h = x.shape

    def mm(a, m):
        return jnp.matmul(operands(a), operands(m), precision=HI)

    we = held_weights(x, w["router"], sizes, held, operands, fault)
    egu, ed = operands(w["egu_w"]), operands(w["ed_w"])
    rows = math.gcd(tokens, 1024)

    @jax.checkpoint
    def token_block(args):
        xb, wb = args  # [rows, h], [rows, count]
        gate, up = jnp.split(jnp.einsum("th,ehf->etf", operands(xb), egu,
                                        precision=HI), 2, axis=-1)
        act = jax.nn.silu(gate) * up * wb.T[:, :, None]
        return jnp.einsum("etf,efh->th", operands(act), ed, precision=HI)

    y = lax.map(token_block, (x.reshape(-1, rows, h),
                              we.reshape(-1, rows, we.shape[-1])))
    y = y.reshape(tokens, h)
    if shared:
        y = y + swiglu(x, w["sgu_w"], w["sd_w"], mm)
    return y


# ---------------------------------------------------------------------------
# the model, part by part
# ---------------------------------------------------------------------------
# A step is followed one part at a time (a layer's mixer, a layer's experts,
# the head with the loss), each a program of its own, forward and then pulled
# back in reverse from the inputs kept: what is live is the state, the parts'
# inputs, the gradients made so far and ONE part's intermediates, so the
# reference fits on the chip beside nothing else. A part's weights are widened
# to float32 inside its program and its gradient comes out in their own type.
MIXER_LEAVES = ("norm1", "q_w", "k_w", "v_w", "o_w", "xbcz_w", "dt_w",
                "conv_w", "conv_b", "a_log", "dt_bias", "d_skip", "gnorm",
                "out_w")


def _residual(sizes, fault):
    return 1.0 if fault == "residual_one" else sizes["residual_multiplier"]


def mixer_part(x, w, sizes, operands, fault, is_attention):
    """One sequence at a time (no mixer looks across sequences), each made
    again in the backward: one sequence's intermediates are live."""
    @jax.checkpoint
    def one(row):
        u = rms_norm(row[None], w["norm1"], sizes["rms_norm_eps"])
        return (attention if is_attention else mamba_mixer)(
            u, w, sizes, operands, fault)[0]

    return x + _residual(sizes, fault) * lax.map(one, x)


def experts_part(x, w, sizes, operands, fault, is_attention):
    b, s, h = x.shape
    u = rms_norm(x, w["norm2"], sizes["rms_norm_eps"]).reshape(b * s, h)
    return x + _residual(sizes, fault) * experts(
        u, w, sizes, operands, fault=fault).reshape(b, s, h)


def head_part(x, w, y, sizes, operands):
    """Mean next-token cross entropy over the held rows of the vocabulary;
    the tied head and the loss run in row chunks, recomputed in the
    backward."""
    h = rms_norm(x, w["norm_f"], sizes["rms_norm_eps"])
    head = operands(w["embed"]).T
    rows = math.gcd(y.size, 2048)

    @jax.checkpoint
    def chunk(args):
        hc, yc = args
        lg = jnp.matmul(operands(hc), head,
                        precision=HI) / sizes["logits_scaling"]
        picked = jnp.take_along_axis(lg, yc[:, None], axis=-1)[:, 0]
        return (jax.nn.logsumexp(lg, axis=-1) - picked).sum()

    return lax.map(chunk, (h.reshape(-1, rows, h.shape[-1]),
                           y.reshape(-1, rows))).sum() / y.size


PARTS = {"mixer": mixer_part, "experts": experts_part}


@functools.partial(jax.jit, static_argnames=(
    "part", "sizes", "operands", "fault", "is_attention"))
def _forward(part, x, w, sizes, operands, fault, is_attention):
    return PARTS[part](x, _wide(w), dict(sizes), operands, fault,
                       is_attention)


@functools.partial(jax.jit, static_argnames=(
    "part", "sizes", "operands", "fault", "is_attention"))
def _pull_back(part, x, w, dy, sizes, operands, fault, is_attention):
    _, vjp = jax.vjp(lambda x, w: PARTS[part](
        x, _wide(w), dict(sizes), operands, fault, is_attention), x, w)
    return vjp(dy)


@functools.partial(jax.jit, static_argnames=("sizes", "operands"))
def _head(x, norm_f, embed, y, sizes, operands):
    """(loss, dx, d norm_f in its own type, the head's share of d embed in
    float32: the lookup's share is added before it is rounded)."""
    loss, (dx, dn, de) = jax.value_and_grad(
        lambda x, n, e: head_part(x, {"norm_f": n.astype(F32), "embed": e},
                                  y, dict(sizes), operands),
        argnums=(0, 1, 2))(x, norm_f, embed.astype(F32))
    return loss, dx, dn, de


@functools.partial(jax.jit, static_argnames=("multiplier",))
def _embed(table, ids, multiplier):
    return table.astype(F32)[ids] * multiplier


@functools.partial(jax.jit, static_argnames=("multiplier",))
def _embed_grad(table, ids, dx, from_head, multiplier):
    return from_head.at[ids].add(dx * multiplier).astype(table.dtype)


def parts_of(p, sizes):
    """[(part, attention layer?, {bare leaf name: the leaf's name in p})] in
    the order the model applies them."""
    out = []
    for i in range(sizes["num_hidden_layers"]):
        tail = f".{i}"
        bare = {k[:-len(tail)]: k for k in p if k.endswith(tail)}
        kind = seeded.is_attention(sizes, i)
        out.append(("mixer", kind,
                    {b: k for b, k in bare.items() if b in MIXER_LEAVES}))
        out.append(("experts", kind,
                    {b: k for b, k in bare.items() if b not in MIXER_LEAVES}))
    return out


def hidden(p, ids, sizes, operands=exact_operands, fault=None, keep=None):
    """The trunk's output before the final norm; ``keep`` (a list) is given
    each part's input."""
    static = _static(sizes)
    x = _embed(p["embed"], ids, sizes["embedding_multiplier"])
    for part, kind, names in parts_of(p, sizes):
        if keep is not None:
            keep.append(x)
        x = _forward(part, x, {b: p[k] for b, k in names.items()}, static,
                     operands, fault, kind)
    return x


def logits(p, ids, sizes, operands=exact_operands):
    h = rms_norm(hidden(p, ids, sizes, operands), p["norm_f"].astype(F32),
                 sizes["rms_norm_eps"])
    return jnp.matmul(operands(h), operands(p["embed"].astype(F32)).T,
                      precision=HI) / sizes["logits_scaling"]


def loss_and_grads(p, x, y, sizes, operands=exact_operands, fault=None):
    """Mean loss and its gradient per leaf, in each leaf's own type."""
    static, kept = _static(sizes), []
    out = hidden(p, x, sizes, operands, fault, keep=kept)
    loss, dx, dnorm, from_head = _head(out, p["norm_f"], p["embed"], y,
                                       static, operands)
    grads = {"norm_f": dnorm}
    del out
    for part, kind, names in reversed(parts_of(p, sizes)):
        dx, dw = _pull_back(part, kept.pop(),
                            {b: p[k] for b, k in names.items()}, dx, static,
                            operands, fault, kind)
        grads.update({names[b]: g for b, g in dw.items()})
    grads["embed"] = _embed_grad(p["embed"], x, dx, from_head,
                                 sizes["embedding_multiplier"])
    return loss, grads


def train(sizes, seed, batches, hyper, param_dtype, steps=3,
          operands=exact_operands, tokens=None, frozen=False, fault=None):
    """Follow the first ``steps`` steps from the seed. ``batches`` are the
    host arrays [batch, seq + 1] the program was fed. ``tokens`` (a count)
    leaves all but the first ``tokens`` positions of each row out, the mean
    taken over the rest; ``frozen`` returns the state unchanged; ``fault``:
    see the module.

    Returns losses per step and, per leaf, the norm and the projection
    (``weights_granite_hybrid.projection``) of the first gradient and of the
    parameters' change after the last step; and the first gradient's norm
    per held expert of each stacked leaf (``expert_grad_norms``)."""
    p = seeded.make(sizes, seed, param_dtype)
    m, v = _zeros_like(p), _zeros_like(p)
    losses, grad_norms, grad_sums, expert_norms = [], None, None, None
    for t in range(steps):
        ids = np.asarray(batches[t])
        if tokens is not None:
            ids = ids[:, :tokens + 1]
        x, y = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])
        loss, g = loss_and_grads(p, x, y, sizes, operands, fault)
        losses.append(float(loss))
        if t == 0:
            grad_norms, grad_sums = map(_floats, _norms_and_projections(
                g, seeded.projection(sizes)))
            expert_norms = {
                f"{k}/{e}": float(x) for k, a in _norms_per_expert(g).items()
                for e, x in enumerate(np.asarray(a, np.float64))}
        if not frozen:
            p, m, v = _adamw(p, g, m, v, float(t + 1), hyper["lr"],
                             hyper["b1"], hyper["b2"], hyper["eps"],
                             hyper["wd"])
        del g
    del m, v
    delta_norms, delta_sums = map(_floats, _norms_and_projections(
        p, seeded.projection(sizes), seeded.make(sizes, seed, param_dtype)))
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_sums": grad_sums, "expert_grad_norms": expert_norms,
            "delta_norms": delta_norms, "delta_sums": delta_sums}
