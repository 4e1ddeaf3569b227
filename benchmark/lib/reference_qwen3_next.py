"""The sparse hybrid decoder of ``configs/qwen3-next-*`` in plain
``jax.numpy``: forward, loss, gradients and the AdamW update, float32 with
every product at ``Precision.HIGHEST``.

Written from the layer equations of ISSUE 28 / PERF.md §4 (the public
``config.json`` of Qwen3-Next-80B-A3B-Instruct gives every size): RMSNorm
eps 1e-6, "zero-centred" gains 1 + w; block h += mixer(norm1(h)); h +=
experts(norm2(h)); layer i is full attention where (i + 1) % 4 == 0.

  linear attention: the gated delta rule as the TOKEN-BY-TOKEN recurrence
    S <- exp(g_t) S; u = beta_t (v_t - S^T k_t); S <- S + k_t u^T; o_t =
    S^T q_t (a ``lax.scan`` over positions, checkpointed by blocks so that its
    gradient fits), after a causal depthwise conv (kernel 4, no bias) with
    SiLU on q | k | v, q / |q| / sqrt(d_k), k / |k|; then o / rms(o) * w *
    silu(z) per head.
  full attention: the dense masked softmax in row blocks, 16 query heads on
    2 KV heads, zero-centred RMSNorm over each head of q and k, rotary
    positions on the first 64 dims (half-split pairing), output times
    sigmoid(gate).
  experts: softmax over ALL router outputs in float32, the k largest,
    renormalised; a sum over the HELD experts with masks (what the absent
    experts would add is left out, as in the program); the shared expert
    whole, times sigmoid(x w_s).

It imports nothing of ``paddle_tpu`` and takes its weights from the seed
(``lib/weights_qwen3_next.py``). Parameters and Adam moments are STORED in
the configuration's ``param_dtype`` between steps and the gradient comes out
in it, as the program holds them; the arithmetic is float32. A layer's
weights are widened inside its own checkpoint, so that one layer's float32
copy is live at a time.

``operands`` is the control's hook (see ``reference_gpt2``). ``fault`` plants
one of: ``no_decay`` (g = 0), ``capacity_drop`` (the most loaded held
expert's slots beyond an even share dropped), ``no_renorm`` (the k weights
not renormalised). ``pinned`` (per layer, expert ids [T, k]) puts another
side's choice of experts in the place of this side's own ``top_k``, the
weights still this side's probabilities of them: what is left of a gap then
is rounding, not routing (``routed_experts`` gives a side's choice;
``tools/routing.py``).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import weights_qwen3_next as seeded
from .reference_gpt2 import (_adamw, _zeros_like, exact_operands,
                             fp8_operands)

HI = lax.Precision.HIGHEST
EPS = 1e-6
F32 = jnp.float32
STACKED = ("egu_w", "ed_w")  # leaves that hold one slice per held expert

__all__ = ["train", "loss_and_grads", "logits", "exact_operands",
           "fp8_operands", "delta_rule", "linear_attention",
           "full_attention", "experts", "routed_experts"]


def rms_norm(x, gain):
    return x * lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + EPS) * gain


def rotary(x, rotary_dim, theta):
    """[b, s, heads, d]: pair (i, i + rotary_dim / 2) turned by position."""
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / rotary_dim)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary_dim:]], -1)


def swiglu(x, gate_up, down, mm):
    gate, up = jnp.split(mm(x, gate_up), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, down)


# ---------------------------------------------------------------------------
# linear attention
# ---------------------------------------------------------------------------
def delta_rule(q, k, v, g, beta):
    """The recurrence itself. q, k [b, s, heads, d_k], v [b, s, heads, d_v],
    g, beta [b, s, heads]; state zero at the start. Returns o like v."""
    b, s, heads, dk = q.shape
    block = math.gcd(s, 128)

    def token(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[..., None, None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", state, kt,
                                             precision=HI))
        state = state + kt[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt, precision=HI)

    @jax.checkpoint
    def tokens(state, xs):
        return lax.scan(token, state, xs)

    def blocks(a):  # [b, s, ...] -> [s / block, block, b, ...]
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape(s // block, block, *a.shape[1:])

    state = jnp.zeros((b, heads, dk, v.shape[-1]), F32)
    _, o = lax.scan(tokens, state, tuple(map(blocks, (q, k, v, g, beta))))
    return jnp.moveaxis(o.reshape(s, *o.shape[2:]), 0, 1)


def linear_attention(x, w, sizes, operands=exact_operands, fault=None):
    b, s, _ = x.shape
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    n_qk, n_v = hk * dk, hv * dv

    def mm(a, m):
        return jnp.matmul(operands(a), operands(m), precision=HI)

    qkvz, ba = mm(x, w["qkvz_w"]), mm(x, w["ba_w"])
    qkv, z = qkvz[..., :2 * n_qk + n_v], qkvz[..., 2 * n_qk + n_v:]
    taps = w["conv_w"].shape[-1]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, i:i + s] * w["conv_w"][:, i]
                          for i in range(taps)))
    q = qkv[..., :n_qk].reshape(b, s, hk, dk)
    k = qkv[..., n_qk:2 * n_qk].reshape(b, s, hk, dk)
    v = qkv[..., 2 * n_qk:].reshape(b, s, hv, dv)
    q = q * lax.rsqrt(jnp.square(q).sum(-1, keepdims=True) + EPS) / math.sqrt(dk)
    k = k * lax.rsqrt(jnp.square(k).sum(-1, keepdims=True) + EPS)
    # each key head serves hv / hk value heads
    q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(ba[..., hv:] + w["dt_bias"])
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    o = delta_rule(operands(q), operands(k), operands(v), g, beta)
    y = rms_norm(o, w["gnorm"]) * jax.nn.silu(z.reshape(b, s, hv, dv))
    return mm(y.reshape(b, s, n_v), w["out_w"])


# ---------------------------------------------------------------------------
# full attention
# ---------------------------------------------------------------------------
def full_attention(x, w, sizes, operands=exact_operands):
    b, s, _ = x.shape
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = sizes["head_dim"]
    rotary_dim = int(d * sizes["partial_rotary_factor"])

    def mm(a, m):
        return jnp.matmul(operands(a), operands(m), precision=HI)

    q_gate = mm(x, w["q_w"]).reshape(b, s, heads, 2 * d)
    q, gate = q_gate[..., :d], q_gate[..., d:]
    k = mm(x, w["k_w"]).reshape(b, s, kv, d)
    v = mm(x, w["v_w"]).reshape(b, s, kv, d)
    q = rotary(rms_norm(q, 1.0 + w["qnorm"]), rotary_dim, sizes["rope_theta"])
    k = rotary(rms_norm(k, 1.0 + w["knorm"]), rotary_dim, sizes["rope_theta"])
    k, v = (operands(jnp.repeat(a, heads // kv, axis=2)) for a in (k, v))
    rows = math.gcd(s, 512)

    @jax.checkpoint
    def row_block(args):
        qb, r0 = args  # [b, rows, heads, d], the block's first row
        scores = jnp.einsum("bqnd,bknd->bnqk", operands(qb), k,
                            precision=HI) / math.sqrt(d)
        seen = (jnp.arange(s)[None, :]
                <= r0 + jnp.arange(rows)[:, None])
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bnqk,bknd->bqnd", operands(probs), v, precision=HI)

    qb = jnp.moveaxis(q.reshape(b, s // rows, rows, heads, d), 1, 0)
    out = lax.map(row_block, (qb, jnp.arange(0, s, rows)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, heads * d)
    return mm(out * jax.nn.sigmoid(gate.reshape(b, s, heads * d)), w["o_w"])


# ---------------------------------------------------------------------------
# experts
# ---------------------------------------------------------------------------
def router_probabilities(x, router, operands):
    return jax.nn.softmax(
        jnp.matmul(operands(x), operands(router), precision=HI), axis=-1)


def held_weights(x, router, sizes, held, operands, fault, pinned=None):
    """[T, count]: the weight each held expert has for each token (0 where
    it is not among the token's k), routing over ALL of the router."""
    first, count = held
    top_k = sizes["num_experts_per_tok"]
    p = router_probabilities(x, router, operands)
    if pinned is None:
        wt, idx = lax.top_k(p, top_k)
    else:
        wt, idx = jnp.take_along_axis(p, pinned, axis=-1), pinned
    if sizes.get("norm_topk_prob", True) and fault != "no_renorm":
        wt = wt / wt.sum(-1, keepdims=True)
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
            shared=True, pinned=None):
    """[T, h] -> [T, h]: the held experts' part, and the shared expert."""
    if held is None:
        held = (sizes.get("held_first", 0), sizes["num_experts"])
    tokens, h = x.shape

    def mm(a, m):
        return jnp.matmul(operands(a), operands(m), precision=HI)

    we = held_weights(x, w["router"], sizes, held, operands, fault, pinned)
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
        y = y + jax.nn.sigmoid(mm(x, w["sg_w"])) * swiglu(
            x, w["sgu_w"], w["sd_w"], mm)
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
MIXER_LEAVES = ("norm1", "q_w", "k_w", "v_w", "o_w", "qnorm", "knorm",
                "qkvz_w", "ba_w", "conv_w", "a_log", "dt_bias", "gnorm",
                "out_w")


def mixer_part(x, w, sizes, operands, fault, full, pinned=None):
    """One sequence at a time (no mixer looks across sequences), each made
    again in the backward: one sequence's intermediates are live."""
    @jax.checkpoint
    def one(row):
        a = rms_norm(row[None], 1.0 + w["norm1"])
        return (full_attention(a, w, sizes, operands) if full else
                linear_attention(a, w, sizes, operands, fault))[0]

    return x + lax.map(one, x)


def experts_part(x, w, sizes, operands, fault, full, pinned=None):
    b, s, h = x.shape
    m = rms_norm(x, 1.0 + w["norm2"]).reshape(b * s, h)
    return x + experts(m, w, sizes, operands, fault=fault,
                       pinned=pinned).reshape(b, s, h)


def head_part(x, w, y, sizes, operands):
    """Mean next-token cross entropy over the held rows of the vocabulary;
    the head and the loss run in row chunks, recomputed in the backward."""
    h = rms_norm(x, 1.0 + w["norm_f"])
    head = operands(w["head_w"])
    rows = math.gcd(y.size, 2048)

    @jax.checkpoint
    def chunk(args):
        hc, yc = args
        lg = jnp.matmul(operands(hc), head, precision=HI)
        picked = jnp.take_along_axis(lg, yc[:, None], axis=-1)[:, 0]
        return (jax.nn.logsumexp(lg, axis=-1) - picked).sum()

    return lax.map(chunk, (h.reshape(-1, rows, h.shape[-1]),
                           y.reshape(-1, rows))).sum() / y.size


PARTS = {"mixer": mixer_part, "experts": experts_part}


def _wide(w):
    return {k: a.astype(F32) for k, a in w.items()}


@functools.partial(jax.jit, static_argnames=(
    "part", "sizes", "operands", "fault", "full"))
def _forward(part, x, w, sizes, operands, fault, full, pinned=None):
    return PARTS[part](x, _wide(w), dict(sizes), operands, fault, full,
                       pinned)


@functools.partial(jax.jit, static_argnames=(
    "part", "sizes", "operands", "fault", "full"))
def _pull_back(part, x, w, dy, sizes, operands, fault, full, pinned=None):
    _, vjp = jax.vjp(lambda x, w: PARTS[part](
        x, _wide(w), dict(sizes), operands, fault, full, pinned), x, w)
    return vjp(dy)


@functools.partial(jax.jit, static_argnames=("sizes", "operands"))
def _head(x, w, y, sizes, operands):
    loss, (dx, dw) = jax.value_and_grad(
        lambda x, w: head_part(x, _wide(w), y, dict(sizes), operands),
        argnums=(0, 1))(x, w)
    return loss, dx, dw


@jax.jit
def _embed(table, ids):
    return table.astype(F32)[ids]


@jax.jit
def _embed_grad(table, ids, dx):
    return jnp.zeros(table.shape, F32).at[ids].add(dx).astype(table.dtype)


def _static(sizes):
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, (int, float, bool))))


def parts_of(p, sizes, pinned=None):
    """[(part, full attention?, {bare leaf name: the leaf's name in p}, the
    experts pinned for it or None)] in the order the model applies them."""
    out = []
    for i in range(sizes["num_hidden_layers"]):
        tail = f".{i}"
        bare = {k[:-len(tail)]: k for k in p if k.endswith(tail)}
        full = seeded.is_full_attention(sizes, i)
        out.append(("mixer", full,
                    {b: k for b, k in bare.items() if b in MIXER_LEAVES},
                    None))
        out.append(("experts", full,
                    {b: k for b, k in bare.items() if b not in MIXER_LEAVES},
                    None if pinned is None else pinned[i]))
    return out


def hidden(p, ids, sizes, operands=exact_operands, fault=None, keep=None,
           pinned=None):
    """The trunk's output before the final norm; ``keep`` (a list) is given
    each part's input."""
    static = _static(sizes)
    x = _embed(p["embed"], ids)
    for part, full, names, pin in parts_of(p, sizes, pinned):
        if keep is not None:
            keep.append(x)
        x = _forward(part, x, {b: p[k] for b, k in names.items()}, static,
                     operands, fault, full, pin)
    return x


@functools.partial(jax.jit, static_argnames=("top_k", "operands"))
def _choice(x, norm2, router, top_k, operands):
    m = rms_norm(x.astype(F32), 1.0 + norm2.astype(F32))
    p = router_probabilities(m.reshape(-1, m.shape[-1]), router.astype(F32),
                             operands)
    return lax.top_k(p, top_k)[1]


def routed_experts(sizes, seed, ids, param_dtype, operands=exact_operands):
    """Per layer, the expert ids [T, k] that the seeded model's router
    chooses for the token ids [batch, seq] (of all the router's experts, held
    or not), in this side's arithmetic."""
    p, kept = seeded.make(sizes, seed, param_dtype), []
    hidden(p, jnp.asarray(ids), sizes, operands, keep=kept)
    return [_choice(kept[2 * i + 1], p[f"norm2.{i}"], p[f"router.{i}"],
                    sizes["num_experts_per_tok"], operands)
            for i in range(sizes["num_hidden_layers"])]


def logits(p, ids, sizes, operands=exact_operands):
    h = rms_norm(hidden(p, ids, sizes, operands),
                 1.0 + p["norm_f"].astype(F32))
    return jnp.matmul(operands(h), operands(p["head_w"].astype(F32)),
                      precision=HI)


def loss_and_grads(p, x, y, sizes, operands=exact_operands, fault=None,
                   pinned=None):
    """Mean loss and its gradient per leaf, in each leaf's own type."""
    static, kept = _static(sizes), []
    out = hidden(p, x, sizes, operands, fault, keep=kept, pinned=pinned)
    loss, dx, grads = _head(
        out, {"norm_f": p["norm_f"], "head_w": p["head_w"]}, y, static,
        operands)
    del out
    for part, full, names, pin in reversed(parts_of(p, sizes, pinned)):
        dx, dw = _pull_back(part, kept.pop(),
                            {b: p[k] for b, k in names.items()}, dx, static,
                            operands, fault, full, pin)
        grads.update({names[b]: g for b, g in dw.items()})
    grads["embed"] = _embed_grad(p["embed"], x, dx)
    return loss, grads


@jax.jit
def _norms_and_projections(tree, signs, minus=None):
    """Per leaf of ``tree`` (less ``minus``): its norm, and its entries
    summed under the fixed signs, in float32."""
    f32 = {k: a.astype(F32) - (0.0 if minus is None else minus[k].astype(F32))
           for k, a in tree.items()}
    return ({k: jnp.sqrt(jnp.square(a).sum()) for k, a in f32.items()},
            {k: (a * signs[k].astype(F32)).sum() for k, a in f32.items()})


@jax.jit
def _norms_per_expert(tree):
    return {k: jnp.sqrt(jnp.square(a.astype(F32)).sum(
        tuple(range(1, a.ndim)))) for k, a in tree.items()
        if k.startswith(STACKED)}


def _floats(tree):
    return {k: float(np.asarray(a, np.float64)) for k, a in tree.items()}


def train(sizes, seed, batches, hyper, param_dtype, steps=3,
          operands=exact_operands, rows=None, frozen=False, fault=None,
          pinned=None):
    """Follow the first ``steps`` steps from the seed. ``batches`` are the
    host arrays [batch, seq + 1] the program was fed. ``rows`` (a slice)
    leaves the other rows of each batch out, the mean taken over the rest;
    ``frozen`` returns the state unchanged; ``fault``: see the module;
    ``pinned``: the experts of the FIRST step, see the module.

    Returns losses per step and, per leaf, the norm and the projection
    (``weights_qwen3_next.projection``) of the first gradient and of the
    parameters' change after the last step; and the first gradient's norm
    per held expert of each stacked leaf (``expert_grad_norms``)."""
    p = seeded.make(sizes, seed, param_dtype)
    m, v = _zeros_like(p), _zeros_like(p)
    losses, grad_norms, grad_sums, expert_norms = [], None, None, None
    for t in range(steps):
        ids = np.asarray(batches[t])
        if rows is not None:
            ids = ids[rows]
        x, y = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])
        loss, g = loss_and_grads(p, x, y, sizes, operands, fault,
                                 pinned if t == 0 else None)
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
