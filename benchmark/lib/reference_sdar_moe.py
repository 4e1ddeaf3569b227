"""The block-diffusion sparse decoder of ``configs/sdar-*`` in plain
``jax.numpy``: forward, loss, gradients and the AdamW update, float32 with
every product at ``Precision.HIGHEST``.

Written from the layer equations of ISSUE 34 / PERF.md §4 (the public
``config.json`` of SDAR-30B-A3B-Chat gives every size). With h the residual
stream and plain RMSNorm gains (eps 1e-6):  h += W_o attention(rmsnorm(h));
h += experts(rmsnorm(h));  logits = rmsnorm(h) W_head, the head untied.

  attention: q = m W_q (32 x 128), k = m W_k, v = m W_v (4 x 128), no bias;
    RMSNorm with a gain over each head's 128 dims of q and of k; rotary
    positions on all 128 dims (half-split pairing, theta 1e6), the position
    of stream index i being its index inside its own half; the dense masked
    softmax of q k^T / sqrt(128) in row blocks, float32, query head i on KV
    head i // 8.
  experts: softmax over ALL router outputs in float32, the 8 largest,
    renormalised; a sum over the HELD experts with masks (what the absent
    experts would add is left out, as in the program); no shared expert.
    (``reference_qwen3_next.experts`` without its shared expert: the same
    equations.)

Block diffusion (Arriola et al., ICLR 2025, the masked objective): the model
runs once over a stream of 2 L positions, the clean tokens x0 and then the
noised ones xt (x0 with the masked tokens replaced by the mask id, the held
vocabulary's last row). With b(i) the block (of ``block_length``) of a
position inside its half, M[i, j] = 1 iff

    i noised, j noised, b(j) == b(i)      (own block, both directions)
    or  i noised, j clean,  b(j) <  b(i)  (the clean prefix)
    or  i clean,  j clean,  b(j) <= b(i)  (block-causal)

built here as a dense boolean, a row block at a time. The loss is the sum
over masked positions of (1 / t of the position's block) x the cross entropy
of the noised position's logits against x0, over batch x L; nothing is
shifted. Departure from the issue's text: none; the order of the halves
(clean first) is the program's choice and the configuration's ``assumed``.

It imports nothing of ``paddle_tpu`` and takes its weights from the seed
(``lib/weights_sdar_moe.py``). Parameters and Adam moments are STORED in the
configuration's ``param_dtype`` between steps and the gradient comes out in
it, as the program holds them; the arithmetic is float32.

``operands`` is the control's hook (see ``reference_gpt2``). ``fault`` plants
one of ``FAULTS``: ``causal_mask`` (the causal triangle over the stream in
the block mask's place), ``no_rate_weight`` (the weight 1 / t left out: 1 at
every masked position), ``stream_positions`` (rotary positions 0 .. 2 L - 1
along the stream, so that one half's run L .. 2 L - 1), ``capacity_drop``
(the most loaded held expert's slots beyond an even share dropped),
``no_renorm`` (the k weights not renormalised).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import traffic_block_diffusion as traffic
from . import weights_sdar_moe as seeded
from .reference_gpt2 import (_adamw, _zeros_like, exact_operands,
                             fp8_operands)
# what does not depend on the model: the routed experts' equations, leaves
# that hold one slice per held expert, a part's weights widened, the norms
# and projections read
from .reference_qwen3_next import (STACKED, _floats, _norms_and_projections,
                                   _norms_per_expert, _static, _wide, experts)

HI = lax.Precision.HIGHEST
F32 = jnp.float32
FAULTS = ("causal_mask", "no_rate_weight", "stream_positions",
          "capacity_drop", "no_renorm")

__all__ = ["train", "loss_and_grads", "logits", "allowed", "attention",
           "experts", "exact_operands", "fp8_operands", "FAULTS", "STACKED"]


def rms_norm(x, gain, eps):
    return x * lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * gain


def rotary(x, positions, theta):
    """[b, s, heads, d] turned on every dim: pair (i, i + d / 2)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def allowed(rows, cols, half, block, fault=None):
    """M[i, j] for stream indices ``rows`` [R] and ``cols`` [C] of a stream
    of ``half`` clean and then ``half`` noised positions: the three lines of
    the module's docstring."""
    i, j = rows[:, None], cols[None, :]
    if fault == "causal_mask":
        return j <= i
    ni, nj = i >= half, j >= half
    bi, bj = (i % half) // block, (j % half) // block
    return ((ni & nj & (bj == bi)) | (ni & ~nj & (bj < bi))
            | (~ni & ~nj & (bj <= bi)))


def attention(x, w, sizes, block, operands=exact_operands, fault=None):
    """x [b, 2 L, h], the stream; returns W_o attention."""
    b, s, _ = x.shape
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, eps = sizes["head_dim"], sizes["rms_norm_eps"]

    def mm(a, m):
        return jnp.matmul(operands(a), operands(m), precision=HI)

    positions = jnp.arange(s)
    if fault != "stream_positions":
        positions = positions % (s // 2)
    q = rms_norm(mm(x, w["q_w"]).reshape(b, s, heads, d), w["qnorm"], eps)
    k = rms_norm(mm(x, w["k_w"]).reshape(b, s, kv, d), w["knorm"], eps)
    v = mm(x, w["v_w"]).reshape(b, s, kv, d)
    q = rotary(q, positions, sizes["rope_theta"])
    k = rotary(k, positions, sizes["rope_theta"])
    k, v = (operands(jnp.repeat(a, heads // kv, axis=2)) for a in (k, v))
    rows = math.gcd(s, 256)

    @jax.checkpoint
    def row_block(args):
        qb, r0 = args  # [b, rows, heads, d], the block's first stream index
        scores = jnp.einsum("bqnd,bknd->bnqk", operands(qb), k,
                            precision=HI) / math.sqrt(d)
        seen = allowed(r0 + jnp.arange(rows), jnp.arange(s), s // 2, block,
                       fault)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bnqk,bknd->bqnd", operands(probs), v, precision=HI)

    qb = jnp.moveaxis(q.reshape(b, s // rows, rows, heads, d), 1, 0)
    out = lax.map(row_block, (qb, jnp.arange(0, s, rows)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, heads * d)
    return mm(out, w["o_w"])


# ---------------------------------------------------------------------------
# the model, part by part
# ---------------------------------------------------------------------------
# A step is followed one part at a time (a layer's attention, a layer's
# experts, the head with the loss), each a program of its own, forward and
# then pulled back in reverse from the inputs kept: what is live is the
# state, the parts' inputs, the gradients made so far and ONE part's
# intermediates, so the reference fits on the chip beside nothing else. A
# part's weights are widened to float32 inside its program and its gradient
# comes out in their own type.
MIXER_LEAVES = ("norm1", "q_w", "k_w", "v_w", "o_w", "qnorm", "knorm")


def mixer_part(x, w, sizes, block, operands, fault):
    """One sequence's stream at a time (attention does not look across
    sequences), each made again in the backward."""
    @jax.checkpoint
    def one(row):
        m = rms_norm(row[None], w["norm1"], sizes["rms_norm_eps"])
        return attention(m, w, sizes, block, operands, fault)[0]

    return x + lax.map(one, x)


def experts_part(x, w, sizes, block, operands, fault):
    b, s, h = x.shape
    m = rms_norm(x, w["norm2"], sizes["rms_norm_eps"]).reshape(b * s, h)
    return x + experts(m, w, sizes, operands, fault=fault,
                       shared=False).reshape(b, s, h)


def head_part(x, w, labels, weights, sizes, operands):
    """x [b, L, h], the noised half: sum of weight x cross entropy over the
    held rows of the vocabulary, over batch x L; the head and the loss run in
    row chunks, recomputed in the backward."""
    h = rms_norm(x, w["norm_f"], sizes["rms_norm_eps"])
    head = operands(w["head_w"])
    rows = math.gcd(labels.size, 2048)

    @jax.checkpoint
    def chunk(args):
        hc, yc, wc = args
        lg = jnp.matmul(operands(hc), head, precision=HI)
        picked = jnp.take_along_axis(lg, yc[:, None], axis=-1)[:, 0]
        return (wc * (jax.nn.logsumexp(lg, axis=-1) - picked)).sum()

    return lax.map(chunk, (h.reshape(-1, rows, h.shape[-1]),
                           labels.reshape(-1, rows),
                           weights.reshape(-1, rows))).sum() / labels.size


PARTS = {"mixer": mixer_part, "experts": experts_part}
_STATIC = ("part", "sizes", "block", "operands", "fault")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _forward(part, x, w, sizes, block, operands, fault):
    return PARTS[part](x, _wide(w), dict(sizes), block, operands, fault)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _pull_back(part, x, w, dy, sizes, block, operands, fault):
    _, vjp = jax.vjp(lambda x, w: PARTS[part](
        x, _wide(w), dict(sizes), block, operands, fault), x, w)
    return vjp(dy)


@functools.partial(jax.jit, static_argnames=("sizes", "operands"))
def _head(x, w, labels, weights, sizes, operands):
    """(loss, d of the stream's activations, the head's gradients): the
    clean half's activations meet no head, so their cotangent starts at 0."""
    half = x.shape[1] // 2
    loss, (dx, dw) = jax.value_and_grad(
        lambda x, w: head_part(x[:, half:], _wide(w), labels, weights,
                               dict(sizes), operands),
        argnums=(0, 1))(x, w)
    return loss, dx, dw


@jax.jit
def _embed(table, ids):
    return table.astype(F32)[ids]


@jax.jit
def _embed_grad(table, ids, dx):
    return jnp.zeros(table.shape, F32).at[ids].add(dx).astype(table.dtype)


def stream_of(batch, mask_id):
    """[b, 2 L] ids: x0, then xt."""
    return jnp.concatenate(
        [batch.ids, np.where(batch.masked, np.int32(mask_id), batch.ids)],
        axis=1)


def parts_of(p, sizes):
    """[(part, {bare leaf name: the leaf's name in p})] in the order the
    model applies them."""
    out = []
    for i in range(sizes["num_hidden_layers"]):
        tail = f".{i}"
        bare = {k[:-len(tail)]: k for k in p if k.endswith(tail)}
        out.append(("mixer",
                    {b: k for b, k in bare.items() if b in MIXER_LEAVES}))
        out.append(("experts",
                    {b: k for b, k in bare.items() if b not in MIXER_LEAVES}))
    return out


def hidden(p, stream, sizes, block, operands=exact_operands, fault=None,
           keep=None):
    """The trunk's output over the stream before the final norm; ``keep`` (a
    list) is given each part's input."""
    static = _static(sizes)
    x = _embed(p["embed"], stream)
    for part, names in parts_of(p, sizes):
        if keep is not None:
            keep.append(x)
        x = _forward(part, x, {b: p[k] for b, k in names.items()}, static,
                     block, operands, fault)
    return x


def logits(p, batch, sizes, block, operands=exact_operands):
    """[b, L, vocabulary held] float32: the noised half's."""
    x = hidden(p, stream_of(batch, sizes["vocab_size"] - 1), sizes, block,
               operands)
    h = rms_norm(x[:, x.shape[1] // 2:], p["norm_f"].astype(F32),
                 sizes["rms_norm_eps"])
    return jnp.matmul(operands(h), operands(p["head_w"].astype(F32)),
                      precision=HI)


def loss_and_grads(p, batch, sizes, block, operands=exact_operands,
                   fault=None):
    """The loss and its gradient per leaf, in each leaf's own type."""
    static, kept = _static(sizes), []
    stream = stream_of(batch, sizes["vocab_size"] - 1)
    weights = (batch.masked.astype(np.float32) if fault == "no_rate_weight"
               else traffic.weights(batch, block))
    out = hidden(p, stream, sizes, block, operands, fault, keep=kept)
    loss, dx, dw = _head(
        out, {"norm_f": p["norm_f"], "head_w": p["head_w"]},
        jnp.asarray(batch.ids), jnp.asarray(weights), static, operands)
    grads = dict(dw)
    del out
    for part, names in reversed(parts_of(p, sizes)):
        dx, dw = _pull_back(part, kept.pop(),
                            {b: p[k] for b, k in names.items()}, dx, static,
                            block, operands, fault)
        grads.update({names[b]: g for b, g in dw.items()})
    grads["embed"] = _embed_grad(p["embed"], stream, dx)
    return loss, grads


def train(sizes, seed, batches, hyper, param_dtype, block, steps=3,
          operands=exact_operands, tokens=None, frozen=False, fault=None):
    """Follow the first ``steps`` steps from the seed. ``batches`` are the
    host batches (``traffic_block_diffusion.Batch``) the program was fed,
    ``block`` the block length. ``tokens`` (a count) leaves all but the
    first ``tokens`` tokens of each row out, the mean taken over the rest;
    ``frozen`` returns the state unchanged; ``fault``: see the module.

    Returns losses per step and, per leaf, the norm and the projection
    (``weights_sdar_moe.projection``) of the first gradient and of the
    parameters' change after the last step; and the first gradient's norm
    per held expert of each stacked leaf (``expert_grad_norms``)."""
    p = seeded.make(sizes, seed, param_dtype)
    m, v = _zeros_like(p), _zeros_like(p)
    losses, grad_norms, grad_sums, expert_norms = [], None, None, None
    for t in range(steps):
        batch = batches[t]
        if tokens is not None:
            batch = traffic.first_tokens(batch, tokens, block)
        loss, g = loss_and_grads(p, batch, sizes, block, operands, fault)
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
