"""The sliding-window sparse decoder of ``configs/mellum2-*`` in plain
``jax.numpy``: forward, loss, gradients and the AdamW update, float32 with
every product at ``Precision.HIGHEST``.

Written from the layer equations of PERF.md §4 (the public ``config.json``
of Mellum2-12B-A2.5B-Instruct gives every size). With h the
residual stream and plain RMSNorm gains (eps 1e-6):  h += W_o
attention(rmsnorm(h));  h += experts(rmsnorm(h));  logits = rmsnorm(h)
W_head, the head untied; the loss the mean next-token cross entropy over the
held rows of the vocabulary.

  attention: q = m W_q (32 x 128), k = m W_k, v = m W_v (4 x 128), no bias,
    no per-head norm; rotary on all 128 dims (half-split pairing); the dense
    masked softmax of q k^T / sqrt(128) in row blocks, float32, query head i
    on KV head i // 8. A layer's kind is ``layer_types[i]``:
      sliding_attention: query i sees key j iff i - W < j <= i (W =
        ``sliding_window``), the band built as a dense boolean a row block
        at a time; the default table theta ** (-2 i / 128).
      full_attention: causal; YaRN (Peng et al. 2023) in its published
        form: inv_extra[i] = theta ** (-2 i / 128), inv_inter = inv_extra /
        factor, low = floor(128 ln(L / (beta_fast 2 pi)) / (2 ln theta)),
        high = ceil(128 ln(L / (beta_slow 2 pi)) / (2 ln theta)) with L the
        original length, ramp = clip((i - low) / (high - low), 0, 1),
        inv_freq = inv_inter ramp + inv_extra (1 - ramp); cos and sin times
        the attention factor.
  experts: softmax over ALL router outputs in float32, the 8 largest,
    renormalised; a sum over the HELD experts with masks (what the absent
    experts would add is left out, as in the program); no shared expert
    (``reference_qwen3_next.experts`` without its shared expert).

It imports nothing of ``paddle_tpu`` and takes its weights from the seed
(``lib/weights_mellum2.py``). Parameters and Adam moments are STORED in the
configuration's ``param_dtype`` between steps and the gradient comes out in
it, as the program holds them; the arithmetic is float32.

``operands`` is the control's hook (see ``reference_gpt2``). ``fault`` plants
one of ``FAULTS``: ``no_window`` (the sliding layers made causal),
``default_rope_full`` (the full layers' YaRN table replaced by the default
one), ``no_attention_factor`` (cos and sin not scaled), ``no_renorm`` (the k
weights not renormalised), ``capacity_drop`` (the most loaded held expert's
slots beyond an even share dropped).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import weights_mellum2 as seeded
from .reference_gpt2 import (_adamw, _zeros_like, exact_operands,
                             fp8_operands)
# what does not depend on the model: the routed experts' equations, leaves
# that hold one slice per held expert, a part's weights widened, the norms
# and projections read
from .reference_qwen3_next import (STACKED, _embed, _embed_grad, _floats,
                                   _norms_and_projections, _norms_per_expert,
                                   _static, _wide, experts)
from .reference_sdar_moe import rms_norm

HI = lax.Precision.HIGHEST
F32 = jnp.float32
FAULTS = ("no_window", "default_rope_full", "no_attention_factor",
          "no_renorm", "capacity_drop")

__all__ = ["train", "loss_and_grads", "logits", "attention", "experts",
           "yarn_inv_freq", "exact_operands", "fp8_operands", "FAULTS",
           "STACKED"]


def yarn_inv_freq(theta, dim, factor, original, beta_fast, beta_slow):
    """[dim / 2] float32: the YaRN table, in the module's equations."""
    i = np.arange(dim // 2, dtype=np.float64)
    inv_extra = theta ** (-2.0 * i / dim)
    inv_inter = inv_extra / factor
    low = math.floor(dim * math.log(original / (beta_fast * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(dim * math.log(original / (beta_slow * 2 * math.pi))
                     / (2 * math.log(theta)))
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (inv_inter * ramp + inv_extra * (1.0 - ramp)).astype(np.float32)


def rotary(x, inv_freq, factor=1.0):
    """[b, s, heads, d] turned on every dim, pair (i, i + d / 2); cos and sin
    times ``factor``."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * jnp.asarray(inv_freq)
    cos = factor * jnp.cos(ang)[None, :, None]
    sin = factor * jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def table(sizes, rope, fault=None):
    """(inv_freq, the factor on cos and sin) of a layer's ``rope`` section."""
    d, theta = sizes["head_dim"], rope["rope_theta"]
    if rope["rope_type"] != "yarn" or fault == "default_rope_full":
        return (theta ** (-np.arange(d // 2, dtype=np.float64) * 2.0 / d)
                ).astype(np.float32), 1.0
    factor = 1.0 if fault == "no_attention_factor" else \
        rope["attention_factor"]
    return yarn_inv_freq(theta, d, rope["factor"],
                         rope["original_max_position_embeddings"],
                         rope["beta_fast"], rope["beta_slow"]), factor


def attention(x, w, sizes, kind, rope, operands=exact_operands, fault=None):
    """x [b, s, h]; returns W_o attention of a layer of ``kind`` whose rotary
    section is ``rope`` (a dict)."""
    b, s, _ = x.shape
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = sizes["head_dim"]
    window = sizes["sliding_window"] if (
        kind == "sliding_attention" and fault != "no_window") else None

    def mm(a, m):
        return jnp.matmul(operands(a), operands(m), precision=HI)

    inv_freq, factor = table(sizes, rope, fault)
    q = rotary(mm(x, w["q_w"]).reshape(b, s, heads, d), inv_freq, factor)
    k = rotary(mm(x, w["k_w"]).reshape(b, s, kv, d), inv_freq, factor)
    v = mm(x, w["v_w"]).reshape(b, s, kv, d)
    k, v = (operands(jnp.repeat(a, heads // kv, axis=2)) for a in (k, v))
    rows = math.gcd(s, 256)

    @jax.checkpoint
    def row_block(args):
        qb, r0 = args  # [b, rows, heads, d], the block's first row
        scores = jnp.einsum("bqnd,bknd->bnqk", operands(qb), k,
                            precision=HI) / math.sqrt(d)
        i = r0 + jnp.arange(rows)[:, None]
        j = jnp.arange(s)[None, :]
        seen = j <= i
        if window is not None:
            seen = seen & (j > i - window)
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
# then pulled back in reverse from the inputs kept, as in
# ``reference_qwen3_next``: what is live is the state, the parts' inputs, the
# gradients made so far and ONE part's intermediates.
MIXER_LEAVES = ("norm1", "q_w", "k_w", "v_w", "o_w")


def mixer_part(x, w, sizes, operands, fault, kind, rope):
    """One sequence at a time (attention does not look across sequences),
    each made again in the backward."""
    rope = dict(rope)

    @jax.checkpoint
    def one(row):
        m = rms_norm(row[None], w["norm1"], sizes["rms_norm_eps"])
        return attention(m, w, sizes, kind, rope, operands, fault)[0]

    return x + lax.map(one, x)


def experts_part(x, w, sizes, operands, fault, kind, rope):
    b, s, h = x.shape
    m = rms_norm(x, w["norm2"], sizes["rms_norm_eps"]).reshape(b * s, h)
    return x + experts(m, w, sizes, operands, fault=fault,
                       shared=False).reshape(b, s, h)


def head_part(x, w, y, sizes, operands):
    """Mean next-token cross entropy over the held rows of the vocabulary;
    the head and the loss run in row chunks, recomputed in the backward."""
    h = rms_norm(x, w["norm_f"], sizes["rms_norm_eps"])
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
_STATIC = ("part", "sizes", "operands", "fault", "kind", "rope")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _forward(part, x, w, sizes, operands, fault, kind, rope):
    return PARTS[part](x, _wide(w), dict(sizes), operands, fault, kind, rope)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _pull_back(part, x, w, dy, sizes, operands, fault, kind, rope):
    _, vjp = jax.vjp(lambda x, w: PARTS[part](
        x, _wide(w), dict(sizes), operands, fault, kind, rope), x, w)
    return vjp(dy)


@functools.partial(jax.jit, static_argnames=("sizes", "operands"))
def _head(x, w, y, sizes, operands):
    loss, (dx, dw) = jax.value_and_grad(
        lambda x, w: head_part(x, _wide(w), y, dict(sizes), operands),
        argnums=(0, 1))(x, w)
    return loss, dx, dw


def parts_of(p, sizes):
    """[(part, kind, rope section as items, {bare leaf name: the leaf's name
    in p})] in the order the model applies them."""
    out = []
    for i in range(sizes["num_hidden_layers"]):
        tail = f".{i}"
        bare = {k[:-len(tail)]: k for k in p if k.endswith(tail)}
        kind = sizes["layer_types"][i]
        rope = tuple(sorted(sizes["rope_parameters"][kind].items()))
        out.append(("mixer", kind, rope,
                    {b: k for b, k in bare.items() if b in MIXER_LEAVES}))
        out.append(("experts", kind, rope,
                    {b: k for b, k in bare.items() if b not in MIXER_LEAVES}))
    return out


def hidden(p, ids, sizes, operands=exact_operands, fault=None, keep=None):
    """The trunk's output before the final norm; ``keep`` (a list) is given
    each part's input."""
    static = _static(sizes)
    x = _embed(p["embed"], ids)
    for part, kind, rope, names in parts_of(p, sizes):
        if keep is not None:
            keep.append(x)
        x = _forward(part, x, {b: p[k] for b, k in names.items()}, static,
                     operands, fault, kind, rope)
    return x


def logits(p, ids, sizes, operands=exact_operands):
    h = rms_norm(hidden(p, ids, sizes, operands), p["norm_f"].astype(F32),
                 sizes["rms_norm_eps"])
    return jnp.matmul(operands(h), operands(p["head_w"].astype(F32)),
                      precision=HI)


def loss_and_grads(p, x, y, sizes, operands=exact_operands, fault=None):
    """Mean loss and its gradient per leaf, in each leaf's own type."""
    static, kept = _static(sizes), []
    out = hidden(p, x, sizes, operands, fault, keep=kept)
    loss, dx, grads = _head(
        out, {"norm_f": p["norm_f"], "head_w": p["head_w"]}, y, static,
        operands)
    del out
    for part, kind, rope, names in reversed(parts_of(p, sizes)):
        dx, dw = _pull_back(part, kept.pop(),
                            {b: p[k] for b, k in names.items()}, dx, static,
                            operands, fault, kind, rope)
        grads.update({names[b]: g for b, g in dw.items()})
    grads["embed"] = _embed_grad(p["embed"], x, dx)
    return loss, grads


def train(sizes, seed, batches, hyper, param_dtype, steps=3,
          operands=exact_operands, rows=None, frozen=False, fault=None):
    """Follow the first ``steps`` steps from the seed. ``batches`` are the
    host arrays [batch, seq + 1] the program was fed. ``rows`` (a slice)
    leaves the other rows of each batch out, the mean taken over the rest;
    ``frozen`` returns the state unchanged; ``fault``: see the module.

    Returns losses per step and, per leaf, the norm and the projection
    (``weights_mellum2.projection``) of the first gradient and of the
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
