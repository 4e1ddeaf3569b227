"""GPT-2 in plain ``jax.numpy``: forward, loss, gradients and the AdamW
update, float32 with every product at ``Precision.HIGHEST``.

Written from the published description (Radford et al. 2019; the
``gpt2-medium`` / ``gpt2-large`` ``config.json``): learned position
embeddings, pre-LayerNorm blocks (eps 1e-5), causal softmax attention,
tanh-GELU MLP of width 4h, final LayerNorm, head tied to the token embedding.
It imports nothing of ``paddle_tpu`` and takes its weights from the seed
(``lib/weights.py``), never from the program. Departures, each stated in the
configuration files: the vocabulary is padded to ``padded_vocab`` rows (the
loss runs over all of them); ``qkv_w`` columns are heads-major; parameters
and Adam moments are STORED in the configuration's ``param_dtype`` between
steps and the gradient is rounded to it before the update, as the program
holds them — the arithmetic stays float32.

Layers run under ``lax.scan`` with ``jax.checkpoint`` and the head in row
chunks, so the reference fits beside nothing else on one chip.

``operands`` is the control's hook: a function applied to both operands of
every matrix product. ``fp8_operands`` rounds them to float8_e4m3 under a
per-tensor scale, the next precision below the bfloat16 the cells state.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import weights as seeded

HI = lax.Precision.HIGHEST
LN_EPS = 1e-5
STACKED = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
           "ln2_g", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")


def exact_operands(x):
    return x


def fp8_operands(x):
    """Per-tensor-scaled float8_e4m3 rounding, straight-through gradient."""
    scale = jnp.max(jnp.abs(x)) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def layer_norm(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + LN_EPS) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, w, n_head, operands):
    b, s, h = x.shape
    hd = h // n_head

    def mm(a, m):
        return jnp.matmul(operands(a), operands(m), precision=HI)

    a = layer_norm(x, w["ln1_g"], w["ln1_b"])
    qkv = (mm(a, w["qkv_w"]) + w["qkv_b"]).reshape(b, s, n_head, 3, hd)
    q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
    scores = jnp.einsum("bqnd,bknd->bnqk", operands(q), operands(k),
                        precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bnqk,bknd->bqnd", operands(probs), operands(v),
                   precision=HI).reshape(b, s, h)
    x = x + mm(o, w["proj_w"]) + w["proj_b"]
    m = layer_norm(x, w["ln2_g"], w["ln2_b"])
    return x + mm(gelu_tanh(mm(m, w["fc1_w"]) + w["fc1_b"]),
                  w["fc2_w"]) + w["fc2_b"]


def hidden(w, ids, n_head, operands=exact_operands):
    """Final-LayerNorm output [rows, seq, h] for token ids [rows, seq]."""
    x = w["wte"][ids] + w["wpe"][: ids.shape[1]]
    body = jax.checkpoint(
        lambda x, wl: (block(x, wl, n_head, operands), None))
    x, _ = lax.scan(body, x, {k: w[k] for k in STACKED})
    return layer_norm(x, w["lnf_g"], w["lnf_b"])


def logits_of(w, h, operands=exact_operands):
    return jnp.matmul(operands(h), operands(w["wte"]).T, precision=HI)


def loss_sum(w, x, y, n_head, operands=exact_operands):
    """Summed next-token cross entropy over ``padded_vocab`` logits; the
    head and the loss run row by row, recomputed in the backward."""
    h = hidden(w, x, n_head, operands)

    @jax.checkpoint
    def row_loss(args):
        h_row, y_row = args
        lg = logits_of(w, h_row, operands)
        picked = jnp.take_along_axis(lg, y_row[:, None], axis=-1)[:, 0]
        return (jax.nn.logsumexp(lg, axis=-1) - picked).sum()

    return lax.map(row_loss, (h, y)).sum()


@functools.partial(jax.jit, static_argnames=("n_head", "operands"))
def _loss_and_grads(p, x, y, n_head, operands):
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    loss, g = jax.value_and_grad(loss_sum)(w, x, y, n_head, operands)
    n = x.size
    return loss / n, jax.tree_util.tree_map(lambda a: a / n, g)


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw(p, g, m, v, step, lr, b1, b2, eps, wd):
    f32 = jnp.float32

    def one(p, g, m, v):
        store = p.dtype
        g = g.astype(store).astype(f32)  # the optimizer gets it in store type
        m = b1 * m.astype(f32) + (1 - b1) * g
        v = b2 * v.astype(f32) + (1 - b2) * jnp.square(g)
        lr_t = lr * jnp.sqrt(1 - b2 ** step) / (1 - b1 ** step)
        new = p.astype(f32) * (1 - lr * wd) - lr_t * m / (jnp.sqrt(v) + eps)
        return new.astype(store), m.astype(store), v.astype(store)

    out = {k: one(p[k], g[k], m[k], v[k]) for k in p}
    return tuple({k: out[k][i] for k in p} for i in range(3))


@jax.jit
def _zeros_like(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


@jax.jit
def _leaf_norms(tree):
    """{name: norm} or, for a stacked leaf, one norm per layer."""
    def norm(name, a):
        a = a.astype(jnp.float32)
        axes = tuple(range(1, a.ndim)) if name in STACKED else None
        return jnp.sqrt(jnp.square(a).sum(axes))
    return {k: norm(k, a) for k, a in tree.items()}


@jax.jit
def _leaf_projections(tree, signs):
    """Like ``_leaf_norms``: each leaf's entries summed under fixed random
    signs, its projection on one direction."""
    return {k: (a.astype(jnp.float32) * signs[k].astype(jnp.float32)).sum(
        tuple(range(1, a.ndim)) if k in STACKED else None)
        for k, a in tree.items()}


@jax.jit
def _delta(new, old):
    return {k: new[k].astype(jnp.float32) - old[k].astype(jnp.float32)
            for k in new}


def flat_names(norms):
    """{'wte': x, 'qkv_w.3': y, ...} as Python floats."""
    out = {}
    for k, a in norms.items():
        a = np.asarray(a, np.float64)
        if a.ndim == 0:
            out[k] = float(a)
        else:
            out.update({f"{k}.{i}": float(x) for i, x in enumerate(a)})
    return out


def train(sizes, seed, batches, hyper, param_dtype, steps=3,
          operands=exact_operands, rows=None, frozen=False):
    """Follow the first ``steps`` steps from the seed. ``batches`` are the
    host arrays [batch, seq + 1] the program was fed. Two faults can be
    planted: ``rows`` (a slice) leaves the other rows of each batch out, the
    mean taken over the rest; ``frozen`` returns the state unchanged.

    Returns losses per step and, per leaf, the norm and the projection
    (``weights.projection``) of the first gradient and of the parameters' change after the last step.
    """
    p = seeded.stacked(sizes, seed, param_dtype)
    m, v = _zeros_like(p), _zeros_like(p)
    p0 = jax.tree_util.tree_map(jnp.copy, p)
    signs = seeded.projection(sizes)
    losses, grad_norms, grad_sums = [], None, None
    for t in range(steps):
        ids = np.asarray(batches[t])
        if rows is not None:
            ids = ids[rows]
        x, y = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])
        loss, g = _loss_and_grads(p, x, y, sizes["n_head"], operands)
        losses.append(float(loss))
        if t == 0:
            grad_norms = flat_names(_leaf_norms(g))
            grad_sums = flat_names(_leaf_projections(g, signs))
        if not frozen:
            p, m, v = _adamw(p, g, m, v, float(t + 1), hyper["lr"],
                             hyper["b1"], hyper["b2"], hyper["eps"],
                             hyper["wd"])
        del g
    delta = _delta(p, p0)
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_sums": grad_sums,
            "delta_norms": flat_names(_leaf_norms(delta)),
            "delta_sums": flat_names(_leaf_projections(delta, signs))}
