"""The one-part-a-layer hybrid decoder of ``configs/nemotron-3-nano-*`` in
plain ``jax.numpy``: forward, loss, gradients and the AdamW update, float32
with every product at ``Precision.HIGHEST``.

Written from the layer equations of PERF.md §4 (the public ``config.json``
of NVIDIA-Nemotron-3-Nano-30B-A3B gives every size). With h the residual
stream, plain RMSNorm gains (eps 1e-5) and layer i's kind the i-th letter of
``hybrid_override_pattern``:  h = E[ids];  h += part_i(rmsnorm(h));  logits
= rmsnorm(h) W_head, the head untied; the loss the mean next-token cross
entropy over the held rows of the vocabulary.

  M (Mamba-2): (x B C | z) = u W_xbcz, dt = u W_dt; (x, B, C) <- silu(causal
    depthwise conv, kernel 4, + bias); dt <- softplus(dt + dt_bias), a_t =
    exp(-exp(A_log) dt_t); the TOKEN-BY-TOKEN recurrence S_t = a_t S_(t-1)
    + dt_t x_t B_t^T, y_t = S_t C_t + D x_t per head
    (``reference_granite_hybrid.ssd_recurrence``), head h reading B / C
    group h // (64 / 8); g = y * silu(z), then g / rms(g) * gain with one
    mean of squares for each group of 512 channels; W_out.
  E (experts): s = sigmoid(u W_r) over ALL router outputs in float32; the
    experts are the 6 largest of s + b (b the correction bias, which takes
    part in the choice only), their weights s renormalised (over the sum
    plus 1e-20) and times ``routed_scaling_factor``; an expert is W_d
    relu(W_u u)^2; a sum over the HELD experts with masks (what the absent
    experts would add is left out, as in the program); the shared expert W_sd
    relu(W_su u)^2, ungated.
  * (attention): q, k, v, o without bias, no positions; the dense masked
    softmax of q k^T / sqrt(128) in row blocks, query head i on KV head i //
    16 (``reference_granite_hybrid.attention`` at that scale).

Every count in ``sizes`` is what this chip holds. It imports nothing of
``paddle_tpu`` and takes its weights from the seed
(``lib/weights_nemotron_h.py``). Parameters and Adam moments are STORED in
the configuration's ``param_dtype`` between steps and the gradient comes
out in it, as the program holds them; the arithmetic is float32; the
correction bias is float32 and, after each step, moves by the balancing
rule written out in ``moved_bias`` (the configuration's
``router_bias_update_rate``; the choice is made again from the part's
input, whatever the fault, and the whole batch is counted).

``operands`` is the control's hook (see ``reference_gpt2``). ``fault``
plants one of ``FAULTS``: ``bias_ignored`` (the choice by s alone),
``softmax_router`` (softmax scores, no bias, in place of sigmoid ones),
``no_routed_scale`` (the weights not times the scale), ``relu_not_squared``
(relu for relu^2, routed and shared), ``one_bc_group`` (every head reads B /
C group 0), ``norm_over_all_lanes`` (one statistic over all channels of the
gated norm).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import weights_nemotron_h as seeded
from .reference_gpt2 import (_adamw, _zeros_like, exact_operands,
                             fp8_operands)
from .reference_granite_hybrid import attention as _nope_attention
from .reference_granite_hybrid import rms_norm, ssd_recurrence
# what does not depend on the model: a part's weights widened, the
# embedding's lookup and its gradient, the norms and projections read
from .reference_qwen3_next import (_embed, _embed_grad, _floats,
                                   _norms_and_projections, _static, _wide)

HI = lax.Precision.HIGHEST
F32 = jnp.float32
FAULTS = ("bias_ignored", "softmax_router", "no_routed_scale",
          "relu_not_squared", "one_bc_group", "norm_over_all_lanes")
STACKED = ("eu_w", "ed_w")  # leaves that hold one slice per held expert

__all__ = ["train", "loss_and_grads", "logits", "mamba", "experts",
           "choice", "held_weights", "moved_bias", "bias_moves",
           "bc_group_norms", "gated_norm",
           "exact_operands", "fp8_operands", "FAULTS", "STACKED"]


def _mm(operands):
    def mm(a, m):
        return jnp.matmul(operands(a), operands(m), precision=HI)
    return mm


# ---------------------------------------------------------------------------
# Mamba-2
# ---------------------------------------------------------------------------
def gated_norm(y, z, gain, group, eps):
    """y * silu(z), then one mean of squares for each run of ``group``
    channels, rsqrt, the gain: y, z [.., channels]."""
    g = y * jax.nn.silu(z)
    runs = g.reshape(*g.shape[:-1], g.shape[-1] // group, group)
    statistic = jnp.square(runs).mean(-1, keepdims=True)
    return (runs * lax.rsqrt(statistic + eps)).reshape(g.shape) * gain


def mamba(u, w, sizes, operands=exact_operands, fault=None):
    """u [b, s, h] -> [b, s, h]: one Mamba-2 mixer."""
    b, s, _ = u.shape
    heads, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    groups, n = sizes["n_groups"], sizes["ssm_state_size"]
    inner, bc, channels = seeded.mamba_widths(sizes)
    mm = _mm(operands)
    xbcz, dt = mm(u, w["xbcz_w"]), mm(u, w["dt_w"])
    xbc, z = xbcz[..., :channels], xbcz[..., channels:]
    taps = w["conv_w"].shape[-1]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, i:i + s] * w["conv_w"][:, i]
                          for i in range(taps)) + w["conv_b"])
    x = xbc[..., :inner].reshape(b, s, heads, p)
    bm = xbc[..., inner:inner + bc].reshape(b, s, groups, n)
    cm = xbc[..., inner + bc:].reshape(b, s, groups, n)
    if fault == "one_bc_group":
        bm, cm = bm[:, :, :1], cm[:, :, :1]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = jnp.exp(-jnp.exp(w["a_log"]) * dt)
    y = ssd_recurrence(operands(x), dt, a, operands(bm), operands(cm))
    y = (y + w["d_skip"][:, None] * x).reshape(b, s, inner)
    group = inner if fault == "norm_over_all_lanes" else inner // groups
    normed = gated_norm(y, z, w["gnorm"], group, sizes["layer_norm_epsilon"])
    return mm(normed, w["out_w"])


# ---------------------------------------------------------------------------
# experts
# ---------------------------------------------------------------------------
def choice(x, router, bias, sizes, operands, fault):
    """(weights [T, k], router outputs [T, k]) of each token's k experts,
    routing over ALL of the router."""
    top_k = sizes["num_experts_per_tok"]
    logits = _mm(operands)(x, router)
    if fault == "softmax_router":
        wt, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    else:
        s = jax.nn.sigmoid(logits)
        _, idx = lax.top_k(s if fault == "bias_ignored" else s + bias, top_k)
        wt = jnp.take_along_axis(s, idx, axis=-1)
    if sizes["norm_topk_prob"]:
        wt = wt / (wt.sum(-1, keepdims=True) + 1e-20)
    if fault != "no_routed_scale":
        wt = wt * sizes["routed_scaling_factor"]
    return wt, idx


def held_weights(x, router, bias, sizes, held, operands, fault):
    """[T, count]: the weight each held expert has for each token (0 where
    it is not among the token's k)."""
    first, count = held
    wt, idx = choice(x, router, bias, sizes, operands, fault)
    onehot = jax.nn.one_hot(idx - first, count, dtype=F32)  # [T, k, count]
    return (onehot * wt[..., None]).sum(1)


def moved_bias(bias, x, router, sizes, operands, fault):
    """The correction bias after the balancing rule's step on tokens x:
    each router output's slots counted over all of them, and its bias moved
    by the rate toward even load (up below the mean count, down above it,
    not at all at it), in float32."""
    _, idx = choice(x, router, bias, sizes, operands, fault)
    outputs = router.shape[-1]
    load = (jax.nn.one_hot(idx, outputs, dtype=F32)).sum((0, 1))
    even = idx.size / outputs
    rate = np.float32(sizes["router_bias_update_rate"])
    return bias + rate * jnp.sign(even - load)


def _act(fault):
    if fault == "relu_not_squared":
        return jax.nn.relu
    return lambda a: jnp.square(jax.nn.relu(a))


def experts(x, w, sizes, operands=exact_operands, held=None, fault=None,
            shared=True):
    """[T, h] -> [T, h]: the held experts' part, and the shared expert."""
    if held is None:
        held = (sizes.get("held_first", 0), sizes["n_routed_experts"])
    tokens, h = x.shape
    act, mm = _act(fault), _mm(operands)
    we = held_weights(x, w["router"], w["bias"], sizes, held, operands,
                      fault)
    eu, ed = operands(w["eu_w"]), operands(w["ed_w"])
    rows = math.gcd(tokens, 1024)

    @jax.checkpoint
    def token_block(args):
        xb, wb = args  # [rows, h], [rows, count]
        up = jnp.einsum("th,ehf->etf", operands(xb), eu, precision=HI)
        a = act(up) * wb.T[:, :, None]
        return jnp.einsum("etf,efh->th", operands(a), ed, precision=HI)

    y = lax.map(token_block, (x.reshape(-1, rows, h),
                              we.reshape(-1, rows, we.shape[-1])))
    y = y.reshape(tokens, h)
    if shared:
        y = y + mm(act(mm(x, w["su_w"])), w["sd_w"])
    return y


# ---------------------------------------------------------------------------
# the model, part by part
# ---------------------------------------------------------------------------
# A step is followed one part at a time (a layer's one part, the head with
# the loss), each a program of its own, forward and then pulled back in
# reverse from the inputs kept, as in ``reference_qwen3_next``: what is live
# is the state, the parts' inputs, the gradients made so far and ONE part's
# intermediates. A mixer runs one sequence at a time and is made again in
# its pull-back: a whole 2 x 8,192 pull-back would not fit beside the state.
def part(x, w, sizes, operands, fault, kind):
    eps = sizes["layer_norm_epsilon"]
    if kind == "experts":
        b, s, h = x.shape
        u = rms_norm(x, w["norm"], eps).reshape(b * s, h)
        return x + experts(u, w, sizes, operands,
                           fault=fault).reshape(b, s, h)
    if kind == "mamba":
        def mixer(u):
            return mamba(u, w, sizes, operands, fault)
    else:
        scaled = dict(sizes, attention_multiplier=sizes["head_dim"] ** -0.5)

        def mixer(u):
            return _nope_attention(u, w, scaled, operands)

    @jax.checkpoint
    def one(row):
        return mixer(rms_norm(row[None], w["norm"], eps))[0]

    return x + lax.map(one, x)


def head_part(x, w, y, sizes, operands):
    """Mean next-token cross entropy over the held rows of the vocabulary;
    the head and the loss run in row chunks, recomputed in the backward."""
    h = rms_norm(x, w["norm_f"], sizes["layer_norm_epsilon"])
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


_STATIC = ("sizes", "operands", "fault", "kind")


def _bias_apart(w):
    """(the part's weights widened, its correction bias as it is)."""
    wide = _wide({k: a for k, a in w.items() if k != "bias"})
    if "bias" in w:
        wide["bias"] = lax.stop_gradient(w["bias"])
    return wide


@functools.partial(jax.jit, static_argnames=_STATIC)
def _forward(x, w, sizes, operands, fault, kind):
    return part(x, _bias_apart(w), dict(sizes), operands, fault, kind)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _pull_back(x, w, bias, dy, sizes, operands, fault, kind):
    """(dx, {leaf: gradient}) of a part; the bias takes no gradient."""
    def run(x, w):
        return part(x, _bias_apart(dict(w, **bias)), dict(sizes), operands,
                    fault, kind)

    _, vjp = jax.vjp(run, x, w)
    return vjp(dy)


@functools.partial(jax.jit, static_argnames=("sizes", "operands", "fault"))
def _moved_bias(x, w, sizes, operands, fault):
    """An expert part's correction bias after the step whose input is x."""
    sizes = dict(sizes)
    b, s, h = x.shape
    u = rms_norm(x, w["norm"].astype(F32), sizes["layer_norm_epsilon"])
    return moved_bias(w["bias"], u.reshape(b * s, h),
                      w["router"].astype(F32), sizes, operands, fault)


@functools.partial(jax.jit, static_argnames=("sizes", "operands"))
def _head(x, w, y, sizes, operands):
    loss, (dx, dw) = jax.value_and_grad(
        lambda x, w: head_part(x, _wide(w), y, dict(sizes), operands),
        argnums=(0, 1))(x, w)
    return loss, dx, dw


def parts_of(p, sizes):
    """[(kind, {bare leaf name: the leaf's name in p})] in the order the
    model applies them."""
    out = []
    for i, kind in enumerate(seeded.kinds(sizes)):
        tail = f".{i}"
        out.append((kind, {k[:-len(tail)]: k for k in p if k.endswith(tail)}))
    return out


def hidden(p, ids, sizes, operands=exact_operands, fault=None, keep=None,
           moved=None):
    """The trunk's output before the final norm; ``keep`` (a list) is given
    each part's input, ``moved`` (a dict) each expert part's correction
    bias after the balancing rule's step on this batch."""
    static = _static(sizes)
    x = _embed(p["embed"], ids)
    for kind, names in parts_of(p, sizes):
        if keep is not None:
            keep.append(x)
        w = {b: p[k] for b, k in names.items()}
        if moved is not None and kind == "experts":
            moved[names["bias"]] = _moved_bias(x, w, static, operands, fault)
        x = _forward(x, w, static, operands, fault, kind)
    return x


def logits(p, ids, sizes, operands=exact_operands):
    h = rms_norm(hidden(p, ids, sizes, operands), p["norm_f"].astype(F32),
                 sizes["layer_norm_epsilon"])
    return jnp.matmul(operands(h), operands(p["head_w"].astype(F32)),
                      precision=HI)


def loss_and_grads(p, x, y, sizes, operands=exact_operands, fault=None,
                   moved=None):
    """Mean loss and its gradient per parameter leaf, in each leaf's own
    type (the correction biases in ``p`` get none); ``moved``: see
    ``hidden``."""
    static, kept = _static(sizes), []
    out = hidden(p, x, sizes, operands, fault, keep=kept, moved=moved)
    loss, dx, grads = _head(
        out, {"norm_f": p["norm_f"], "head_w": p["head_w"]}, y, static,
        operands)
    del out
    for kind, names in reversed(parts_of(p, sizes)):
        w = {b: p[k] for b, k in names.items() if not seeded.is_bias(k)}
        bias = {b: p[k] for b, k in names.items() if seeded.is_bias(k)}
        dx, dw = _pull_back(kept.pop(), w, bias, dx, static, operands, fault,
                            kind)
        grads.update({names[b]: g for b, g in dw.items()})
    grads["embed"] = _embed_grad(p["embed"], x, dx)
    return loss, grads


def bc_group_norms(tree, sizes):
    """{'xbcz_w.0/B3': norm}: each Mamba-2 input projection's B and C
    columns, one float32 norm per group (the columns ``inner + g N ..`` and
    ``inner + groups N + g N ..``): a whole leaf's norm cannot see which
    group a head read, so long as B and C weigh little in it."""
    inner, bc, _ = seeded.mamba_widths(sizes)
    n = sizes["ssm_state_size"]
    out = {}
    for k, a in tree.items():
        if not k.startswith("xbcz_w"):
            continue
        for name in bc_group_names(k, sizes):
            part, g = name[-2], int(name.split("/")[1][1:])
            at = inner + g * n + (bc if part == "C" else 0)
            out[name] = jnp.sqrt(jnp.square(a[:, at:at + n].astype(F32)).sum())
    return out


def bc_group_names(leaf, sizes):
    """``bc_group_norms``'s names for one leaf, in its order: B0, C0, B1,
    ..."""
    return [f"{leaf}/{part}{g}" for g in range(sizes["n_groups"])
            for part in "BC"]


@jax.jit
def _norms_per_expert(tree):
    return {k: jnp.sqrt(jnp.square(a.astype(F32)).sum(
        tuple(range(1, a.ndim)))) for k, a in tree.items()
        if k.startswith(STACKED)}


def train(sizes, seed, batches, hyper, param_dtype, steps=3,
          operands=exact_operands, rows=None, frozen=False, fault=None):
    """Follow the first ``steps`` steps from the seed. ``batches`` are the
    host arrays [batch, seq + 1] the program was fed. ``rows`` (a slice)
    leaves the other rows of each batch out, the mean taken over the rest;
    ``frozen`` returns the state unchanged; ``fault``: see the module.

    Returns losses per step and, per parameter leaf, the norm and the
    projection (``weights_nemotron_h.projection``) of the first gradient and
    of the parameters' change after the last step; the first gradient's
    norm per held expert of each stacked leaf (``expert_grad_norms``) and
    per B / C group of each Mamba-2 input projection
    (``bc_group_grad_norms``); and each correction bias's move over the
    steps (``bias_moves``)."""
    p = seeded.make(sizes, seed, param_dtype)
    biases = {k: p.pop(k) for k in list(p) if seeded.is_bias(k)}
    m, v = _zeros_like(p), _zeros_like(p)
    losses, grad_norms, grad_sums, expert_norms = [], None, None, None
    for t in range(steps):
        ids = np.asarray(batches[t])
        if rows is not None:
            ids = ids[rows]
        x, y = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])
        moved = {}
        loss, g = loss_and_grads(dict(p, **biases), x, y, sizes, operands,
                                 fault, moved)
        losses.append(float(loss))
        if t == 0:
            grad_norms, grad_sums = map(_floats, _norms_and_projections(
                g, seeded.projection(sizes)))
            expert_norms = {
                f"{k}/{e}": float(x) for k, a in _norms_per_expert(g).items()
                for e, x in enumerate(np.asarray(a, np.float64))}
            group_norms = _floats(bc_group_norms(g, sizes))
        if not frozen:
            p, m, v = _adamw(p, g, m, v, float(t + 1), hyper["lr"],
                             hyper["b1"], hyper["b2"], hyper["eps"],
                             hyper["wd"])
            biases = moved
        del g
    del m, v
    start = seeded.make(sizes, seed, param_dtype)
    delta_norms, delta_sums = map(_floats, _norms_and_projections(
        p, seeded.projection(sizes), {k: start[k] for k in p}))
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_sums": grad_sums, "expert_grad_norms": expert_norms,
            "bc_group_grad_norms": group_norms,
            "delta_norms": delta_norms, "delta_sums": delta_sums,
            "bias_moves": bias_moves(biases, start)}


def bias_moves(biases, start):
    """{'bias.1': float64 [router outputs]}: each correction bias less its
    seeded start."""
    return {k: np.asarray(b, np.float64) - np.asarray(start[k], np.float64)
            for k, b in biases.items()}
