"""Mixture-of-Experts: two expert layers, for two uses.

``MoELayer`` (below, with its gates) is the reference-shaped layer: a
capacity-bucketed one-hot dispatch [T, E, C] that DROPS what overflows an
expert's bucket, experts as a list of Layers vmapped over stacked weights. It
stays for the reference API and its tests; its one-hots grow with T x E x C,
so it is for small expert counts and short batches.

``DroplessExperts`` (at the end of this file) is the layer a model on the
chip uses: it is told which experts of ``num_experts`` it HOLDS (this chip's
share of an expert-parallel deployment), routes over all of them, gathers the
slots that fall on held experts sorted by expert, multiplies them as grouped
matrix products (``jax.lax.ragged_dot``, a Mosaic kernel on the TPU) over
stacked ``[held, ...]`` leaves with the slot weight applied on the narrow
activation between them, and adds the down product's float32 rows into the
tokens with a kernel of its own (``ops/pallas/moe_combine.py``): between ops
the rows lie in the tokens' dtype, and nothing of the buffer's length and
the model's width is written but the two products' own results (forward;
backward likewise). No slot is
dropped whatever the imbalance, and every shape is static: the sorted slots
go through a row buffer of fixed size in as many passes as the routed load
needs (one, unless routing is badly skewed). On one chip it runs without its
exchange; there is no ``ep`` mesh axis yet.

What follows describes ``MoELayer``.

Reference analogue:
  - python/paddle/incubate/distributed/models/moe/moe_layer.py:226 MoELayer
    (experts LayerList + gate config {"type": naive|gshard|switch, "top_k"}),
    gates in .../moe/gate/{naive,gshard,switch}_gate.py;
  - expert dispatch via global_scatter/global_gather CUDA alltoall ops
    (paddle/fluid/operators/collective/global_scatter_op.cu.cc,
    python/paddle/distributed/utils.py:57,179).

TPU-native design (NOT a port): the reference routes tokens with index-based
scatter over NCCL alltoall. On TPU the idiomatic form is the GShard einsum
formulation — dense dispatch/combine one-hots contracted on the MXU:

    dispatch[t,e,c], combine[t,e,c]  (capacity-bucketed one-hots)
    expert_in  = einsum('tec,th->ech', dispatch, x)
    expert_out = vmap(expert)(stacked_params, expert_in)
    y          = einsum('ech,tec->th', expert_out, combine)

Expert weights are STACKED to a leading [num_experts, ...] dim carrying an
expert-parallel sharding spec (folded over dp×sharding, like the reference
folds EP into the data-parallel world); with tokens batch-sharded and experts
expert-sharded, GSPMD materializes exactly the all-to-all pair the reference
hand-writes — over ICI. Static shapes throughout (capacity fixed per step),
so the whole layer jits into one XLA program.
"""
from __future__ import annotations

import functools
import math
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle

from .. import nn
from ..core.dispatch import apply, no_grad
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.layer_base import Layer

__all__ = ["BaseGate", "NaiveGate", "GShardGate", "SwitchGate", "MoELayer",
           "DroplessExperts"]


class BaseGate(Layer):
    """reference: moe/gate/base_gate.py."""

    def __init__(self, num_expert, world_size=1):
        super().__init__()
        self.world_size = world_size
        self.num_expert = num_expert
        self.tot_expert = num_expert * world_size
        self.loss = None

    def get_loss(self, clear=True):
        loss = self.loss
        if clear:
            self.loss = None
        return loss


class NaiveGate(BaseGate):
    """Linear router + top-k, no aux loss (reference: naive_gate.py)."""

    def __init__(self, d_model, num_expert, world_size=1, topk=2):
        super().__init__(num_expert, world_size)
        self.gate = nn.Linear(d_model, self.tot_expert)
        self.top_k = topk

    def forward(self, x):
        logits = self.gate(x)  # [T, E]
        val, idx = paddle.topk(logits, self.top_k, axis=-1)
        # normalized combine weights over the selected experts
        gate_prob = F.softmax(val, axis=-1)
        return gate_prob, idx, logits


class GShardGate(NaiveGate):
    """Top-2 gate with the GShard load-balance aux loss
    l_aux = E * Σ_e (mean softmax prob on e) · (fraction of tokens on e)
    (reference: gshard_gate.py)."""

    def __init__(self, d_model, num_expert, world_size=1, topk=2,
                 capacity=(1.2, 2.4), group=None):
        super().__init__(d_model, num_expert, world_size, topk=topk)
        self.capacity = capacity

    def forward(self, x):
        gate_prob, idx, logits = super().forward(x)
        probs = F.softmax(logits, axis=-1)               # [T, E]
        me = probs.mean(axis=0)                          # [E]
        top1 = idx[:, 0]
        ce = F.one_hot(top1, self.tot_expert).astype("float32").mean(axis=0)
        self.loss = (me * ce).sum() * float(self.tot_expert)
        return gate_prob, idx, logits


class SwitchGate(NaiveGate):
    """Top-1 switch-transformer gate with its aux loss
    (reference: switch_gate.py)."""

    def __init__(self, d_model, num_expert, world_size=1, topk=1,
                 capacity=(1.2, 2.4), group=None):
        super().__init__(d_model, num_expert, world_size, topk=1)
        self.capacity = capacity

    def forward(self, x):
        logits = self.gate(x)
        probs = F.softmax(logits, axis=-1)
        val, idx = paddle.topk(probs, 1, axis=-1)
        me = probs.mean(axis=0)
        ce = F.one_hot(idx[:, 0], self.tot_expert).astype("float32").mean(axis=0)
        self.loss = (me * ce).sum() * float(self.tot_expert)
        return val, idx, logits


def _stack_expert_params(experts: List[Layer]):
    """[param_j over experts] → stacked [E, ...] arrays (homogeneity checked)."""
    named = [sorted(e.named_parameters(), key=lambda kv: kv[0]) for e in experts]
    shapes0 = [(k, tuple(p.shape)) for k, p in named[0]]
    for ns in named[1:]:
        if [(k, tuple(p.shape)) for k, p in ns] != shapes0:
            raise ValueError("MoE experts are not homogeneous")
    stacked = []
    for j in range(len(named[0])):
        stacked.append(jnp.stack([ns[j][1]._value for ns in named]))
    return stacked


class MoELayer(Layer):
    """reference: moe_layer.py:226. Einsum dispatch over stacked experts.

    `experts` is a list/LayerList of homogeneous Layers (e.g. the FFN expert
    of the reference docstring). Their weights are stacked into [E, ...]
    Parameters sharded over the expert-parallel axes; the per-expert Layer
    objects become the vmapped computation template.
    """

    def __init__(self, d_model, experts, gate=None, moe_group=None,
                 mp_group=None, capacity_factor=1.25, ep_axes=("dp", "sharding"),
                 **kwargs):
        super().__init__()
        self.d_model = d_model
        self.num_expert = len(experts)
        self.capacity_factor = capacity_factor
        self.group = moe_group
        self.recompute_interval = kwargs.get("recompute_interval", 0)
        # mp_group is accepted for reference-API parity but unused: TP inside
        # experts comes from weight dist_specs, not a separate comm group
        if moe_group is not None and moe_group.nranks > 1:
            # the reference hosts num_expert experts PER RANK (tot_expert
            # global) and alltoalls tokens between processes; here `experts`
            # is the GLOBAL list inside one SPMD program — sharding over
            # ranks comes from the stacked weights' expert-dim spec
            raise NotImplementedError(
                "pass the global expert list (experts are sharded over the "
                "mesh via their stacked weight spec); a moe_group with "
                "nranks > 1 implies the reference's per-rank expert hosting, "
                "which does not exist in the single-program SPMD model"
            )
        world = 1

        if gate is None:
            gate = {}
        if isinstance(gate, dict):
            self.top_k = gate.get("top_k", 2)
            gtype = gate.get("type", "gshard")
            if gtype in ("naive", None):
                gate = NaiveGate(d_model, self.num_expert, world, topk=self.top_k)
            elif gtype == "gshard":
                # dict-configured gates defer capacity to the layer's
                # capacity_factor; explicit gate instances keep their own
                gate = GShardGate(
                    d_model, self.num_expert, world, topk=self.top_k,
                    capacity=None,
                )
            elif gtype == "switch":
                gate = SwitchGate(d_model, self.num_expert, world, capacity=None)
            else:
                raise ValueError(f"unknown gate type {gtype!r}")
        self.top_k = gate.top_k
        self.gate = gate

        # template for the vmapped expert computation; its own params are
        # placeholders (bound per-expert at run time), so they are detached
        # from this layer's parameter list
        template = experts[0]
        object.__setattr__(self, "_template", template)
        self._template_objs = [
            p for _, p in sorted(template.named_parameters(), key=lambda kv: kv[0])
        ]
        stacked_vals = _stack_expert_params(list(experts))
        self.stacked_params = nn.ParameterList(
            [nn.Parameter(v) for v in stacked_vals]
        )
        for p in self.stacked_params:
            base = [None] * (p.ndim - 1)
            p.dist_spec = (tuple(ep_axes),) + tuple(base)
        self.l_aux = None

    def _capacity_factor(self):
        # gates may carry the reference's (train, eval) capacity pair; it
        # takes precedence over the layer-level capacity_factor
        cap = getattr(self.gate, "capacity", None)
        if cap is not None:
            return cap[0] if self.training else cap[1]
        return self.capacity_factor

    def _dispatch_tensors(self, x_flat):
        """Capacity-bucketed one-hot dispatch/combine (GShard algorithm)."""
        T = x_flat.shape[0]
        E, K = self.num_expert, self.top_k
        C = max(1, int(math.ceil(self._capacity_factor() * T * K / E)))
        gate_prob, idx, _ = self.gate(x_flat)  # [T, K]

        def build(prob, idx):
            # prob [T, K] f32, idx [T, K] i32 — all-jnp, traced in one op
            masks = jax.nn.one_hot(idx, E, dtype=jnp.float32)     # [T, K, E]
            # position of each (t, k) claim within its expert, priority by
            # slot then token order (gshard's sequential cumsum)
            flat = masks.transpose(1, 0, 2).reshape(K * T, E)     # slots major
            pos = jnp.cumsum(flat, axis=0) - flat                  # claims before
            pos = pos.reshape(K, T, E).transpose(1, 0, 2)          # [T, K, E]
            in_cap = (pos * masks).sum(-1, keepdims=True) < C      # [T, K, 1]
            masks = masks * in_cap
            cpos = (pos * masks).sum(-1).astype(jnp.int32)         # [T, K]
            cap_onehot = jax.nn.one_hot(cpos, C, dtype=jnp.float32)  # [T, K, C]
            # combine[t,e,c] = Σ_k prob[t,k]·mask[t,k,e]·cap[t,k,c]
            combine = jnp.einsum("tk,tke,tkc->tec", prob, masks, cap_onehot)
            dispatch = jnp.einsum("tke,tkc->tec", masks, cap_onehot)
            return combine, (dispatch > 0).astype(x_flat._value.dtype)

        return apply(build, gate_prob, idx, op_name="moe_dispatch"), C

    def forward(self, x):
        orig_shape = list(x.shape)
        h = self.d_model
        x_flat = x.reshape([-1, h])
        (combine, dispatch), C = self._dispatch_tensors(x_flat)
        self.l_aux = self.gate.get_loss(clear=True)

        expert_in = paddle.einsum("tec,th->ech", dispatch, x_flat)

        template, t_objs = self._template, self._template_objs

        def run_experts(*vals_and_x):
            *stacked, ein = vals_and_x

            def one(vals, xi):
                from ..jit import _bind_values

                with _bind_values(t_objs, list(vals)), no_grad():
                    return template(Tensor(xi, stop_gradient=True))._value

            return jax.vmap(one)(tuple(stacked), ein)

        if self.recompute_interval > 0:
            inner = run_experts
            run_experts = jax.checkpoint(inner)
        expert_out = apply(
            run_experts, *self.stacked_params, expert_in, op_name="moe_experts"
        )
        out = paddle.einsum("ech,tec->th", expert_out, combine)
        return out.reshape(orig_shape)


def global_scatter(x, local_count, global_count, group=None,
                   use_calc_stream=True):
    """Variable-count MoE dispatch alltoall (reference:
    distributed/utils.py:57 global_scatter over global_scatter_op.cu.cc).

    This framework's MoE path dispatches with CAPACITY-PADDED alltoall
    (static shapes — see MoELayer): ragged per-expert counts can't trace
    under XLA. World size 1 is the degenerate identity; for >1 use
    MoELayer / the padded alltoall primitive."""
    from ..parallel.topology import get_mesh

    mesh = get_mesh()
    if mesh is None or mesh.devices.size == 1:
        return x.clone() if hasattr(x, "clone") else x
    raise NotImplementedError(
        "ragged global_scatter has no static-shape XLA lowering; use "
        "incubate.moe.MoELayer (capacity-padded dispatch) or "
        "distributed.alltoall on equal splits"
    )


def global_gather(x, local_count, global_count, group=None,
                  use_calc_stream=True):
    """Inverse of global_scatter (reference: distributed/utils.py:179)."""
    from ..parallel.topology import get_mesh

    mesh = get_mesh()
    if mesh is None or mesh.devices.size == 1:
        return x.clone() if hasattr(x, "clone") else x
    raise NotImplementedError(
        "ragged global_gather has no static-shape XLA lowering; use "
        "incubate.moe.MoELayer (capacity-padded combine) or "
        "distributed.alltoall on equal splits"
    )


# ---------------------------------------------------------------------------
# the dropless layer: held experts of a wider router, grouped products
# ---------------------------------------------------------------------------
def route_top_k(logits, top_k, renormalize):
    """(weights [T, k] float32, expert ids [T, k]) of the ``top_k`` largest
    of softmax(logits) in float32; ``renormalize``: the k weights sum to 1."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(p, top_k)
    if renormalize:
        w = w / w.sum(-1, keepdims=True)
    return w, idx.astype(jnp.int32)


def route_sigmoid_top_k(logits, top_k, renormalize, bias, scale):
    """(weights [T, k] float32, expert ids [T, k]) under sigmoid scores s =
    sigmoid(logits) in float32: the experts are the ``top_k`` largest of s +
    ``bias`` (a correction that takes part in the choice only, and takes no
    gradient), the weights the chosen UNBIASED scores, renormalised to sum
    to 1 (``renormalize``, with 1e-20 beside the sum) and times ``scale``."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if renormalize:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * scale, idx.astype(jnp.int32)


def expert_load(idx, num_experts):
    """int32 [num_experts]: the slots each router output took of ``idx``
    [T, k], held or not."""
    outputs = jnp.arange(num_experts, dtype=idx.dtype)
    return (idx.reshape(-1, 1) == outputs).sum(0, dtype=jnp.int32)


def balance_score_bias(bias, load, rate):
    """The correction bias after one step of the auxiliary-loss-free
    balancing rule: each expert's bias moves by ``rate``, up where its
    ``load`` is under the mean over all router outputs, down where over, not
    at all where even (a sign step, in float32)."""
    load = load.astype(jnp.float32)
    return bias + np.float32(rate) * jnp.sign(load.mean() - load)


def sort_held_slots(idx, first, count):
    """The T x k routing slots in the order the grouped products want them:
    slots on held experts [first, first + count) first, sorted by expert, the
    rest after. Returns (order, inverse, offsets): ``order[i]`` is the flat
    slot at sorted place i, ``inverse`` its inverse permutation, and
    ``offsets`` [count + 1] the sorted places at which each held expert's
    slots start (``offsets[count]`` = slots routed to held experts)."""
    local = idx.reshape(-1) - np.int32(first)
    key = jnp.where((local >= 0) & (local < count), local, np.int32(count))
    place = jnp.arange(key.shape[0], dtype=jnp.int32)
    key_sorted, order = jax.lax.sort((key, place), num_keys=1)
    _, inverse = jax.lax.sort((order, place), num_keys=1)
    offsets = jnp.searchsorted(
        key_sorted, jnp.arange(count + 1, dtype=jnp.int32), side="left")
    return order, inverse, offsets.astype(jnp.int32)


@jax.custom_vjp
def _permute(x, order, inverse):
    return x[order]


_permute.defvjp(lambda x, order, inverse: (x[order], (order, inverse)),
                lambda res, g: (g[res[1]], None, None))


def _grouped(rows, stack, sizes):
    """rows [R, a] x stack [held, a, b] -> float32 [R, b], each row under its
    own expert's matrix: rows sorted by expert, ``sizes`` rows for each. A
    cotangent goes back through ``swapaxes(stack, 1, 2)``, a copy of the
    stack: dimension numbers that contract the stack's last axis instead
    lower to a dense product over all held experts under a mask, not to the
    grouped kernel."""
    return jax.lax.ragged_dot(rows, stack, sizes,
                              preferred_element_type=jnp.float32)


_ROWS_CONTRACTED = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _grouped_outer(left, right, sizes, dtype):
    """A stack's gradient as one grouped product: left [R, a] x right [R, b]
    -> [held, a, b] in ``dtype``, each expert's rows contracted away in
    float32."""
    return jax.lax.ragged_dot_general(left, right, sizes, _ROWS_CONTRACTED,
                                      preferred_element_type=dtype)


def _swiglu(gate, up):
    return jax.nn.silu(gate) * up


def _activate(act, h):
    """An expert's activation of its first product, float32 [rows, 2 d]
    gate | up for SwiGLU, [rows, d] for relu^2."""
    if act == "swiglu":
        return _swiglu(*jnp.split(h, 2, axis=-1))
    return jnp.square(jax.nn.relu(h))


def _activate_vjp(act, h):
    """(the activation, the map from its cotangent to h's)."""
    if act == "swiglu":
        s, pull = jax.vjp(_swiglu, *jnp.split(h, 2, axis=-1))
        return s, lambda g: jnp.concatenate(pull(g), axis=-1)
    r = jax.nn.relu(h)
    return jnp.square(r), lambda g: 2.0 * r * g


def _pass_rows(c, rows, tok, wgt, offsets, tokens):
    """Pass c of the row buffer: its slots' tokens, the same with the rows
    that hold no routed slot sent past the last token (where the combine
    drops them), its slots' weights, which rows hold a routed slot, and each
    held expert's rows inside it."""
    lo = c * np.int32(rows)
    valid = lo + jnp.arange(rows, dtype=jnp.int32) < offsets[-1]
    sizes = (jnp.clip(offsets[1:], lo, lo + rows)
             - jnp.clip(offsets[:-1], lo, lo + rows))
    t = jax.lax.dynamic_slice(tok, (lo,), (rows,))
    return (t, jnp.where(valid, t, np.int32(tokens)),
            jax.lax.dynamic_slice(wgt, (lo,), (rows,))[:, None],
            valid[:, None], sizes, lo)


def _n_passes(offsets, rows):
    return jnp.maximum((offsets[-1] + np.int32(rows - 1)) // np.int32(rows),
                       np.int32(1))


def _combine_plan(site, rows, tokens, lanes):
    """The token block the combine kernel takes a pass's float32 [rows,
    lanes] result into ``tokens`` tokens with, or None where the shape keeps
    XLA's scatter-add; one ``moe_combine`` event a trace and direction says
    which, and why."""
    from ..ops.pallas import moe_combine as mc
    from ..profiler import trace

    block, why = mc.plan(tokens, lanes)
    trace.emit("moe_combine", site=site, path="xla" if why else "kernel",
               rows=rows, tokens=tokens, lanes=lanes, token_block=block or 0,
               **({"why": why} if why else {}))
    return block


def _combine(part, kept, total, tokens, block):
    """``total`` (zeros on the first pass, where it is None) plus each
    float32 row of ``part`` added into the token ``kept`` names; a row that
    ``kept`` sends to ``tokens`` is never read. A token's rows are added in
    ascending row index on both paths, so the kernel's sums are the
    scatter-add's bit for bit."""
    if block is None:
        if total is None:
            total = jnp.zeros((tokens, part.shape[1]), jnp.float32)
        return total.at[kept].add(part, mode="drop")
    from ..ops.pallas import moe_combine as mc

    return mc.moe_combine(part, *mc.token_order(kept, tokens, block), total,
                          block=block)


def _held_forward(x, wgt, w_gate_up, w_down, tok, offsets, rows, act):
    """``held_experts_apply``'s output in float32, and the first pass's
    float32 first product ([rows, 2 d] gate-up, or [rows, d] up), which its
    backward takes in place of making it again."""
    tokens = x.shape[0]
    block = _combine_plan("forward", rows, tokens, x.shape[1])

    def one_pass(c, y, gate_up=None):
        t, kept, w, valid, sizes, _ = _pass_rows(
            c, rows, tok, wgt, offsets, tokens)
        if gate_up is None:
            gate_up = _grouped(x[t], w_gate_up, sizes)
        a = jnp.where(valid, _activate(act, gate_up) * w,
                      0.0).astype(x.dtype)
        return (_combine(_grouped(a, w_down, sizes), kept, y, tokens,
                         block), gate_up)

    y, gate_up = one_pass(np.int32(0), None)
    y = jax.lax.fori_loop(np.int32(1), _n_passes(offsets, rows),
                          lambda c, y: one_pass(c, y)[0], y)
    return y, gate_up


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def held_experts_apply(x, wgt, w_gate_up, w_down, tok, offsets, rows,
                       act="swiglu"):
    """sum over a token's slots on held experts of weight * expert(x): x
    [T, h]; the expert is W_d^T swiglu(W_gu^T x) over a [held, h, 2 d]
    ``w_gate_up`` stack (gate | up) with ``act`` "swiglu", or W_d^T
    relu(W_u^T x)^2 over a [held, h, d] up stack with ``act`` "relu2";
    ``tok`` / ``wgt`` the token and weight of each slot in sorted
    order, padded to a multiple of ``rows``; ``offsets`` from
    ``sort_held_slots``. The sorted slots pass through a buffer of ``rows``
    rows, ceil(routed / rows) times: the count is data, so the loop is a
    while loop and the backward (which walks the same passes, recomputing
    each later pass's activations) is written out, not derived.

    What a pass writes to HBM between its gather and its combine: the
    gathered rows (x's dtype), the float32 first product ([rows, 2 d] or
    [rows, d]), the weighted activation (x's dtype, [rows, d]; relu^2 made
    from the float32 product, then rounded once) and the down product's float32
    [rows, h] result, which the combine reads as it is. The slot weight and
    the mask sit on the d-wide activation, never on a [rows, h] array. The
    mask is a select there AND an index: the grouped product skips the tiles
    past the groups and leaves what was in memory, so those rows of its
    result are not zero whatever it was given; the combine never reads them
    (``_combine``), and nothing multiplies them. The first pass, which always
    runs, is made before the loop: its combine writes every token, so no
    zeros are written first; under differentiation its first product is
    kept for the backward."""
    return _held_forward(x, wgt, w_gate_up, w_down, tok, offsets, rows,
                         act)[0].astype(x.dtype)


def _held_fwd(x, wgt, w_gate_up, w_down, tok, offsets, rows, act):
    # the kept product leaves with y (see the barriers in _held_bwd); y in
    # float32, so that what reads it may still fuse its rounding. Measured,
    # not derived: without this barrier the Qwen3-Next expert layer alone
    # keeps 98 MB more (AOT compile for a v5e, PERF.md section 6)
    y, gate_up = jax.lax.optimization_barrier(
        _held_forward(x, wgt, w_gate_up, w_down, tok, offsets, rows, act))
    return y.astype(x.dtype), (x, wgt, w_gate_up, w_down, tok, offsets,
                               gate_up)


def _held_bwd(rows, act, res, dy):
    """The backward walks the forward's passes. A pass's first product is
    the one the forward kept on the first pass and is made again on a later
    one; four grouped products follow, every operand in the rows' dtype and
    every sum float32 (a one-pass step runs six with the forward's two,
    where a derived backward ran eight). With g = dy[t] W_d^T, the
    unweighted [rows, d] cotangent, the slot weight's gradient is
    sum(act * g) and needs no second down product, and the activation's
    is g * w; the weight goes on the activation's side of W_d's gradient
    too, so dy's rows enter both products as gathered. The cotangent of the
    first product ([rows, 2 d] for SwiGLU; [rows, d], 2 relu(u) g w, for
    relu^2) is masked and rounded once, where the activation's derivative
    is applied, for the two products that read it.

    The first pass, which always runs, is made before the loop: its combine
    writes dx with no zeros first, and each stack's gradient is written by
    its product in the stack's dtype. A later pass adds to the stacks in
    float32."""
    x, wgt, w_gate_up, w_down, tok, offsets, gate_up = res
    # measured, not derived: without a barrier on x the compiled Granite
    # step keeps 1.7 GB more; without the kept product behind it too, the
    # Granite expert layer alone keeps 139 MB more (AOT compiles for a v5e,
    # PERF.md section 6)
    x, gate_up = jax.lax.optimization_barrier((x, gate_up))
    tokens = x.shape[0]
    block = _combine_plan("backward", rows, tokens, x.shape[1])
    # one copy of each stack for its cotangent product, whichever pass reads
    w_gate_up_t, w_down_t = (jnp.swapaxes(w, 1, 2)
                             for w in (w_gate_up, w_down))

    def one_pass(c, dx, gate_up=None):
        t, kept, w, valid, sizes, lo = _pass_rows(
            c, rows, tok, wgt, offsets, tokens)
        xin, dyt = x[t], dy[t]
        if gate_up is None:
            gate_up = _grouped(xin, w_gate_up, sizes)
        s, pull = _activate_vjp(act, gate_up)
        g = _grouped(dyt, w_down_t, sizes)
        dh = jnp.where(valid, pull(g * w), 0.0).astype(x.dtype)
        a = jnp.where(valid, s * w, 0.0).astype(x.dtype)
        dw = jnp.where(valid[:, 0], (s * g).sum(-1), 0.0)
        # the order is measured, not derived: all that reads the gate-up
        # product is made before the products that follow it, and both
        # stacks' products before dx's, so that neither the product nor
        # the gathered rows are live beside dx's float32 [rows, h] product
        # and its combine, where the Granite step's first backward layer
        # peaked: the Granite and Mellum2 steps keep 262 and 135 MB less
        # (AOT compiles for a v5e, PERF.md section 6)
        dh, a, dw = jax.lax.optimization_barrier((dh, a, dw))
        dgu = _grouped_outer(xin, dh, sizes, w_gate_up.dtype)
        dd = _grouped_outer(a, dyt, sizes, w_down.dtype)
        dh, dgu, dd = jax.lax.optimization_barrier((dh, dgu, dd))
        return (_combine(_grouped(dh, w_gate_up_t, sizes), kept, dx, tokens,
                         block), dw, lo, dgu, dd)

    def added(total, part):
        return (total.astype(jnp.float32) + part).astype(total.dtype)

    def later_pass(c, carry):
        dx, dwgt, dgu, dd = carry
        dx, dw, lo, dgu_c, dd_c = one_pass(c, dx)
        return (dx, jax.lax.dynamic_update_slice(dwgt, dw, (lo,)),
                added(dgu, dgu_c), added(dd, dd_c))

    dx, dw, lo, dgu, dd = one_pass(np.int32(0), None, gate_up)
    dwgt = jax.lax.dynamic_update_slice(jnp.zeros(wgt.shape, jnp.float32),
                                        dw, (lo,))
    dx, dwgt, dgu, dd = jax.lax.fori_loop(
        np.int32(1), _n_passes(offsets, rows), later_pass,
        (dx, dwgt, dgu, dd))
    return dx.astype(x.dtype), dwgt.astype(wgt.dtype), dgu, dd, None, None


held_experts_apply.defvjp(_held_fwd, _held_bwd)


# The row buffer holds 1.2 times the slots an even router sends to the held
# experts, in whole 512-row tiles (the grouped product's own): on the chip at
# 16,384 tokens, 64 of 512 held, ten a token, every step of every run took ONE
# pass and the products ran over 0.18-0.20 more rows than were routed
# (PERF.md section 5). Constants, not options: they fix the compiled shapes.
ROW_SLACK = 1.2
ROW_TILE = 512


def row_buffer_rows(tokens, top_k, num_experts, held):
    """Rows of the buffer the sorted slots pass through: ``ROW_SLACK`` times
    the slots an even router sends to ``held`` of ``num_experts`` experts, up
    to whole tiles, and never more than all the slots there are."""
    even = tokens * top_k * held / num_experts
    rows = -(-int(math.ceil(ROW_SLACK * even)) // ROW_TILE) * ROW_TILE
    return max(8, min(rows, -(-tokens * top_k // 8) * 8))


def _check_router(router, score_bias, routed_scale):
    if router not in ("softmax", "sigmoid"):
        raise ValueError(f"router {router!r}")
    if (score_bias is None) != (router == "softmax"):
        raise ValueError("a sigmoid router takes a score_bias, a softmax "
                         f"one none: router {router!r}, score_bias "
                         f"{'none' if score_bias is None else 'given'}")
    if router == "softmax" and routed_scale != 1:
        raise ValueError(f"routed_scale {routed_scale} under a softmax router")


def dropless_experts(x, router, w_gate_up, w_down, shared_gate_up,
                     shared_down, shared_gate, score_bias=None, *, first,
                     top_k, renormalize, rows, act="swiglu",
                     router_kind="softmax", routed_scale=1.0):
    """The whole expert layer on [T, h] tokens: (y, routed_slots,
    expert_rows), and under the sigmoid router the slots each router output
    took (``expert_load``). Routes over all of the router's experts by
    ``router_kind``: "softmax", or "sigmoid" with a ``score_bias``
    (``route_sigmoid_top_k``, the weights times ``routed_scale``); adds, for
    each token, its held experts' weighted outputs and the shared expert,
    gated by sigmoid(x ``shared_gate``) or, with ``shared_gate`` None, as it
    is; with ``shared_gate_up`` None there is no shared expert and nothing
    is added. ``act`` is every expert's activation, the shared one's too:
    "swiglu" (``w_gate_up`` and ``shared_gate_up`` gate | up) or "relu2" (up
    alone). What the absent experts would have added is left out."""
    from ..profiler import trace

    _check_router(router_kind, score_bias, routed_scale)
    tokens, count = x.shape[0], w_gate_up.shape[0]
    trace.emit("moe_route", site="dropless_experts", held=count,
               num_experts=router.shape[1], top_k=top_k, buffer_rows=rows,
               tokens=tokens,
               saved_first_product_bytes=rows * w_gate_up.shape[2] * 4,
               act=act, router=router_kind, routed_scale=routed_scale)
    with jax.named_scope("router"):
        logits = jnp.matmul(x, router, preferred_element_type=jnp.float32)
        if router_kind == "softmax":
            w, idx = route_top_k(logits, top_k, renormalize)
        else:
            w, idx = route_sigmoid_top_k(logits, top_k, renormalize,
                                         score_bias, routed_scale)
            load = expert_load(idx, router.shape[1])
        order, inverse, offsets = sort_held_slots(idx, first, count)
        pad = -(-order.shape[0] // rows) * rows - order.shape[0]
        tok = jnp.pad(order // np.int32(top_k), (0, pad))
        wgt = jnp.pad(_permute(w.reshape(-1), order, inverse), (0, pad))
        routed = offsets[-1]
    with jax.named_scope("experts"):
        y = held_experts_apply(x, wgt, w_gate_up, w_down, tok, offsets, rows,
                               act)
    if shared_gate_up is not None:
        with jax.named_scope("shared_expert"):
            shared = jnp.matmul(
                _activate(act, jnp.matmul(x, shared_gate_up)), shared_down)
            if shared_gate is not None:
                open_ = jax.nn.sigmoid(jnp.matmul(
                    x, shared_gate, preferred_element_type=jnp.float32))
                shared = (shared * open_).astype(x.dtype)
            y = y + shared
    out = (y, routed, _n_passes(offsets, rows) * np.int32(rows))
    return out if router_kind == "softmax" else out + (load,)


class DroplessExperts(Layer):
    """Sparse experts with a shared expert, dropless, for a chip that holds
    ``held = (first, count)`` of ``num_experts`` experts. What is computed
    is named by the model:

    ``act``     "swiglu": an expert is W_d^T (silu(W_g^T x) * W_u^T x);
                "relu2": W_d^T relu(W_u^T x)^2, no gate. The shared expert
                takes the same activation.
    ``router``  "softmax": the ``top_k`` largest of softmax(x W_r),
                renormalised with ``renormalize``; "sigmoid": s =
                sigmoid(x W_r), the experts the ``top_k`` largest of s +
                ``score_bias``, their weights the unbiased s, renormalised
                with ``renormalize`` and times ``routed_scale``. With a
                ``bias_update_rate``, each forward in training mode moves
                ``score_bias`` by the balancing rule
                (``balance_score_bias``) on the load of ALL router outputs
                over its tokens, after its own choice: in a compiled step
                the next step chooses with the moved bias.

    Parameters: ``router`` [h, num_experts] (the published width, whatever
    is held); for SwiGLU ``w_gate_up`` [count, h, 2 d] and
    ``shared_gate_up`` [h, 2 d_s] (gate | up), for relu^2 ``w_up`` [count,
    h, d] and ``shared_up`` [h, d_s]; ``w_down`` [count, d, h] (stacked
    leaves, not one array an expert), ``shared_down`` [d_s, h],
    ``shared_gate`` [h, 1] (none with ``shared_gate=False``: the shared
    expert is added ungated; with ``d_shared=0`` the model has no shared
    expert: none of the three is built and nothing is added). A sigmoid
    router keeps ``score_bias`` [num_experts], a float32 buffer that no
    gradient reaches and that ``to(dtype)`` leaves float32 (zero until the
    model's owner sets it). Two int32
    buffers ride a compiled step like a running statistic and hold, after
    each forward, ``routed_slots`` (slots that fell on held experts) and
    ``expert_rows`` (rows of the row buffer the grouped products were
    given: passes x the buffer's rows); one ``moe_route`` event is left in
    the flight recorder per trace."""

    def __init__(self, d_model, d_expert, num_experts, top_k, held=None,
                 d_shared=None, renormalize=True, weight_attr=None,
                 shared_gate=True, act="swiglu", router="softmax",
                 routed_scale=1.0, bias_update_rate=0.0):
        super().__init__()
        first, count = held if held is not None else (0, num_experts)
        if first < 0 or count < 1 or first + count > num_experts:
            raise ValueError(f"held {held} is no range of {num_experts}")
        if act not in ("swiglu", "relu2"):
            raise ValueError(f"act {act!r}")
        if bias_update_rate and router != "sigmoid":
            raise ValueError(f"bias_update_rate under router {router!r}")
        self.first, self.count = int(first), int(count)
        self.num_experts, self.top_k = num_experts, top_k
        self.renormalize, self.act, self.router_kind = renormalize, act, router
        self.routed_scale = float(routed_scale)
        self.bias_update_rate = float(bias_update_rate)
        d_shared = d_expert if d_shared is None else d_shared
        wide = 2 if act == "swiglu" else 1

        def make(*shape):
            return self.create_parameter(shape=list(shape), attr=weight_attr)

        self.router = make(d_model, num_experts)
        if act == "swiglu":
            self.w_gate_up = make(self.count, d_model, 2 * d_expert)
        else:
            self.w_up = make(self.count, d_model, d_expert)
        self.w_down = make(self.count, d_expert, d_model)
        shared_in = make(d_model, wide * d_shared) if d_shared else None
        if act == "swiglu":
            self.shared_gate_up = shared_in
        else:
            self.shared_up = shared_in
        self.shared_down = make(d_shared, d_model) if d_shared else None
        self.shared_gate = (make(d_model, 1) if shared_gate and d_shared
                            else None)
        if router == "sigmoid":
            self.register_buffer("score_bias", Tensor(
                np.zeros(num_experts, np.float32)), keep_dtype=True)
        else:
            self.score_bias = None
        _check_router(router, self.score_bias, routed_scale)
        self.register_buffer("routed_slots", Tensor(np.int32(0)))
        self.register_buffer("expert_rows", Tensor(np.int32(0)))

    def forward(self, x):
        shape = list(x.shape)
        flat = x.reshape([-1, shape[-1]])
        rows = row_buffer_rows(flat.shape[0], self.top_k, self.num_experts,
                               self.count)
        swiglu = self.act == "swiglu"
        y, routed, ran, *load = apply(
            dropless_experts, flat, self.router,
            self.w_gate_up if swiglu else self.w_up, self.w_down,
            self.shared_gate_up if swiglu else self.shared_up,
            self.shared_down, self.shared_gate, self.score_bias,
            first=self.first, top_k=self.top_k, renormalize=self.renormalize,
            rows=rows, act=self.act, router_kind=self.router_kind,
            routed_scale=self.routed_scale, op_name="dropless_experts")
        self.routed_slots._value = routed._value
        self.expert_rows._value = ran._value
        if self.bias_update_rate and self.training:
            self.score_bias._value = balance_score_bias(
                self.score_bias._value, load[0]._value,
                self.bias_update_rate)
        return y.reshape(shape)
