"""A present-day sparse hybrid decoder (the Qwen3-Next family): most layers
mix tokens with a gated delta rule (a recurrent [d_k, d_v] state per head),
every ``full_attention_interval``-th with gated grouped-query softmax
attention, and every layer is followed by sparse SwiGLU experts with a gated
shared expert.

Block, for hidden h:  h += mixer(norm1(h));  h += experts(norm2(h)); both
norms and the final one are zero-centred RMSNorm (gain 1 + w); the head is
untied. Layer i is full attention where (i + 1) % interval == 0.

  linear attention   (q, k, v, z) = split(x W_qkvz), (b, a) = split(x W_ba);
                     (q, k, v) <- silu(causal depthwise conv, kernel 4);
                     q <- q / |q| / sqrt(d_k), k <- k / |k| per head; the
                     gated delta rule with g = -exp(A_log) softplus(a +
                     dt_bias), beta = sigmoid(b); per head o / rms(o) * w *
                     silu(z); W_out. (ops/linear_attention.py)
  full attention     (q, gate) = split(x W_q) per head, k = x W_k, v = x W_v;
                     zero-centred RMSNorm over each head of q and k; rotary
                     positions on the first ``rotary_dim`` dims; causal
                     softmax attention through the flash kernel, the KV heads
                     shared by groups of query heads; (attn * sigmoid(gate))
                     W_o.
  experts            incubate.moe.DroplessExperts: this chip holds
                     ``held_experts = (first, count)`` of ``num_experts``.

Column orders (``W_qkvz`` as q | k | v | z, ``W_q`` as [head, (q, gate)])
are this file's own; under random weights they are a relabelling of the
public implementation's. No multi-token-prediction module and no auxiliary
load-balancing loss are built.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dispatch import apply
from ..incubate.moe import DroplessExperts
from ..nn import functional as F
from ..nn import initializer as I
from ..ops import nn_ops as _nn
from ._hybrid import linear as _linear
from ._hybrid import residual_mixer, routed_load


@dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    # full attention
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # linear attention
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # experts
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    held_experts: Optional[Tuple[int, int]] = None  # (first, count); all
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    use_recompute: bool = False

    def is_full_attention(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0


def _norm(cfg, width=None):
    return nn.RMSNorm(width or cfg.hidden_size, epsilon=cfg.rms_norm_eps,
                      zero_centered=True)


# ---------------------------------------------------------------------------
# full attention
# ---------------------------------------------------------------------------
def _heads_for_attention(q_gate, k, q_norm, k_norm, *, heads, kv_heads,
                         head_dim, rotary_dim, theta, eps):
    """q [b, s, heads, d], its gate [b, s, heads * d] and k [b, s, kv, d]:
    split, normalised per head, turned by their positions."""
    b, s = q_gate.shape[0], q_gate.shape[1]
    q_gate = q_gate.reshape(b, s, heads, 2 * head_dim)
    q, gate = q_gate[..., :head_dim], q_gate[..., head_dim:]
    k = k.reshape(b, s, kv_heads, head_dim)
    q = _nn.rms_norm(q, q_norm, epsilon=eps, zero_centered=True)
    k = _nn.rms_norm(k, k_norm, epsilon=eps, zero_centered=True)
    q = _nn.rotary_embedding(q, rotary_dim=rotary_dim, theta=theta)
    k = _nn.rotary_embedding(k, rotary_dim=rotary_dim, theta=theta)
    return q, gate.reshape(b, s, heads * head_dim), k


def _gated(attn, gate):
    b, s = attn.shape[0], attn.shape[1]
    return attn.reshape(b, s, -1) * jax.nn.sigmoid(
        gate.astype(jnp.float32)).astype(attn.dtype)


class Qwen3NextAttention(nn.Layer):
    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        self.q_proj = _linear(cfg, h, cfg.num_attention_heads * d * 2)
        self.k_proj = _linear(cfg, h, cfg.num_key_value_heads * d)
        self.v_proj = _linear(cfg, h, cfg.num_key_value_heads * d)
        self.o_proj = _linear(cfg, cfg.num_attention_heads * d, h)
        self.q_norm = _norm(cfg, d)
        self.k_norm = _norm(cfg, d)

    def forward(self, x):
        cfg = self.cfg
        b, s = x.shape[0], x.shape[1]
        q, gate, k = apply(
            _heads_for_attention, self.q_proj(x), self.k_proj(x),
            self.q_norm.weight, self.k_norm.weight,
            heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
            head_dim=cfg.head_dim,
            rotary_dim=int(cfg.head_dim * cfg.partial_rotary_factor),
            theta=float(cfg.rope_theta), eps=cfg.rms_norm_eps,
            op_name="qwen3_next_attention_heads")
        v = self.v_proj(x).reshape(
            [b, s, cfg.num_key_value_heads, cfg.head_dim])
        attn = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self.o_proj(apply(_gated, attn, gate, op_name="attention_gate"))


# ---------------------------------------------------------------------------
# linear attention
# ---------------------------------------------------------------------------
def _gated_delta_mixer(qkvz, ba, conv_w, a_log, dt_bias, norm_w, *, hk, hv,
                       dk, dv, eps):
    """From the two input projections to the gated, normalised output of the
    rule, [b, s, hv * dv]."""
    # imported here: the module brings Pallas in, which costs every program
    # that imports paddle_tpu.models a second and a half at start-up
    from ..ops import linear_attention as _la

    b, s = qkvz.shape[0], qkvz.shape[1]
    n_qk, n_v = hk * dk, hv * dv
    with jax.named_scope("short_conv"):
        # over qkvz's first 2 n_qk + n_v lanes, read where they lie; q, k
        # and v each written where the rule reads it
        q, k, v = _la.short_conv_silu(qkvz, conv_w, (n_qk, n_qk, n_v))
    with jax.named_scope("gated_delta_rule"):
        # q and k are normalised (l2, a head's row) and q scaled by
        # d_k^-0.5 inside the rule's kernels
        q, k = q.reshape(b, s, hk, dk), k.reshape(b, s, hk, dk)
        v = v.reshape(b, s, hv, dv)
        g, beta = _la.decay_and_beta(ba[..., hv:], ba[..., :hv], a_log,
                                     dt_bias)
        o = _la.gated_delta_rule(q, k, v, g, beta)
    with jax.named_scope("gated_norm"):
        # the gate z is qkvz's last n_v lanes
        return _la.gated_rms_norm(o.reshape(b, s, n_v), qkvz, norm_w,
                                  epsilon=eps)


class Qwen3NextGatedDeltaNet(nn.Layer):
    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        n_qk = hk * cfg.linear_key_head_dim
        n_v = hv * cfg.linear_value_head_dim
        self.in_proj_qkvz = _linear(cfg, h, 2 * n_qk + 2 * n_v)
        self.in_proj_ba = _linear(cfg, h, 2 * hv)
        self.out_proj = _linear(cfg, n_v, h)
        self.conv_weight = self.create_parameter(
            shape=[2 * n_qk + n_v, cfg.linear_conv_kernel_dim],
            default_initializer=I.Normal(0.0, cfg.initializer_range))
        # the public init: A_log = log U(0, 16), dt_bias = 1, gain = 1
        self.A_log = self.create_parameter(
            shape=[hv], default_initializer=I.Assign(np.log(
                np.random.default_rng(0).uniform(1e-3, 16.0, hv))))
        self.dt_bias = self.create_parameter(
            shape=[hv], default_initializer=I.Constant(1.0))
        self.norm_weight = self.create_parameter(
            shape=[cfg.linear_value_head_dim],
            default_initializer=I.Constant(1.0))

    def forward(self, x):
        cfg = self.cfg
        y = apply(
            _gated_delta_mixer, self.in_proj_qkvz(x), self.in_proj_ba(x),
            self.conv_weight, self.A_log, self.dt_bias, self.norm_weight,
            hk=cfg.linear_num_key_heads, hv=cfg.linear_num_value_heads,
            dk=cfg.linear_key_head_dim, dv=cfg.linear_value_head_dim,
            eps=cfg.rms_norm_eps,
            op_name="gated_delta_mixer")
        return self.out_proj(y)


# ---------------------------------------------------------------------------
# block, trunk, head
# ---------------------------------------------------------------------------
class Qwen3NextDecoderLayer(nn.Layer):
    def __init__(self, cfg: Qwen3NextConfig, index: int):
        super().__init__()
        self.cfg = cfg
        self.norm1 = _norm(cfg)
        self.mixer = (Qwen3NextAttention(cfg) if cfg.is_full_attention(index)
                      else Qwen3NextGatedDeltaNet(cfg))
        self.norm2 = _norm(cfg)
        self.experts = DroplessExperts(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, held=cfg.held_experts,
            d_shared=cfg.shared_expert_intermediate_size,
            renormalize=cfg.norm_topk_prob,
            weight_attr=I.Normal(0.0, cfg.initializer_range))

    def forward(self, x):
        x = residual_mixer(x, self.norm1, self.mixer,
                           recompute=self.cfg.use_recompute)
        return x + self.experts(self.norm2(x))


class Qwen3NextModel(nn.Layer):
    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=I.Normal(0.0, cfg.initializer_range))
        self.layers = nn.LayerList([Qwen3NextDecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = _norm(cfg)

    def forward(self, input_ids):
        h = self.embed_tokens(input_ids)
        for layer in self.layers:
            h = layer(h)
        return self.norm(h)


class Qwen3NextForCausalLM(nn.Layer):
    """Trunk + untied head over the held rows of the vocabulary."""

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.cfg = cfg
        self.model = Qwen3NextModel(cfg)
        self.lm_head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)

    def forward(self, input_ids):
        # the trunk's pieces called here, as models/gpt.py does, so that a
        # layer's scope reads layers.2/mixer in the profiler's by-layer view
        trunk = self.model
        h = trunk.embed_tokens(input_ids)
        for layer in trunk.layers:
            h = layer(h)
        return self.lm_head(trunk.norm(h))

    def routed_load(self):
        """[(layer, routed_slots, expert_rows)] of the last forward."""
        return routed_load(self.model.layers)
