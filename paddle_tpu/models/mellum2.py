"""A sparse decoder of sliding-window and full attention layers (the Mellum 2
family, ``model_type`` ``mellum``): every layer is grouped-query softmax
attention followed by sparse SwiGLU experts with no shared expert; the
published ``layer_types`` repeat three ``sliding_attention`` layers and one
``full_attention`` layer.

Block, for hidden h:  h += W_o attention(norm1(h));  h += experts(norm2(h));
the norms are plain RMSNorm (a gain, not 1 + w); the head is untied.

  attention   q = x W_q, k = x W_k, v = x W_v, no bias, no per-head norm;
              rotary positions on every dim of a head (half-split pairing);
              softmax of q k^T / sqrt(head_dim) through the flash kernels,
              the KV heads shared by groups of query heads. What a layer's
              kind sets (``rope_parameters`` has a section a kind):
    sliding_attention  query i sees key j iff i - W < j <= i (W the
              ``sliding_window``, the ``transformers`` convention: W keys,
              its own included); the default rotary table.
    full_attention     causal; the YaRN table (``ops.nn_ops.rope_inv_freq``)
              and YaRN's attention factor, which the published form puts on
              cos and sin: here it scales the scores by its square instead
              (the same in exact arithmetic).
  experts     incubate.moe.DroplessExperts with ``d_shared=0``: this chip
              holds ``held_experts = (first, count)`` of ``num_experts``.

Each layer's attention runs under a scope named by its kind
(``sliding_attention`` / ``full_attention``, under ``layers.N/mixer``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax

from .. import nn
from ..core.dispatch import apply
from ..incubate.moe import DroplessExperts
from ..nn import functional as F
from ..nn import initializer as I
from ..ops import nn_ops as _nn
from ._hybrid import linear as _linear
from ._hybrid import residual_mixer, routed_load

SLIDING, FULL = "sliding_attention", "full_attention"
_YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
              "beta_slow")


def _published_rope():
    return {FULL: {"rope_type": "yarn", "rope_theta": 500000.0,
                   "factor": 16.0, "original_max_position_embeddings": 8192,
                   "beta_fast": 32.0, "beta_slow": 1.0,
                   "attention_factor": 1.2772588722239782},
            SLIDING: {"rope_type": "default", "rope_theta": 500000.0}}


@dataclass
class Mellum2Config:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    held_experts: Optional[Tuple[int, int]] = None  # (first, count); all
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    use_recompute: bool = False
    sliding_window: int = 1024
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 7
    rope_parameters: dict = field(default_factory=_published_rope)


def _norm(cfg):
    return nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)


def _turned(q, k, *, heads, kv_heads, head_dim, theta, yarn):
    """q [b, s, heads, d] and k [b, s, kv, d], split and turned by their
    positions with the layer's table."""
    b, s = q.shape[0], q.shape[1]
    inv_freq = _nn.rope_inv_freq(theta, head_dim, yarn and dict(yarn))
    return (_nn.rotary_embedding(q.reshape(b, s, heads, head_dim),
                                 rotary_dim=head_dim, inv_freq=inv_freq),
            _nn.rotary_embedding(k.reshape(b, s, kv_heads, head_dim),
                                 rotary_dim=head_dim, inv_freq=inv_freq))


class Mellum2Attention(nn.Layer):
    """Grouped-query attention of one kind: ``sliding_attention`` (a window
    of ``sliding_window`` keys, the default rotary table) or
    ``full_attention`` (causal, YaRN)."""

    def __init__(self, cfg: Mellum2Config, kind: str):
        super().__init__()
        self.cfg, self.kind = cfg, kind
        h, d = cfg.hidden_size, cfg.head_dim
        self.q_proj = _linear(cfg, h, cfg.num_attention_heads * d)
        self.k_proj = _linear(cfg, h, cfg.num_key_value_heads * d)
        self.v_proj = _linear(cfg, h, cfg.num_key_value_heads * d)
        self.o_proj = _linear(cfg, cfg.num_attention_heads * d, h)
        rope = cfg.rope_parameters[kind]
        self.theta = float(rope["rope_theta"])
        self.window = cfg.sliding_window if kind == SLIDING else None
        self.yarn = self.scale = None
        if rope["rope_type"] == "yarn":
            self.yarn = tuple((k, rope[k]) for k in _YARN_KEYS)
            self.scale = rope["attention_factor"] ** 2 / math.sqrt(d)

    def forward(self, x):
        cfg = self.cfg
        b, s = x.shape[0], x.shape[1]
        q, k = self.q_proj(x), self.k_proj(x)
        v = self.v_proj(x).reshape(
            [b, s, cfg.num_key_value_heads, cfg.head_dim])
        with jax.named_scope(self.kind):
            q, k = apply(
                _turned, q, k, heads=cfg.num_attention_heads,
                kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
                theta=self.theta, yarn=self.yarn, op_name="mellum2_rotary")
            attn = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, scale=self.scale, window=self.window)
        return self.o_proj(attn.reshape([b, s, -1]))


class Mellum2DecoderLayer(nn.Layer):
    def __init__(self, cfg: Mellum2Config, index: int):
        super().__init__()
        self.cfg = cfg
        self.norm1 = _norm(cfg)
        self.mixer = Mellum2Attention(cfg, cfg.layer_types[index])
        self.norm2 = _norm(cfg)
        self.experts = DroplessExperts(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, held=cfg.held_experts, d_shared=0,
            renormalize=cfg.norm_topk_prob,
            weight_attr=I.Normal(0.0, cfg.initializer_range))

    def forward(self, x):
        x = residual_mixer(x, self.norm1, self.mixer,
                           recompute=self.cfg.use_recompute)
        return x + self.experts(self.norm2(x))


class Mellum2Model(nn.Layer):
    def __init__(self, cfg: Mellum2Config):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=I.Normal(0.0, cfg.initializer_range))
        self.layers = nn.LayerList([Mellum2DecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = _norm(cfg)

    def forward(self, input_ids):
        h = self.embed_tokens(input_ids)
        for layer in self.layers:
            h = layer(h)
        return self.norm(h)


class Mellum2ForCausalLM(nn.Layer):
    """Trunk + untied head over the held rows of the vocabulary."""

    def __init__(self, cfg: Mellum2Config):
        super().__init__()
        self.cfg = cfg
        self.model = Mellum2Model(cfg)
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
