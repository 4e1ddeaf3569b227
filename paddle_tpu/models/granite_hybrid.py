"""A state-space / attention hybrid decoder with sparse experts (the
Granite 4.0-H family, ``model_type`` ``granitemoehybrid``): most layers mix
tokens with a Mamba-2 state-space scan, those ``layer_types`` names
``"attention"`` with grouped-query softmax attention WITHOUT positions, and
every mixer is followed by sparse SwiGLU experts beside an ungated shared
expert. Four scalar multipliers sit on the residual stream's ends:

    h = embedding_multiplier * E[ids]
    h += residual_multiplier * mixer_i(rmsnorm(h))
    h += residual_multiplier * (experts(u) + shared(u)),  u = rmsnorm(h)
    logits = rmsnorm(h) E^T / logits_scaling        (tied E, plain gains)

  state space   (x B C | z) = u W_xbcz, dt = u W_dt; (x, B, C) <- silu(causal
                depthwise conv, kernel 4, + bias); dt <- softplus(dt +
                dt_bias); S_t = exp(-exp(A_log) dt_t) S_(t-1) + dt_t x_t
                B_t^T, y_t = S_t C_t + D x_t per head (ops/state_space.py);
                rmsnorm(y * silu(z)) with ONE statistic over the channels
                held here; W_out.
  attention     q, k, v, o without bias; causal softmax(q k^T *
                attention_multiplier) through the flash kernel, the KV heads
                shared by groups of query heads; no positional term.
  experts       incubate.moe.DroplessExperts(shared_gate=False).

A chip of a deployment that shares each layer holds ``(first, count)`` of a
mixer's heads (``mamba_heads_held``, ``attention_heads_held``: query heads in
whole KV groups), of the experts and of the vocabulary, beside the published
counts. A mixer that holds a share computes its own heads' part of the
out-projection (o-projection) and hands that PARTIAL sum on: over the chips
of a group the parts add up to the whole layer's, with one exception. The
gated norm's statistic is taken over the channels held here, where the whole
layer's is over all heads: the exchange that would make it whole is the one
the layer runs without. No code stands in for the absent chips.

Column orders (``in_proj_xbcz`` as x | B | C | z with ``in_proj_dt`` a
matrix of its own, where the public implementation has z | x B C | dt in one)
are this file's own: under random weights a relabelling.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax

import paddle_tpu as paddle

from .. import nn
from ..incubate.moe import DroplessExperts
from ..nn import initializer as I
from ._hybrid import Mamba2Mixer, NoPEAttention, residual_mixer, routed_load

Held = Optional[Tuple[int, int]]  # (first, count); None: all


@dataclass
class GraniteHybridConfig:
    # the published keys: the whole deployment's counts
    vocab_size: int = 100352
    hidden_size: int = 4096
    num_hidden_layers: int = 40
    layer_types: Optional[Tuple[str, ...]] = None  # default: attention at 5
    #                                                of every 10
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    intermediate_size: int = 768  # of one routed expert
    shared_intermediate_size: int = 1536
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    # what this chip holds of them
    mamba_heads_held: Held = None
    attention_heads_held: Held = None  # query heads, whole KV groups
    experts_held: Held = None
    vocab_held: Held = None
    initializer_range: float = 0.02
    use_recompute: bool = False

    def kind(self, layer: int) -> str:
        if self.layer_types is not None:
            return self.layer_types[layer]
        return "attention" if layer % 10 == 5 else "mamba"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def held(self, what: str) -> Tuple[int, int]:
        """(first, count) of ``what`` held here, checked against its count."""
        total = {"mamba_heads": self.mamba_n_heads,
                 "attention_heads": self.num_attention_heads,
                 "experts": self.num_local_experts,
                 "vocab": self.vocab_size}[what]
        first, count = getattr(self, what + "_held") or (0, total)
        if first < 0 or count < 1 or first + count > total:
            raise ValueError(f"{what}_held {(first, count)} of {total}")
        return first, count


def _norm(cfg, width=None):
    return nn.RMSNorm(width or cfg.hidden_size, epsilon=cfg.rms_norm_eps)


class GraniteHybridAttention(NoPEAttention):
    """Query heads held in whole KV groups, the scores times
    ``attention_multiplier``."""

    op_name = "granite_attention_heads"

    def __init__(self, cfg: GraniteHybridConfig):
        group = cfg.num_attention_heads // cfg.num_key_value_heads
        first, heads = cfg.held("attention_heads")
        if first % group or heads % group:
            raise ValueError(
                f"attention_heads_held {(first, heads)}: not whole "
                f"groups of {group} query heads on one KV head")
        super().__init__(cfg, heads, heads // group, cfg.head_dim,
                         cfg.attention_multiplier, cfg.num_attention_heads)


class GraniteHybridMamba(Mamba2Mixer):
    """The held state-space heads; one statistic over their channels."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__(
            cfg, heads=cfg.held("mamba_heads")[1],
            heads_published=cfg.mamba_n_heads, p=cfg.mamba_d_head,
            n=cfg.mamba_d_state, groups=cfg.mamba_n_groups,
            conv=cfg.mamba_d_conv, chunk=cfg.mamba_chunk_size,
            eps=cfg.rms_norm_eps)


# ---------------------------------------------------------------------------
# block, trunk, head
# ---------------------------------------------------------------------------
class GraniteHybridDecoderLayer(nn.Layer):
    def __init__(self, cfg: GraniteHybridConfig, index: int):
        super().__init__()
        self.cfg = cfg
        self.norm1 = _norm(cfg)
        self.mixer = (GraniteHybridAttention(cfg)
                      if cfg.kind(index) == "attention"
                      else GraniteHybridMamba(cfg))
        self.norm2 = _norm(cfg)
        self.experts = DroplessExperts(
            cfg.hidden_size, cfg.intermediate_size, cfg.num_local_experts,
            cfg.num_experts_per_tok, held=cfg.held("experts"),
            d_shared=cfg.shared_intermediate_size, renormalize=True,
            weight_attr=I.Normal(0.0, cfg.initializer_range),
            shared_gate=False)

    def forward(self, x):
        cfg = self.cfg
        x = residual_mixer(x, self.norm1, self.mixer,
                           recompute=cfg.use_recompute,
                           multiplier=cfg.residual_multiplier)
        return x + self.experts(self.norm2(x)) * cfg.residual_multiplier


class GraniteHybridModel(nn.Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.held("vocab")[1], cfg.hidden_size,
            weight_attr=I.Normal(0.0, cfg.initializer_range))
        self.layers = nn.LayerList([GraniteHybridDecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = _norm(cfg)

    def forward(self, input_ids):
        h = self.embed_tokens(input_ids) * self.cfg.embedding_multiplier
        for layer in self.layers:
            h = layer(h)
        return self.norm(h)


class GraniteHybridForCausalLM(nn.Layer):
    """Trunk + tied head over the held rows of the vocabulary (ids are the
    slice's own, from 0)."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.model = GraniteHybridModel(cfg)

    def forward(self, input_ids):
        # the trunk's pieces called here, as models/gpt.py does, so that a
        # layer's scope reads layers.2/mixer in the profiler's by-layer view
        trunk, cfg = self.model, self.cfg
        h = trunk.embed_tokens(input_ids) * cfg.embedding_multiplier
        for layer in trunk.layers:
            h = layer(h)
        with jax.named_scope("lm_head"):
            logits = paddle.matmul(trunk.norm(h), trunk.embed_tokens.weight,
                                   transpose_y=True)
            return logits * (1.0 / cfg.logits_scaling)

    def routed_load(self):
        """[(layer, routed_slots, expert_rows)] of the last forward."""
        return routed_load(self.model.layers)
