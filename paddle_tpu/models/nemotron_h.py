"""A hybrid decoder whose layers are each ONE part (the Nemotron-H family,
``model_type`` ``nemotron_h``): ``hybrid_override_pattern`` gives layer i's
kind, ``M`` a Mamba-2 mixer, ``E`` sparse experts, ``*`` attention, and
every layer is the same residual branch:

    h = E[ids]
    h += part_i(rmsnorm(h))                       (plain gains, eps 1e-5)
    logits = rmsnorm(h) W_head                    (untied)

  M   (x B C | z) = u W_xbcz, dt = u W_dt; (x, B, C) <- silu(causal
      depthwise conv, kernel 4, + bias); dt <- softplus(dt + dt_bias); the
      Mamba-2 scan S_t = exp(-exp(A_log) dt_t) S_(t-1) + dt_t x_t B_t^T, y_t
      = S_t C_t + D x_t per head, head h reading B / C group h // (heads /
      n_groups) (ops/state_space.py); g = y * silu(z), then g / rms(g) *
      gain with ONE statistic for each group of intermediate / n_groups
      channels; W_out.
  E   incubate.moe.DroplessExperts(act="relu2", router="sigmoid"): s =
      sigmoid(u W_r) over all ``n_routed_experts``, the ``num_experts_per_tok``
      largest of s + ``e_score_correction_bias`` chosen, their weights the
      unbiased s renormalised and times ``routed_scaling_factor``; an expert
      is W_d relu(W_u u)^2; the shared expert (``moe_shared_expert_
      intermediate_size`` wide) is relu^2 and ungated. ``n_group`` and
      ``topk_group`` 1 make the published group-limited choice a no-op.
  *   q, k, v, o without bias; causal softmax(q k^T / sqrt(head_dim))
      through the flash kernels, the KV heads shared by groups of query
      heads; no positional term (the published attention applies no rotary
      embedding: ``rope_theta`` is read by nothing).

A chip of an expert-parallel deployment holds ``experts_held = (first,
count)`` of the routed experts and ``vocab_held`` of the vocabulary's rows
(ids, logits and loss are the slice's own); the mixers, the router and the
shared expert are whole. What the absent experts would add is left out. The
correction bias is a float32 buffer of the expert layer, which no gradient
reaches; with ``router_bias_update_rate`` (a training recipe's, not the
published config's) each training forward moves it by the
auxiliary-loss-free balancing rule, a sign step toward even load over all
``n_routed_experts`` (``incubate.moe.balance_score_bias``).

Column orders (``in_proj_xbcz`` as x | B | C | z with ``in_proj_dt`` apart,
where the public ``in_proj`` is z | x B C | dt) are this file's own: under
random weights a relabelling.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

from .. import nn
from ..incubate.moe import DroplessExperts
from ..nn import initializer as I
from ._hybrid import Mamba2Mixer, NoPEAttention, linear, residual_mixer
from ._hybrid import routed_load

Held = Optional[Tuple[int, int]]  # (first, count); None: all
KINDS = {"M": "mamba", "E": "experts", "*": "attention"}
_PATTERN = ("MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")


@dataclass
class NemotronHConfig:
    # the published keys: the whole deployment's counts
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = _PATTERN
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    layer_norm_epsilon: float = 1e-5
    # what this chip holds of them
    experts_held: Held = None
    vocab_held: Held = None
    initializer_range: float = 0.02
    use_recompute: bool = False
    # the training recipe's: 0 holds the correction bias where it is set
    router_bias_update_rate: float = 0.0

    def kind(self, layer: int) -> str:
        """'mamba', 'experts' or 'attention'."""
        return KINDS[self.hybrid_override_pattern[layer]]

    def held(self, what: str) -> Tuple[int, int]:
        """(first, count) of ``what`` held here, checked against its count."""
        total = {"experts": self.n_routed_experts,
                 "vocab": self.vocab_size}[what]
        first, count = getattr(self, what + "_held") or (0, total)
        if first < 0 or count < 1 or first + count > total:
            raise ValueError(f"{what}_held {(first, count)} of {total}")
        return first, count


class NemotronHDecoderLayer(nn.Layer):
    """h + part(rmsnorm(h)): ``mixer`` (Mamba-2 or attention) or
    ``experts``; the mixers' branch is made again in the backward with
    ``use_recompute``."""

    def __init__(self, cfg: NemotronHConfig, index: int):
        super().__init__()
        self.cfg, self.kind = cfg, cfg.kind(index)
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        if self.kind == "mamba":
            inner = cfg.mamba_num_heads * cfg.mamba_head_dim
            self.mixer = Mamba2Mixer(
                cfg, heads=cfg.mamba_num_heads,
                heads_published=cfg.mamba_num_heads, p=cfg.mamba_head_dim,
                n=cfg.ssm_state_size, groups=cfg.n_groups,
                conv=cfg.conv_kernel, chunk=cfg.chunk_size,
                eps=cfg.layer_norm_epsilon, norm_group=inner // cfg.n_groups)
        elif self.kind == "attention":
            self.mixer = NoPEAttention(
                cfg, cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim, cfg.head_dim ** -0.5)
        else:
            self.experts = DroplessExperts(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                held=cfg.held("experts"),
                d_shared=cfg.moe_shared_expert_intermediate_size,
                renormalize=cfg.norm_topk_prob,
                weight_attr=I.Normal(0.0, cfg.initializer_range),
                shared_gate=False, act="relu2", router="sigmoid",
                routed_scale=cfg.routed_scaling_factor,
                bias_update_rate=cfg.router_bias_update_rate)

    def forward(self, x):
        if self.kind == "experts":
            return x + self.experts(self.norm(x))
        return residual_mixer(x, self.norm, self.mixer,
                              recompute=self.cfg.use_recompute)


class NemotronHModel(nn.Layer):
    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        if len(cfg.hybrid_override_pattern) < cfg.num_hidden_layers:
            raise ValueError(
                f"hybrid_override_pattern names "
                f"{len(cfg.hybrid_override_pattern)} layers of "
                f"{cfg.num_hidden_layers}")
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.held("vocab")[1], cfg.hidden_size,
            weight_attr=I.Normal(0.0, cfg.initializer_range))
        self.layers = nn.LayerList([NemotronHDecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm_f = nn.RMSNorm(cfg.hidden_size,
                                 epsilon=cfg.layer_norm_epsilon)

    def forward(self, input_ids):
        h = self.embed_tokens(input_ids)
        for layer in self.layers:
            h = layer(h)
        return self.norm_f(h)


class NemotronHForCausalLM(nn.Layer):
    """Trunk + untied head over the held rows of the vocabulary (ids are the
    slice's own, from 0)."""

    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        self.model = NemotronHModel(cfg)
        self.lm_head = linear(cfg, cfg.hidden_size, cfg.held("vocab")[1])

    def forward(self, input_ids):
        # the trunk's pieces called here, as models/gpt.py does, so that a
        # layer's scope reads layers.2/mixer in the profiler's by-layer view
        trunk = self.model
        h = trunk.embed_tokens(input_ids)
        for layer in trunk.layers:
            h = layer(h)
        return self.lm_head(trunk.norm_f(h))

    def routed_load(self):
        """[(layer, routed_slots, expert_rows)] of the last forward, for
        the expert layers."""
        return routed_load(self.model.layers)
