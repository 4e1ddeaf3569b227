"""A sparse all-attention decoder trained by block diffusion (the SDAR-MoE
family, ``model_type`` ``sdar_moe``): every layer is grouped-query softmax
attention followed by sparse SwiGLU experts with no shared expert, and the
training objective denoises blocks of masked tokens instead of predicting
the next one.

Block, for hidden h:  h += W_o attention(norm1(h));  h += experts(norm2(h));
the norms are plain RMSNorm (a gain, not 1 + w); the head is untied.

  attention   q = x W_q, k = x W_k, v = x W_v, no bias; RMSNorm with a gain
              over each head of q and of k; rotary positions on every dim of
              a head (half-split pairing); softmax attention through the
              flash kernels, the KV heads shared by groups of query heads.
  experts     incubate.moe.DroplessExperts with ``d_shared=0``: this chip
              holds ``held_experts = (first, count)`` of ``num_experts``.

Training (Arriola et al., "Block Diffusion", ICLR 2025, the masked
objective): a sequence x0 of L tokens is cut into blocks of ``block_length``;
the caller masks tokens (each block at a rate t of its own) and the model
runs ONCE over a stream of 2 L positions: the clean tokens, then the noised
ones (masked tokens replaced by ``mask_token_id``). A position's rotary
position is its place inside its own half. Attention is under the two-stream
block mask (``ops/pallas/flash_attention.py``, ``block_mask``): a clean
position sees the clean blocks up to its own, a noised position the clean
blocks before its own and the noised positions of its own block. The final
norm and the head are applied to the noised half only; the loss
(``BlockDiffusionCriterion``) is the cross entropy of x0 at the masked
positions, each weighted by 1 / t, over batch x L; nothing is shifted:
position i predicts token i.

The decoder's layers take the training stream as their input; generation (a
step that yields a block) belongs with the serving engine and is not built.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dispatch import apply
from ..core.tensor import Tensor
from ..incubate.moe import DroplessExperts
from ..nn import functional as F
from ..nn import initializer as I
from ..ops import nn_ops as _nn
from ._hybrid import linear as _linear
from ._hybrid import residual_mixer, routed_load


@dataclass
class SDARMoEConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    held_experts: Optional[Tuple[int, int]] = None  # (first, count); all
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    use_recompute: bool = False
    block_length: int = 4
    mask_token_id: Optional[int] = None  # the vocabulary's last row

    @property
    def mask_id(self) -> int:
        return (self.vocab_size - 1 if self.mask_token_id is None
                else self.mask_token_id)


def _norm(cfg, width=None):
    return nn.RMSNorm(width or cfg.hidden_size, epsilon=cfg.rms_norm_eps)


def _heads_of_stream(q, k, q_norm, k_norm, *, heads, kv_heads, head_dim,
                     theta, eps):
    """q [b, 2 L, heads, d] and k [b, 2 L, kv, d]: split, normalised per
    head, turned by their positions, which run 0 .. L - 1 in each half."""
    b, s = q.shape[0], q.shape[1]
    q = _nn.rms_norm(q.reshape(b, s, heads, head_dim), q_norm, epsilon=eps)
    k = _nn.rms_norm(k.reshape(b, s, kv_heads, head_dim), k_norm, epsilon=eps)
    positions = jnp.tile(jnp.arange(s // 2, dtype=jnp.float32), 2)
    return (_nn.rotary_embedding(q, positions, rotary_dim=head_dim,
                                 theta=theta),
            _nn.rotary_embedding(k, positions, rotary_dim=head_dim,
                                 theta=theta))


class SDARMoEAttention(nn.Layer):
    """Attention over the training stream [b, 2 L, h] (clean half, then
    noised half) under the two-stream block mask."""

    def __init__(self, cfg: SDARMoEConfig):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        self.q_proj = _linear(cfg, h, cfg.num_attention_heads * d)
        self.k_proj = _linear(cfg, h, cfg.num_key_value_heads * d)
        self.v_proj = _linear(cfg, h, cfg.num_key_value_heads * d)
        self.o_proj = _linear(cfg, cfg.num_attention_heads * d, h)
        self.q_norm = _norm(cfg, d)
        self.k_norm = _norm(cfg, d)

    def forward(self, x):
        cfg = self.cfg
        b, s = x.shape[0], x.shape[1]
        q, k = apply(
            _heads_of_stream, self.q_proj(x), self.k_proj(x),
            self.q_norm.weight, self.k_norm.weight,
            heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
            head_dim=cfg.head_dim, theta=float(cfg.rope_theta),
            eps=cfg.rms_norm_eps, op_name="sdar_moe_attention_heads")
        v = self.v_proj(x).reshape(
            [b, s, cfg.num_key_value_heads, cfg.head_dim])
        attn = F.scaled_dot_product_attention(
            q, k, v, block_mask=(s // 2, cfg.block_length))
        return self.o_proj(attn.reshape([b, s, -1]))


class SDARMoEDecoderLayer(nn.Layer):
    def __init__(self, cfg: SDARMoEConfig):
        super().__init__()
        self.cfg = cfg
        self.norm1 = _norm(cfg)
        self.mixer = SDARMoEAttention(cfg)
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


class SDARMoEModel(nn.Layer):
    """Embedding, the decoder layers and the final norm, over a stream of
    token ids [b, 2 L]."""

    def __init__(self, cfg: SDARMoEConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=I.Normal(0.0, cfg.initializer_range))
        self.layers = nn.LayerList([SDARMoEDecoderLayer(cfg)
                                    for _ in range(cfg.num_hidden_layers)])
        self.norm = _norm(cfg)

    def forward(self, stream_ids):
        h = self.embed_tokens(stream_ids)
        for layer in self.layers:
            h = layer(h)
        return self.norm(h)


def _noised_stream(ids, masked, *, mask_id):
    """(the stream's ids [b, 2 L]: x0, then xt = x0 with the masked tokens
    replaced by the mask id; how many positions are masked)."""
    with jax.named_scope("diffusion_noise"):
        masked = masked != 0
        noised = jnp.where(masked, np.int32(mask_id), ids)
        return (jnp.concatenate([ids, noised], axis=1),
                masked.sum(dtype=jnp.int32))


class SDARMoEForBlockDiffusion(nn.Layer):
    """The block-diffusion front: ``forward(input_ids, masked)`` with the
    clean ids [b, L] and the mask draws [b, L] (non-zero where a token is
    masked) gives the logits of the NOISED half [b, L, vocabulary]. The
    int32 buffer ``loss_positions`` rides a compiled step like a running
    statistic and holds, after each forward, how many positions were masked
    (the positions the loss is taken at)."""

    def __init__(self, cfg: SDARMoEConfig):
        super().__init__()
        self.cfg = cfg
        self.model = SDARMoEModel(cfg)
        self.lm_head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)
        self.register_buffer("loss_positions", Tensor(np.int32(0)))

    def forward(self, input_ids, masked):
        stream, n_masked = apply(_noised_stream, input_ids, masked,
                                 mask_id=self.cfg.mask_id,
                                 differentiable=False,
                                 op_name="diffusion_noise")
        self.loss_positions._value = n_masked._value
        # the trunk's pieces called here, as models/gpt.py does, so that a
        # layer's scope reads layers.2/mixer in the profiler's by-layer view
        trunk = self.model
        h = trunk.embed_tokens(stream)
        for layer in trunk.layers:
            h = layer(h)
        return self.lm_head(trunk.norm(h[:, input_ids.shape[1]:]))

    def routed_load(self):
        """[(layer, routed_slots, expert_rows)] of the last forward."""
        return routed_load(self.model.layers)


class BlockDiffusionCriterion(nn.Layer):
    """sum over masked positions of weight x CE(logits, x0) / (batch x L).
    ``targets`` [b, L, 2] float32 is the step's last input: x0's ids
    (whole numbers, exact in float32) and the weights, 1 / t of a masked
    position's block and 0 elsewhere."""

    def forward(self, logits, targets):
        labels = targets[:, :, 0].astype("int32")
        loss = F.cross_entropy(logits, labels, reduction="none")
        return (loss * targets[:, :, 1]).sum() / float(
            targets.shape[0] * targets.shape[1])
