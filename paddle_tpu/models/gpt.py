"""GPT model family — the flagship decoder-only LM.

Reference analogue: the GPT configs the reference's fleet stack trains
(BASELINE config 4: GPT-2 345M hybrid TP+PP) — model code lives in
PaddleNLP upstream; rebuilt here TPU-first:
  - attention/MLP built from fleet.meta_parallel TP layers (Column/Row
    parallel with mp sharding specs — mp_layers.py analogues);
  - sequence parallelism via `sep`-axis sharding constraints on the token
    axis (capability gap in the reference — SURVEY.md §5 long-context);
  - causal attention through ops.nn_ops.scaled_dot_product_attention (XLA
    flash-pattern fusion; Pallas kernel in ops/pallas for long sequences);
  - weight-tied LM head (SharedLayerDesc semantics) with vocab-parallel
    cross entropy.

Everything is shape-static and scan-friendly: one compiled step trains it
under any mesh (dp / mp / sharding / sep) via fleet.distributed_train_step.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax

import paddle_tpu as paddle

from .. import nn
from ..nn import functional as F
from ..nn import initializer as I
from ..distributed.fleet.meta_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..parallel.sharding import with_sharding_constraint


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_hidden_size: Optional[int] = None
    max_seq_len: int = 1024
    dropout: float = 0.1
    attn_dropout: float = 0.1
    initializer_range: float = 0.02
    sequence_parallel: bool = False
    # how seq-sharded attention is computed when sequence_parallel and the
    # sep axis > 1: "gspmd" (compiler-inserted gathers), "ring" (ppermute KV
    # rotation — O(S/P) memory, the long-context path), "ulysses" (alltoall
    # heads<->seq). Reference has none of these (SURVEY §5 gap-fill).
    sequence_parallel_mode: str = "gspmd"
    use_recompute: bool = False
    dtype: str = "float32"

    def __post_init__(self):
        if self.ffn_hidden_size is None:
            self.ffn_hidden_size = 4 * self.hidden_size


def _sp(x, cfg, *spec):
    """Activation sharding hint; batch on dp(+sharding), seq on sep."""
    return with_sharding_constraint(x, *spec)


class CacheOverflow(ValueError):
    """Structured KV-cache overflow: a generation step would write past the
    cache capacity. A REQUEST-level verdict, not a run-killer — the serving
    scheduler (paddle.serving) catches it and answers the offending request
    with an error response while the rest of the batch keeps decoding.
    Subclasses ValueError so pre-existing callers that caught the old
    ValueError keep working."""

    def __init__(self, need: int, capacity: int, detail: str = ""):
        self.need = int(need)
        self.capacity = int(capacity)
        suffix = f" ({detail})" if detail else ""
        super().__init__(
            f"KV cache overflow: need {need} positions > capacity "
            f"{capacity}{suffix}"
        )


def convert_legacy_qkv_state_dict(state_dict, num_heads: int):
    """One-time converter for checkpoints saved before the fused-qkv layout
    switched from 3-major ([h, 3, H, hd] over the output dim) to heads-major
    ([h, H, 3, hd], Megatron-style — see GPTAttention.forward). Old
    checkpoints LOAD WITHOUT ERROR but silently permute q/k/v; run them
    through this once. Operates on any key containing 'qkv_proj'; returns a
    new dict."""
    import numpy as np

    out = {}
    for k, v in state_dict.items():
        if "qkv_proj" in k:
            arr = np.asarray(v.numpy() if hasattr(v, "numpy") else v)
            three_h = arr.shape[-1]
            hd = three_h // (3 * num_heads)
            # [..., 3*H*hd] 3-major -> heads-major
            arr = arr.reshape(arr.shape[:-1] + (3, num_heads, hd))
            arr = np.swapaxes(arr, -3, -2).reshape(arr.shape[:-3] + (three_h,))
            out[k] = arr
        else:
            out[k] = v
    return out


class GPTAttention(nn.Layer):
    """Fused-qkv layout is heads-major (state_dict layout v2); checkpoints
    from the 3-major era must pass through convert_legacy_qkv_state_dict."""

    QKV_LAYOUT_VERSION = 2

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        init = I.Normal(0.0, cfg.initializer_range)
        self.qkv_proj = ColumnParallelLinear(
            cfg.hidden_size, 3 * cfg.hidden_size, weight_attr=init,
            gather_output=False,
        )
        self.out_proj = RowParallelLinear(
            cfg.hidden_size, cfg.hidden_size, weight_attr=init,
            input_is_parallel=True,
        )

    def forward(self, x, cache=None):
        cfg = self.cfg
        b, s = x.shape[0], x.shape[1]
        qkv = self.qkv_proj(x)  # [b, s, 3h] sharded on mp
        if cache is not None and not isinstance(cache, dict):
            # paged KV view (paddle.serving.PagedCacheView): block storage,
            # per-row lengths, and the block table live in the view; the
            # attention math is the paged_decode_attention analogue of
            # cached_attention below (bitwise-equal over the same context)
            qkv = qkv.reshape([b, s, self.num_heads, 3, self.head_dim])
            q, k, v = qkv.unstack(axis=3)
            out = cache.append_attend(
                q, k, v, scale=1.0 / math.sqrt(self.head_dim)
            )
            out = out.reshape([b, s, self.num_heads * self.head_dim])
            return self.out_proj(out)
        # heads-major fused-qkv layout (Megatron-style): 3h splits as
        # H x 3 x hd so the mp sharding of the fused dim lands on the
        # HEADS subdim (divisible by mp). The 3-major layout put mp on the
        # size-3 subdim — GSPMD could only replicate-then-repartition,
        # the 'Involuntary full rematerialization' churn in the backward.
        qkv = qkv.reshape([b, s, self.num_heads, 3, self.head_dim])
        q, k, v = qkv.unstack(axis=3)
        if cache is not None:
            # incremental decode over a PREALLOCATED fixed-shape cache:
            # every step reuses one compiled program (ops/nn_ops.py
            # cached_attention), with a prefix+causal mask that stays
            # correct for multi-token chunks too.
            import numpy as _np

            from ..core.dispatch import apply as _apply
            from ..ops import nn_ops as _ops

            if cache.get("k") is None:
                cache["k"] = paddle.zeros(
                    [b, cfg.max_seq_len, self.num_heads, self.head_dim],
                    dtype=str(k._value.dtype),
                )
                cache["v"] = paddle.zeros(
                    [b, cfg.max_seq_len, self.num_heads, self.head_dim],
                    dtype=str(v._value.dtype),
                )
                cache["len"] = 0
            if cache["len"] + s > cfg.max_seq_len:
                raise CacheOverflow(
                    cache["len"] + s, cfg.max_seq_len,
                    detail=f"cached {cache['len']} + new {s} > max_seq_len",
                )
            cur = paddle.Tensor(_np.int32(cache["len"]), stop_gradient=True)
            out, nk, nv = _apply(
                _ops.cached_attention, q, cache["k"], cache["v"], k, v, cur,
                scale=1.0 / math.sqrt(self.head_dim),
                op_name="cached_attention",
            )
            cache["k"], cache["v"] = nk, nv
            cache["len"] += s
            out = out.reshape([b, s, self.num_heads * self.head_dim])
            return self.out_proj(out)
        ring_mode = cfg.sequence_parallel and cfg.sequence_parallel_mode in (
            "ring", "ulysses"
        )
        if ring_mode:
            from ..core.dispatch import apply as _apply
            from ..ops import ring_attention as _ra
            from ..parallel.topology import axis_size, get_mesh

            if axis_size("sep") > 1:
                if self.training and cfg.attn_dropout > 0.0:
                    raise NotImplementedError(
                        "ring/ulysses attention has no attention-dropout "
                        "path; set attn_dropout=0.0 (hidden-state dropout "
                        "still applies) or use sequence_parallel_mode='gspmd'"
                    )
                # KV stay seq-sharded: the ring/alltoall moves them, not GSPMD
                q = _sp(q, cfg, ("dp", "sharding"), "sep", "mp", None)
                k = _sp(k, cfg, ("dp", "sharding"), "sep", "mp", None)
                v = _sp(v, cfg, ("dp", "sharding"), "sep", "mp", None)
                fn = (
                    _ra.ring_attention
                    if cfg.sequence_parallel_mode == "ring"
                    else _ra.ulysses_attention
                )
                # module-level fn + hashable static kwargs → per-op jit cache
                # applies (a closure here would defeat it — dispatch refuses
                # to cache closures)
                out = _apply(
                    fn, q, k, v, mesh=get_mesh(), causal=True,
                    op_name=f"{cfg.sequence_parallel_mode}_attention",
                )
                out = out.reshape([b, s, self.num_heads * self.head_dim])
                return self.out_proj(out)
        # heads axis is the mp-sharded axis (TP attention)
        q = _sp(q, cfg, ("dp", "sharding"), "sep", "mp", None)
        k = _sp(k, cfg, ("dp", "sharding"), None, "mp", None)
        v = _sp(v, cfg, ("dp", "sharding"), None, "mp", None)
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=True,
            dropout_p=cfg.attn_dropout if self.training else 0.0,
            training=self.training,
        )
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        return self.out_proj(out)


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        init = I.Normal(0.0, cfg.initializer_range)
        out_init = I.Normal(
            0.0, cfg.initializer_range / math.sqrt(2.0 * cfg.num_layers)
        )
        self.fc1 = ColumnParallelLinear(
            cfg.hidden_size, cfg.ffn_hidden_size, weight_attr=init,
            gather_output=False,
        )
        self.fc2 = RowParallelLinear(
            cfg.ffn_hidden_size, cfg.hidden_size, weight_attr=out_init,
            input_is_parallel=True,
        )

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class GPTDecoderLayer(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.ln1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size)
        self.mlp = GPTMLP(cfg)
        self.dropout = nn.Dropout(cfg.dropout)

    def _block(self, x, cache=None):
        x = x + self.dropout(self.attn(self.ln1(x), cache=cache))
        x = x + self.dropout(self.mlp(self.ln2(x)))
        return _sp(x, self.cfg, ("dp", "sharding"), "sep", None)

    def forward(self, x, cache=None):
        if self.cfg.use_recompute and cache is None:
            from ..incubate.recompute import recompute

            return recompute(self._block, x)
        return self._block(x, cache=cache)


class GPTEmbeddings(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        init = I.Normal(0.0, cfg.initializer_range)
        self.word_embeddings = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=init
        )
        self.position_embeddings = nn.Embedding(
            cfg.max_seq_len, cfg.hidden_size, weight_attr=init
        )
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, input_ids, pos_offset=0):
        s = input_ids.shape[1]
        pos = paddle.arange(s, dtype="int64").unsqueeze(0)
        if isinstance(pos_offset, paddle.Tensor):
            # per-row offsets (continuous-batching decode: every sequence in
            # the batch sits at its own position) — [b] broadcasts to [b, s]
            pos = pos + pos_offset.astype("int64").unsqueeze(-1)
        else:
            pos = pos + pos_offset
        h = self.word_embeddings(input_ids) + self.position_embeddings(pos)
        h = _sp(h, self.cfg, ("dp", "sharding"), "sep", None)
        return self.dropout(h)


class GPTModel(nn.Layer):
    """Decoder-only transformer trunk."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = GPTEmbeddings(cfg)
        self.layers = nn.LayerList([GPTDecoderLayer(cfg) for _ in range(cfg.num_layers)])
        self.final_ln = nn.LayerNorm(cfg.hidden_size)

    def forward(self, input_ids, caches=None, pos_offset: int = 0):
        h = self.embeddings(input_ids, pos_offset=pos_offset)
        for i, layer in enumerate(self.layers):
            h = layer(h, cache=None if caches is None else caches[i])
        return self.final_ln(h)


class GPTForPretraining(nn.Layer):
    """Trunk + weight-tied vocab-parallel LM head."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)

    def forward(self, input_ids, caches=None, pos_offset: int = 0):
        # the same three phases the pipeline schedule runs, so the eager and
        # pipelined computations cannot diverge
        if caches is not None:
            h = self.gpt(input_ids, caches=caches, pos_offset=pos_offset)
            return self._tied_head(h)
        h = self.pp_embed(input_ids)
        for layer in self.gpt.layers:
            h = layer(h)
        return self.pp_head(h)

    def _tied_head(self, h):
        w = self.gpt.embeddings.word_embeddings.weight
        # no Layer of its own: the scope names the vocabulary-wide matmul in
        # the profiler's by-layer view
        with jax.named_scope("lm_head"):
            logits = paddle.matmul(h, w, transpose_y=True)
            return _sp(logits, self.cfg, ("dp", "sharding"), "sep", "mp")

    # pipeline-partition protocol (parallel/pipeline.py): homogeneous middle
    # = the decoder stack; embedding/head replicated across pp stages
    def pp_embed(self, input_ids):
        return self.gpt.embeddings(input_ids)

    @property
    def pp_blocks(self):
        return list(self.gpt.layers)

    def pp_head(self, h):
        return self._tied_head(self.gpt.final_ln(h))

    @paddle.no_grad()
    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 1.0, top_k: Optional[int] = None,
                 eos_token_id: Optional[int] = None):
        """Autoregressive decoding (greedy, or top-k sampling when top_k set).

        Fixed-shape incremental decode: the sequence buffer is padded to
        prompt+max_new_tokens once, so every step re-runs ONE compiled
        forward (causal masking makes the not-yet-written tail irrelevant to
        the current position's logits). O(T·forward) — flash attention keeps
        that cheap; a KV-cache decode path is the optimization on top, not a
        correctness requirement. Reference analogue: generation loops live
        upstream (PaddleNLP) — provided here so the flagship model is usable
        end to end.
        """
        import numpy as np

        was_training = self.training
        self.eval()
        try:
            ids = np.asarray(
                input_ids.numpy() if isinstance(input_ids, paddle.Tensor) else input_ids,
                np.int64,
            )
            if ids.ndim == 1:
                ids = ids[None, :]
            b, prompt_len = ids.shape
            if prompt_len >= self.cfg.max_seq_len:
                raise ValueError(
                    f"prompt length {prompt_len} leaves no room to generate "
                    f"within max_seq_len={self.cfg.max_seq_len}; truncate the "
                    "prompt (keep its most recent tokens) before calling"
                )
            total = min(prompt_len + max_new_tokens, self.cfg.max_seq_len)
            buf = np.zeros((b, total), np.int64)
            buf[:, :prompt_len] = ids[:, :total]
            done = np.zeros((b,), bool)

            from ..parallel.topology import get_mesh

            mesh = get_mesh()
            sharded = mesh is not None and mesh.devices.size > 1
            # KV-cache incremental decode: prefill once over the prompt,
            # then one single-token forward per step — O(T) tokens instead
            # of O(T) full-sequence forwards. Sharded meshes keep the
            # fixed-shape path (growing cache shapes fight GSPMD layouts).
            caches = (
                None if sharded
                else [{"k": None, "v": None} for _ in self.gpt.layers]
            )

            def _feed(arr):
                # under a live mesh the params are sharded: feed ids
                # replicated so GSPMD can re-shard activations per layer
                if sharded:
                    import jax as _jax
                    from jax.sharding import NamedSharding, PartitionSpec

                    return paddle.Tensor(
                        _jax.device_put(arr, NamedSharding(mesh, PartitionSpec())),
                        stop_gradient=True,
                    )
                return paddle.to_tensor(arr)

            for cur in range(prompt_len, total):
                if caches is not None:
                    if cur == prompt_len:  # prefill the whole prompt
                        logits = self(
                            _feed(buf[:, :prompt_len]), caches=caches, pos_offset=0
                        )
                        step_t = logits[:, -1, :]
                    else:  # one new token
                        logits = self(
                            _feed(buf[:, cur - 1 : cur]), caches=caches,
                            pos_offset=cur - 1,
                        )
                        step_t = logits[:, 0, :]
                else:
                    logits = self(_feed(buf))  # [b, total, vocab]
                    # slice the current position ON DEVICE before the host
                    # copy (full [b, total, vocab] D2H would dominate)
                    step_t = logits[:, cur - 1, :]
                if top_k is not None:
                    t = max(float(temperature), 1e-6)
                    k_eff = min(int(top_k), step_t.shape[-1])
                    vals, idx = paddle.topk(step_t / t, k_eff, axis=-1)
                    probs = F.softmax(vals, axis=-1)
                    # multinomial draws through the framework generator, so
                    # paddle.seed reproduces runs while successive calls
                    # yield different samples
                    choice = paddle.multinomial(probs, num_samples=1)
                    nxt = np.take_along_axis(
                        idx.numpy(), choice.numpy().astype(np.int64), axis=-1
                    )[:, 0]
                else:
                    nxt = step_t.numpy().argmax(-1)
                nxt = np.where(done, buf[:, cur - 1], nxt)
                buf[:, cur] = nxt
                if eos_token_id is not None:
                    done |= nxt == eos_token_id
                    if done.all():
                        buf = buf[:, : cur + 1]
                        break
            return paddle.to_tensor(buf)
        finally:
            if was_training:
                self.train()


class GPTPretrainingCriterion(nn.Layer):
    """reference: ParallelCrossEntropy (mp_layers.py:249) over shifted LM
    labels, masked mean."""

    def __init__(self, cfg: Optional[GPTConfig] = None):
        super().__init__()

    def forward(self, logits, labels, loss_mask=None):
        loss = F.cross_entropy(logits, labels, reduction="none")
        if loss_mask is not None:
            loss = loss * loss_mask
            return loss.sum() / loss_mask.sum().clip(min=1.0)
        return loss.mean()


def gpt2_small(**kw) -> GPTConfig:
    return GPTConfig(hidden_size=768, num_layers=12, num_heads=12, **kw)


def gpt2_medium(**kw) -> GPTConfig:
    return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16, **kw)


def gpt2_345m(**kw) -> GPTConfig:
    """BASELINE config 4: GPT-2 345M."""
    return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16, **kw)
