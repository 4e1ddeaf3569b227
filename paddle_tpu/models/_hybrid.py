"""What the hybrid decoders (``qwen3_next.py``, ``granite_hybrid.py``,
``nemotron_h.py``) share: a residual branch ``h += mixer(norm(h))``, the
branch optionally made again in the backward; the Mamba-2 mixer and the
causal grouped-query attention without positions of the two state-space
hybrids; the routed load of their expert layers."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dispatch import apply
from ..nn import functional as F
from ..nn import initializer as I


def linear(cfg, n_in, n_out):
    return nn.Linear(n_in, n_out, bias_attr=False,
                     weight_attr=I.Normal(0.0, cfg.initializer_range))


def residual_mixer(x, norm, mixer, *, recompute, multiplier=None):
    """x + [multiplier *] mixer(norm(x)). ``recompute``: the mixer's
    activations are dropped and made again in the backward; the experts stay
    outside, so that their counters are written once, by the forward."""
    def mix(x):
        y = mixer(norm(x))
        return x + (y if multiplier is None else y * multiplier)

    if not recompute:
        return mix(x)
    from ..incubate.recompute import _ChunkParams, recompute as remake

    mix.__self__ = _ChunkParams([norm, mixer])
    return remake(mix, x)


def routed_load(layers):
    """[(layer, routed_slots, expert_rows)] of the last forward, for the
    layers that have experts."""
    return [(i, int(l.experts.routed_slots._value),
             int(l.experts.expert_rows._value))
            for i, l in enumerate(layers)
            if getattr(l, "experts", None) is not None]


def share_event(kind, held, published):
    from ..profiler import trace
    trace.emit("mixer_share", site=kind, heads=held, heads_published=published)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def _attention_heads(q, k, v, *, heads, kv_heads, heads_published):
    share_event("attention", heads, heads_published)
    b, s = q.shape[0], q.shape[1]
    return (q.reshape(b, s, heads, -1), k.reshape(b, s, kv_heads, -1),
            v.reshape(b, s, kv_heads, -1))


class NoPEAttention(nn.Layer):
    """Causal softmax attention of ``heads`` query heads on ``kv_heads`` KV
    heads of ``head_dim``: q, k, v, o without bias, the scores times
    ``scale``, no positional term, through the flash kernels."""

    op_name = "attention_heads"

    def __init__(self, cfg, heads, kv_heads, head_dim, scale,
                 heads_published=None):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        self.heads, self.kv_heads, self.scale = heads, kv_heads, scale
        self.heads_published = heads_published or heads
        self.q_proj = linear(cfg, h, heads * head_dim)
        self.k_proj = linear(cfg, h, kv_heads * head_dim)
        self.v_proj = linear(cfg, h, kv_heads * head_dim)
        self.o_proj = linear(cfg, heads * head_dim, h)

    def forward(self, x):
        q, k, v = apply(
            _attention_heads, self.q_proj(x), self.k_proj(x), self.v_proj(x),
            heads=self.heads, kv_heads=self.kv_heads,
            heads_published=self.heads_published, op_name=self.op_name)
        attn = F.scaled_dot_product_attention(
            q, k, v, is_causal=True, scale=self.scale)
        return self.o_proj(attn.reshape([x.shape[0], x.shape[1], -1]))


# ---------------------------------------------------------------------------
# state space
# ---------------------------------------------------------------------------
def _mamba_mixer(xbcz, dt, conv_w, conv_b, a_log, dt_bias, d_skip, norm_w, *,
                heads, heads_published, p, groups, n, chunk, eps,
                norm_group=None):
    """From the two input projections to the gated, normalised output of the
    scan, [b, s, heads * p]: one statistic over the channels held, or one
    for each ``norm_group`` of them."""
    # imported here: the modules bring Pallas in, which costs every program
    # that imports paddle_tpu.models a second and a half at start-up
    from ..ops import linear_attention as _la
    from ..ops import state_space as _ss

    share_event("mamba", heads, heads_published)
    b, s = xbcz.shape[0], xbcz.shape[1]
    inner, bc = heads * p, groups * n
    with jax.named_scope("short_conv"):
        # over xbcz's first inner + 2 bc lanes, read where they lie; x, B
        # and C each written where the scan reads it
        x, bm, cm = _la.short_conv_silu(xbcz, conv_w, (inner, bc, bc),
                                        bias=conv_b)
    with jax.named_scope("ssd_scan"):
        step = jax.nn.softplus(dt.astype(jnp.float32)
                               + dt_bias.astype(jnp.float32))
        y = _ss.ssd_scan(
            x.reshape(b, s, heads, p), step, a_log,
            bm.reshape(b, s, groups, n), cm.reshape(b, s, groups, n), d_skip,
            chunk=chunk, heads_published=heads_published)
    with jax.named_scope("gated_norm"):
        # the gate z is xbcz's last inner lanes
        return _la.gated_rms_norm(y.reshape(b, s, inner), xbcz, norm_w,
                                  epsilon=eps, gate_first=True,
                                  **({} if norm_group is None
                                     else {"group": norm_group}))


class Mamba2Mixer(nn.Layer):
    """The Mamba-2 mixer of ``heads`` heads of width ``p``: (x B C | z) = u
    W_xbcz and dt = u W_dt (x | B | C | z, a relabelling of the public z |
    x B C | dt under random weights); (x, B, C) <- silu(causal depthwise
    conv of ``conv`` taps + bias); dt <- softplus(dt + dt_bias); the scan
    (``ops/state_space.py``) over ``groups`` B / C groups of state ``n``;
    rmsnorm(y * silu(z)) with one statistic over the channels, or over each
    ``norm_group`` of them; W_out."""

    def __init__(self, cfg, *, heads, heads_published, p, n, groups, conv,
                 chunk, eps, norm_group=None):
        super().__init__()
        self.cfg = cfg
        self.heads, self.heads_published, self.p = heads, heads_published, p
        self.n, self.groups, self.chunk, self.eps = n, groups, chunk, eps
        self.norm_group = norm_group
        h, inner = cfg.hidden_size, heads * p
        channels = inner + 2 * groups * n
        self.in_proj_xbcz = linear(cfg, h, channels + inner)
        self.in_proj_dt = linear(cfg, h, heads)
        self.out_proj = linear(cfg, inner, h)
        self.conv_weight = self.create_parameter(
            shape=[channels, conv],
            default_initializer=I.Normal(0.0, cfg.initializer_range))
        self.conv_bias = self.create_parameter(
            shape=[channels], default_initializer=I.Constant(0.0))
        # the public init: A_log = log U(1, 16), dt_bias the inverse softplus
        # of dt in [1e-3, 1e-1], D = 1, gain = 1
        rng = np.random.default_rng(0)
        step = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), heads))
        self.A_log = self.create_parameter(
            shape=[heads], default_initializer=I.Assign(np.log(
                rng.uniform(1.0, 16.0, heads))))
        self.dt_bias = self.create_parameter(
            shape=[heads],
            default_initializer=I.Assign(step + np.log(-np.expm1(-step))))
        self.D = self.create_parameter(
            shape=[heads], default_initializer=I.Constant(1.0))
        self.norm_weight = self.create_parameter(
            shape=[inner], default_initializer=I.Constant(1.0))

    def forward(self, x):
        extra = {} if self.norm_group is None else {
            "norm_group": self.norm_group}
        y = apply(
            _mamba_mixer, self.in_proj_xbcz(x), self.in_proj_dt(x),
            self.conv_weight, self.conv_bias, self.A_log, self.dt_bias,
            self.D, self.norm_weight,
            heads=self.heads, heads_published=self.heads_published,
            p=self.p, groups=self.groups, n=self.n, chunk=self.chunk,
            eps=self.eps, op_name="mamba_mixer", **extra)
        return self.out_proj(y)
