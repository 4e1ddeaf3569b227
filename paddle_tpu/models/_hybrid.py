"""What the hybrid decoders (``qwen3_next.py``, ``granite_hybrid.py``)
share: a block is ``h += mixer(norm1(h)); h += experts(norm2(h))`` with a
mixer of one of two kinds, dropless sparse experts after every mixer, and
the mixer's half optionally made again in the backward."""
from __future__ import annotations

from .. import nn
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
    """[(layer, routed_slots, expert_rows)] of the last forward."""
    return [(i, int(l.experts.routed_slots._value),
             int(l.experts.expert_rows._value))
            for i, l in enumerate(layers)]
