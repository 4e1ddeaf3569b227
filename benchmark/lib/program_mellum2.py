"""How a ``mellum2-*`` configuration file becomes the program's model: the one
place that knows ``paddle_tpu``'s parameter names for it. Used by the driver
only; the reference never imports it."""
import paddle_tpu as paddle
from paddle_tpu.models import Mellum2Config, Mellum2ForCausalLM

from . import weights_mellum2 as seeded

_LAYER_LEAF = {
    "norm1.weight": "norm1", "norm2.weight": "norm2",
    "mixer.q_proj.weight": "q_w", "mixer.k_proj.weight": "k_w",
    "mixer.v_proj.weight": "v_w", "mixer.o_proj.weight": "o_w",
    "experts.router": "router", "experts.w_gate_up": "egu_w",
    "experts.w_down": "ed_w",
}
_TOP_LEAF = {"model.embed_tokens.weight": "embed", "lm_head.weight": "head_w",
             "model.norm.weight": "norm_f"}


def flat_name(param_name):
    """The reference's name of a leaf: 'q_w.0', 'embed'."""
    if param_name in _TOP_LEAF:
        return _TOP_LEAF[param_name]
    _, _, layer, rest = param_name.split(".", 3)
    return f"{_LAYER_LEAF[rest]}.{layer}"


def build_model(sizes):
    """The configuration file's ``num_experts`` and ``vocab_size`` are what
    this chip holds; the router keeps ``router_experts`` outputs. The layers'
    kinds are the first ``num_hidden_layers`` of ``layer_types``."""
    n = sizes["num_hidden_layers"]
    cfg = Mellum2Config(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_hidden_layers=n,
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], num_experts=sizes["router_experts"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        norm_topk_prob=sizes["norm_topk_prob"],
        held_experts=(sizes["held_first"], sizes["num_experts"]),
        rms_norm_eps=sizes["rms_norm_eps"],
        use_recompute=sizes["recompute_mixer"],
        sliding_window=sizes["sliding_window"],
        layer_types=tuple(sizes["layer_types"][:n]),
        rope_parameters=sizes["rope_parameters"])
    return cfg, Mellum2ForCausalLM(cfg)


def seed_weights(model, sizes, seed, dtype):
    """Replace every parameter by the seeded one (one jitted call makes them
    all, on the device, in ``dtype``)."""
    made = seeded.make(sizes, seed, dtype)
    for name, p in model.named_parameters():
        value = made[flat_name(name)]
        if tuple(value.shape) != tuple(p.shape):
            raise ValueError(f"{name}: seeded {value.shape} != {p.shape}")
        p._value = value
    return model


__all__ = ["paddle", "build_model", "seed_weights", "flat_name", "seeded"]
