"""How a ``granite-4.0-h`` configuration file becomes the program's model:
the one place that knows ``paddle_tpu``'s parameter names for it. Used by the
driver only; the reference never imports it."""
import paddle_tpu as paddle
from paddle_tpu.models import GraniteHybridConfig, GraniteHybridForCausalLM

from . import weights_granite_hybrid as seeded

_LAYER_LEAF = {
    "norm1.weight": "norm1", "norm2.weight": "norm2",
    "mixer.q_proj.weight": "q_w", "mixer.k_proj.weight": "k_w",
    "mixer.v_proj.weight": "v_w", "mixer.o_proj.weight": "o_w",
    "mixer.in_proj_xbcz.weight": "xbcz_w", "mixer.in_proj_dt.weight": "dt_w",
    "mixer.conv_weight": "conv_w", "mixer.conv_bias": "conv_b",
    "mixer.A_log": "a_log", "mixer.dt_bias": "dt_bias", "mixer.D": "d_skip",
    "mixer.norm_weight": "gnorm", "mixer.out_proj.weight": "out_w",
    "experts.router": "router", "experts.w_gate_up": "egu_w",
    "experts.w_down": "ed_w", "experts.shared_gate_up": "sgu_w",
    "experts.shared_down": "sd_w",
}
_TOP_LEAF = {"model.embed_tokens.weight": "embed",
             "model.norm.weight": "norm_f"}
# the configuration's key for what is held -> the model's published count
_HELD = {"mamba_heads_held": ("mamba_heads_first", "mamba_n_heads"),
         "attention_heads_held": ("attention_heads_first",
                                  "num_attention_heads"),
         "experts_held": ("held_first", "num_local_experts"),
         "vocab_held": ("vocab_first", "vocab_size")}


def flat_name(param_name):
    """The reference's name of a leaf: 'xbcz_w.0', 'embed'."""
    if param_name in _TOP_LEAF:
        return _TOP_LEAF[param_name]
    _, _, layer, rest = param_name.split(".", 3)
    return f"{_LAYER_LEAF[rest]}.{layer}"


def build_model(sizes):
    """The configuration file's counts are what this chip holds; the model
    is told the published ones beside them."""
    wide = dict(sizes["published"])
    n = sizes["num_hidden_layers"]
    cfg = GraniteHybridConfig(
        vocab_size=wide["vocab_size"], hidden_size=sizes["hidden_size"],
        num_hidden_layers=n, layer_types=tuple(sizes["layer_types"][:n]),
        mamba_n_heads=wide["mamba_n_heads"],
        mamba_d_head=sizes["mamba_d_head"],
        mamba_d_state=sizes["mamba_d_state"],
        mamba_d_conv=sizes["mamba_d_conv"],
        mamba_n_groups=sizes["mamba_n_groups"],
        mamba_chunk_size=sizes["mamba_chunk_size"],
        num_attention_heads=wide["num_attention_heads"],
        num_key_value_heads=wide["num_key_value_heads"],
        num_local_experts=sizes["router_experts"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        intermediate_size=sizes["intermediate_size"],
        shared_intermediate_size=sizes["shared_intermediate_size"],
        embedding_multiplier=sizes["embedding_multiplier"],
        residual_multiplier=sizes["residual_multiplier"],
        attention_multiplier=sizes["attention_multiplier"],
        logits_scaling=sizes["logits_scaling"],
        rms_norm_eps=sizes["rms_norm_eps"],
        use_recompute=sizes["recompute_mixer"],
        **{held: (sizes[first], sizes[count])
           for held, (first, count) in _HELD.items()})
    if cfg.head_dim != sizes["head_dim"]:
        raise ValueError(f"head_dim {sizes['head_dim']} != {cfg.head_dim}")
    return cfg, GraniteHybridForCausalLM(cfg)


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
