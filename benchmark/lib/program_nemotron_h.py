"""How a ``nemotron-3-nano`` configuration file becomes the program's model:
the one place that knows ``paddle_tpu``'s parameter names for it. Used by the
driver only; the reference never imports it."""
import paddle_tpu as paddle
from paddle_tpu.models import NemotronHConfig, NemotronHForCausalLM

from . import weights_nemotron_h as seeded

_LAYER_LEAF = {
    "norm.weight": "norm",
    "mixer.q_proj.weight": "q_w", "mixer.k_proj.weight": "k_w",
    "mixer.v_proj.weight": "v_w", "mixer.o_proj.weight": "o_w",
    "mixer.in_proj_xbcz.weight": "xbcz_w", "mixer.in_proj_dt.weight": "dt_w",
    "mixer.conv_weight": "conv_w", "mixer.conv_bias": "conv_b",
    "mixer.A_log": "a_log", "mixer.dt_bias": "dt_bias", "mixer.D": "d_skip",
    "mixer.norm_weight": "gnorm", "mixer.out_proj.weight": "out_w",
    "experts.router": "router", "experts.w_up": "eu_w",
    "experts.w_down": "ed_w", "experts.shared_up": "su_w",
    "experts.shared_down": "sd_w",
}
_TOP_LEAF = {"model.embed_tokens.weight": "embed", "lm_head.weight": "head_w",
             "model.norm_f.weight": "norm_f"}


def flat_name(param_name):
    """The reference's name of a leaf: 'xbcz_w.0', 'embed'."""
    if param_name in _TOP_LEAF:
        return _TOP_LEAF[param_name]
    _, _, layer, rest = param_name.split(".", 3)
    return f"{_LAYER_LEAF[rest]}.{layer}"


def build_model(sizes):
    """The configuration file's ``n_routed_experts`` and ``vocab_size`` are
    what this chip holds; the router keeps ``router_experts`` outputs and the
    model is told the published vocabulary beside the held rows. The layers'
    kinds are the first ``num_hidden_layers`` of the pattern."""
    wide = sizes["published"]
    cfg = NemotronHConfig(
        vocab_size=wide["vocab_size"], hidden_size=sizes["hidden_size"],
        num_hidden_layers=sizes["num_hidden_layers"],
        hybrid_override_pattern=sizes["hybrid_override_pattern"],
        mamba_num_heads=sizes["mamba_num_heads"],
        mamba_head_dim=sizes["mamba_head_dim"],
        ssm_state_size=sizes["ssm_state_size"], n_groups=sizes["n_groups"],
        conv_kernel=sizes["conv_kernel"], chunk_size=sizes["chunk_size"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], n_routed_experts=sizes["router_experts"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=sizes[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=sizes["routed_scaling_factor"],
        norm_topk_prob=sizes["norm_topk_prob"],
        layer_norm_epsilon=sizes["layer_norm_epsilon"],
        experts_held=(sizes["held_first"], sizes["n_routed_experts"]),
        vocab_held=(sizes["vocab_first"], sizes["vocab_size"]),
        use_recompute=sizes["recompute_mixer"],
        router_bias_update_rate=sizes["router_bias_update_rate"])
    return cfg, NemotronHForCausalLM(cfg)


def seed_weights(model, sizes, seed, dtype):
    """Replace every parameter by the seeded one (one jitted call makes them
    all, on the device, in ``dtype``) and each expert layer's correction
    bias by its seeded one (float32, as the layer keeps it)."""
    made = seeded.make(sizes, seed, dtype)
    for name, p in model.named_parameters():
        value = made[flat_name(name)]
        if tuple(value.shape) != tuple(p.shape):
            raise ValueError(f"{name}: seeded {value.shape} != {p.shape}")
        p._value = value
    for i, bias in score_biases(model).items():
        bias._value = made[f"bias.{i}"]
    return model


def score_biases(model):
    """{layer index: the expert layer's correction bias buffer}."""
    return {i: layer.experts.score_bias
            for i, layer in enumerate(model.model.layers)
            if layer.kind == "experts"}


__all__ = ["paddle", "build_model", "seed_weights", "score_biases",
           "flat_name", "seeded"]
