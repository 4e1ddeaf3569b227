"""How a ``qwen3-next`` configuration file becomes the program's model: the
one place that knows ``paddle_tpu``'s parameter names for it. Used by the
driver only; the reference never imports it."""
import paddle_tpu as paddle
from paddle_tpu.models import Qwen3NextConfig, Qwen3NextForCausalLM

from . import weights_qwen3_next as seeded

_LAYER_LEAF = {
    "norm1.weight": "norm1", "norm2.weight": "norm2",
    "mixer.q_proj.weight": "q_w", "mixer.k_proj.weight": "k_w",
    "mixer.v_proj.weight": "v_w", "mixer.o_proj.weight": "o_w",
    "mixer.q_norm.weight": "qnorm", "mixer.k_norm.weight": "knorm",
    "mixer.in_proj_qkvz.weight": "qkvz_w", "mixer.in_proj_ba.weight": "ba_w",
    "mixer.conv_weight": "conv_w", "mixer.A_log": "a_log",
    "mixer.dt_bias": "dt_bias", "mixer.norm_weight": "gnorm",
    "mixer.out_proj.weight": "out_w",
    "experts.router": "router", "experts.w_gate_up": "egu_w",
    "experts.w_down": "ed_w", "experts.shared_gate_up": "sgu_w",
    "experts.shared_down": "sd_w", "experts.shared_gate": "sg_w",
}
_TOP_LEAF = {"model.embed_tokens.weight": "embed", "lm_head.weight": "head_w",
             "model.norm.weight": "norm_f"}


def flat_name(param_name):
    """The reference's name of a leaf: 'qkvz_w.0', 'embed'."""
    if param_name in _TOP_LEAF:
        return _TOP_LEAF[param_name]
    _, _, layer, rest = param_name.split(".", 3)
    return f"{_LAYER_LEAF[rest]}.{layer}"


def build_model(sizes):
    cfg = Qwen3NextConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_hidden_layers=sizes["num_hidden_layers"],
        full_attention_interval=sizes["full_attention_interval"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"],
        partial_rotary_factor=sizes["partial_rotary_factor"],
        rope_theta=sizes["rope_theta"],
        linear_num_key_heads=sizes["linear_num_key_heads"],
        linear_num_value_heads=sizes["linear_num_value_heads"],
        linear_key_head_dim=sizes["linear_key_head_dim"],
        linear_value_head_dim=sizes["linear_value_head_dim"],
        linear_conv_kernel_dim=sizes["linear_conv_kernel_dim"],
        num_experts=sizes["router_experts"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        shared_expert_intermediate_size=sizes[
            "shared_expert_intermediate_size"],
        norm_topk_prob=sizes["norm_topk_prob"],
        held_experts=(sizes["held_first"], sizes["num_experts"]),
        rms_norm_eps=sizes["rms_norm_eps"],
        use_recompute=sizes["recompute_mixer"])
    return cfg, Qwen3NextForCausalLM(cfg)


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


def routed_experts(model, ids):
    """Per layer, the expert ids [T, k] the program's router chooses for the
    token ids [batch, seq]: the model's own layers walked eagerly (each op a
    program of its own, where the compiled step is one: XLA's fusions there
    may round a value once less), the router's product and ``top_k`` as
    ``incubate/moe.py`` writes them."""
    import jax.numpy as jnp
    from paddle_tpu.incubate import moe

    trunk, out = model.model, []
    with paddle.no_grad():
        h = trunk.embed_tokens(paddle.Tensor(jnp.asarray(ids),
                                             stop_gradient=True))
        for layer in trunk.layers:
            h = h + layer.mixer(layer.norm1(h))
            m = layer.norm2(h)
            e = layer.experts
            logits = jnp.matmul(
                m._value.reshape(-1, m.shape[-1]), e.router._value,
                preferred_element_type=jnp.float32)
            out.append(moe.route_top_k(logits, e.top_k, e.renormalize)[1])
            h = h + e(m)
    return out


__all__ = ["paddle", "build_model", "seed_weights", "flat_name",
           "routed_experts"]
