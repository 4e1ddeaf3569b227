"""How a GPT-2 configuration file becomes the program's model: the one place
that knows ``paddle_tpu``'s parameter names. Used by the drivers only; the
reference never imports it."""
import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForPretraining

from . import weights as seeded

_LAYER_LEAF = {
    "ln1.weight": "ln1_g", "ln1.bias": "ln1_b",
    "attn.qkv_proj.weight": "qkv_w", "attn.qkv_proj.bias": "qkv_b",
    "attn.out_proj.weight": "proj_w", "attn.out_proj.bias": "proj_b",
    "ln2.weight": "ln2_g", "ln2.bias": "ln2_b",
    "mlp.fc1.weight": "fc1_w", "mlp.fc1.bias": "fc1_b",
    "mlp.fc2.weight": "fc2_w", "mlp.fc2.bias": "fc2_b",
}
_TOP_LEAF = {
    "gpt.embeddings.word_embeddings.weight": "wte",
    "gpt.embeddings.position_embeddings.weight": "wpe",
    "gpt.final_ln.weight": "lnf_g", "gpt.final_ln.bias": "lnf_b",
}


def reference_leaf(param_name):
    """('qkv_w', 3) for 'gpt.layers.3.attn.qkv_proj.weight'; layer None for
    an unstacked leaf."""
    if param_name in _TOP_LEAF:
        return _TOP_LEAF[param_name], None
    _, _, layer, rest = param_name.split(".", 3)
    return _LAYER_LEAF[rest], int(layer)


def flat_name(param_name):
    """The reference's flat name of a leaf: 'qkv_w.3', 'wte'."""
    leaf, layer = reference_leaf(param_name)
    return leaf if layer is None else f"{leaf}.{layer}"


def build_model(sizes):
    cfg = GPTConfig(
        vocab_size=sizes["padded_vocab"], hidden_size=sizes["n_embd"],
        num_layers=sizes["n_layer"], num_heads=sizes["n_head"],
        max_seq_len=sizes["n_positions"], dropout=0.0, attn_dropout=0.0)
    return cfg, GPTForPretraining(cfg)


def seed_weights(model, sizes, seed, dtype):
    """Replace every parameter by the seeded one (one jitted call makes them
    all, on the device, in ``dtype``)."""
    made = seeded.per_layer(sizes, seed, dtype)
    for name, p in model.named_parameters():
        leaf, layer = reference_leaf(name)
        value = made[leaf] if layer is None else made[leaf][layer]
        if tuple(value.shape) != tuple(p.shape):
            raise ValueError(f"{name}: seeded {value.shape} != {p.shape}")
        p._value = value
    return model


__all__ = ["paddle", "build_model", "seed_weights", "flat_name",
           "reference_leaf"]
