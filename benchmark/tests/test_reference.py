"""``reference_gpt2.py`` agrees with ``models/gpt.py`` at a tiny size on the
CPU in float32: forward logits, loss, gradients; and the seeded weights are
the same values in both layouts."""
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_gpt2 as ref
from benchmark.lib import weights

SIZES = dict(n_layer=2, n_embd=64, n_head=4, n_positions=64, padded_vocab=512,
             vocab_size=500)
SEED = 2**31 + 5


@pytest.fixture(scope="module")
def both():
    from benchmark.lib import program_gpt as prog

    _, model = prog.build_model(SIZES)
    prog.seed_weights(model, SIZES, SEED, "float32")
    return prog, model, weights.stacked(SIZES, SEED, "float32")


def test_seeded_weights_same_in_both_layouts():
    a = weights.stacked(SIZES, SEED, "bfloat16")
    b = weights.per_layer(SIZES, SEED, "bfloat16")
    for name, v in a.items():
        got = jnp.stack(b[name]) if isinstance(b[name], list) else b[name]
        assert np.array_equal(np.asarray(v, np.float32),
                              np.asarray(got, np.float32)), name
    c = weights.stacked(SIZES, SEED + 1, "bfloat16")
    assert not np.array_equal(np.asarray(a["wte"], np.float32),
                              np.asarray(c["wte"], np.float32))


def test_forward_loss_and_gradients_agree(both):
    prog, model, w = both
    paddle = prog.paddle
    from paddle_tpu.models import GPTPretrainingCriterion

    ids = np.random.default_rng(0).integers(0, 500, (3, 33)).astype(np.int32)
    x, y = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])
    out = model(paddle.Tensor(x))
    want = ref.logits_of(w, ref.hidden(w, x, SIZES["n_head"]))
    assert float(jnp.abs(out._value - want).max()) < 1e-5
    loss = GPTPretrainingCriterion()(out, paddle.Tensor(y))
    ref_loss, grads = ref._loss_and_grads(w, x, y, SIZES["n_head"],
                                          ref.exact_operands)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    loss.backward()
    ref_norms = ref.flat_names(ref._leaf_norms(grads))
    for name, p in model.named_parameters():
        got = float(jnp.linalg.norm(p.grad._value))
        assert got == pytest.approx(ref_norms[prog.flat_name(name)],
                                    rel=1e-3, abs=1e-7), name
