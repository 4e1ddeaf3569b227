"""Failures that used to be swallowed on the paths users run now surface.

- ``plan_block_pool``: an unexpected error from the trace propagates (it used
  to become ``overhead = 0``, i.e. a KV pool sized to the whole device);
- with a device that reports 16 GiB and GPT-2 345M's geometry, the planned
  pool plus the traced overhead (weights included) stays under the limit;
- ``TPUPlace(0).jax_device`` on a CPU-only backend raises — no stand-in from
  the default backend, no modulo over the device list;
- ``_prove_sharded_donation`` re-raises an internal error ("unproven" is a
  verdict the pass returns, not what an exception means);
- the compile cache is placed from outside, and importing the package
  initializes no backend.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.analysis import memory as amem
from paddle_tpu.core import lazy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GiB = 1 << 30


def test_plan_block_pool_propagates_unexpected_trace_error():
    def thunk():
        raise AttributeError("module 'jax.core' has no attribute 'Literal'")

    with pytest.raises(AttributeError, match="Literal"):
        amem.plan_block_pool(thunk, block_bytes=1 << 20, budget_mb=1024.0)


def test_planned_pool_fits_a_16gib_device_at_gpt345m_geometry(monkeypatch):
    from paddle_tpu.models import GPTForPretraining, gpt2_345m

    paddle.seed(0)
    model = GPTForPretraining(
        gpt2_345m(max_seq_len=2048, dropout=0.0, attn_dropout=0.0))
    weight_bytes = sum(
        int(np.prod(p.shape)) * 4 for p in model.parameters())
    # an explicit small pool: this test plans the real one, it must not
    # allocate 14 GiB of host memory for it
    eng = serving.Engine(model, serving.ServingConfig(
        prompt_buckets=[32, 64, 128], num_blocks=8))
    try:
        monkeypatch.setattr(amem, "_hbm_cache", [True, 16 * GiB])
        heads, head_dim = 16, 64
        block_bytes = 2 * 24 * eng._block_size * heads * head_dim * 4
        plan = eng._plan_pool(
            heads=heads, head_dim=head_dim, dtype="float32",
            scratch=eng._buckets.max_decode_batch, block_bytes=block_bytes,
            budget_mb=None)
    finally:
        eng.close()
    # the traced overhead is the program's, weights included — not 0
    assert plan.overhead_bytes >= weight_bytes
    assert plan.num_blocks > 0
    total = plan.pool_bytes() + plan.overhead_bytes
    assert total <= plan.budget_bytes < 16 * GiB
    # the pool is everything the budget leaves, to within one block
    assert plan.budget_bytes - total < block_bytes


def test_tpu_place_raises_on_a_cpu_only_backend():
    assert jax.default_backend() == "cpu"
    assert paddle.CUDAPlace(0) is not None  # constructing stays free
    with pytest.raises(RuntimeError, match="no tpu devices"):
        paddle.TPUPlace(0).jax_device
    with pytest.raises(ValueError, match="device"):
        paddle.CPUPlace(len(jax.devices("cpu"))).jax_device
    with pytest.raises(RuntimeError, match="no tpu devices"):
        paddle.to_tensor([1.0], place=paddle.TPUPlace(0))


def test_prove_sharded_donation_reraises_internal_error(monkeypatch):
    from paddle_tpu.core import dispatch

    def boom(entry):
        raise AttributeError("internal: analysis layer is broken")

    monkeypatch.setattr(lazy, "_capture_arg_roles", boom)
    before = dispatch._counters["capture_donation_fallbacks"]
    with pytest.raises(AttributeError, match="analysis layer is broken"):
        lazy._prove_sharded_donation(object(), None, (0, 1))
    assert dispatch._counters["capture_donation_fallbacks"] == before


@pytest.mark.parametrize("env_dir", [None, "outside"],
                         ids=["default_in_checkout", "env_wins"])
def test_compile_cache_is_placed_from_outside(tmp_path, env_dir):
    """A fresh interpreter: importing the package fixes the cache directory
    (the env var wins; else <checkout>/.jax_cache) and touches no backend."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if env_dir is not None:
        want = str(tmp_path / env_dir)
        env["JAX_COMPILATION_CACHE_DIR"] = want
    code = (
        "import paddle_tpu, jax\n"
        "from jax._src import xla_bridge\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(len(xla_bridge._backends))\n"
    )
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=str(tmp_path),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    cache_dir, n_backends = out.stdout.split()[-2:]
    assert cache_dir == want
    assert n_backends == "0"
