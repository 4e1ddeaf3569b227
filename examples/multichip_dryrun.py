"""Multichip dryrun builders for the sharding analyzer / graph_lint --mesh.

The CPU-simulated hybrid-parallel GPT step at dryrun shapes — the
model/mesh family of `__graft_entry__.dryrun_multichip` — exposed as
graph_lint model builders so the static analysis suite (per-shard memory,
donation proofs, collective cost, resharding lints) can gate it in CI
without compiling or running a step:

    python tools/graph_lint.py examples/multichip_dryrun.py --mesh dp=2,mp=2
    python tools/graph_lint.py examples/multichip_dryrun.py --mesh pp=2 \
        --builder build_model_pp

``build_model(mesh_axes=...)`` returns ``(ShardedTrainStep, input_specs)``;
graph_lint routes that pair through
``paddle_tpu.analysis.sharding.check_sharded_step``. The pipeline builder
returns a plain traced function whose ``shard_map`` region the base
analyzer now recurses into.

Run as a script it executes one real step per mesh config (the smoke path
the `__graft_entry__` dryrun uses for every factorization of the device
count).
"""
from __future__ import annotations

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F  # noqa: F401 (re-export convenience)
from paddle_tpu.distributed import fleet
from paddle_tpu.models import (
    GPTConfig, GPTForPretraining, GPTPretrainingCriterion,
)

# dryrun shapes: tiny but with every parallel-relevant dim divisible by
# the mesh axes (heads by mp, batch by dp×sharding, layers by pp)
VOCAB = 512
SEQ = 16


def _init_fleet(mesh_axes):
    axes = dict(mesh_axes or {"dp": 2, "mp": 2})
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": int(axes.get("dp", 1)),
        "mp_degree": int(axes.get("mp", 1)),
        "pp_degree": int(axes.get("pp", 1)),
        "sharding_degree": int(axes.get("sharding", 1)),
        "sep_degree": int(axes.get("sep", 1)),
    }
    if int(axes.get("sharding", 1)) > 1:
        strategy.sharding = True
        strategy.sharding_configs = {"stage": 2}
    fleet.init(is_collective=True, strategy=strategy)
    # fleet.init back-fills leftover devices into dp — read the ACTUAL
    # mesh so batch shapes divide it (dp may exceed the requested degree)
    hcg = fleet.get_hybrid_communicate_group()
    return {
        "dp": hcg.get_data_parallel_world_size(),
        "mp": hcg.get_model_parallel_world_size(),
        "pp": hcg.get_pipe_parallel_world_size(),
        "sharding": hcg.get_sharding_parallel_world_size(),
        "sep": hcg.get_sep_parallel_world_size(),
    }


def _gpt(axes):
    paddle.seed(0)
    n_heads = 4 * max(1, int(axes.get("mp", 1)))
    cfg = GPTConfig(
        vocab_size=VOCAB, hidden_size=32 * n_heads // 4,
        num_layers=2 * max(1, int(axes.get("pp", 1))), num_heads=n_heads,
        max_seq_len=64, dropout=0.0, attn_dropout=0.0,
    )
    model = GPTForPretraining(cfg)
    model = fleet.distributed_model(model)
    criterion = GPTPretrainingCriterion(cfg)

    def loss_fn(logits, labels):
        return criterion(logits, labels)

    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(), weight_decay=0.01
    )
    opt = fleet.distributed_optimizer(opt)
    return model, loss_fn, opt


def build_model(mesh_axes=None):
    """(ShardedTrainStep, input_specs) for the GSPMD hybrid step — default
    mesh dp=2×mp=2; graph_lint --mesh overrides the axes."""
    axes = _init_fleet(mesh_axes)
    model, loss_fn, opt = _gpt(axes)
    step = fleet.distributed_train_step(model, loss_fn, opt)
    bsz = 2 * max(1, int(axes.get("dp", 1)) * int(axes.get("sharding", 1)))
    specs = [
        paddle.static.InputSpec([bsz, SEQ], "int64"),
        paddle.static.InputSpec([bsz, SEQ], "int64"),
    ]
    return step, specs


def build_model_pp(mesh_axes=None):
    """The pp=2 pipeline step's loss program as (fn, input_specs): the
    shard_map(gpipe) region the base analyzer recurses into (per-shard
    body avals, explicit ppermute/psum collectives)."""
    axes = _init_fleet(mesh_axes or {"pp": 2})
    model, loss_fn, opt = _gpt(axes)
    step = fleet.distributed_train_step(model, loss_fn, opt)
    # per-microbatch batch must divide dp×sharding; num_micro defaults to pp
    bsz = (max(1, int(axes.get("pp", 1)))
           * max(1, int(axes.get("dp", 1)) * int(axes.get("sharding", 1))))
    specs = [
        paddle.static.InputSpec([bsz, SEQ], "int64"),
        paddle.static.InputSpec([bsz, SEQ], "int64"),
    ]
    return step, specs


def build_model_captured(mesh_axes=None):
    """Arm the eager whole-step capture tier on a sharded MLP trainer and
    return ``(lazy.captured_step_handle(), None)`` — graph_lint --mesh
    routes the handle through ``check_sharded_step``, which rebuilds the
    per-shard context (and per-position donation verdicts) from the
    capture registry. Runs real eager steps until the capture replays, so
    this builder is slower than the trace-only ones."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    import jax

    from paddle_tpu.core import lazy
    from paddle_tpu.parallel import topology
    from paddle_tpu.parallel.sharding import shard_params
    import paddle_tpu.profiler as prof

    axes = dict(mesh_axes or {"dp": 2, "mp": 2})
    if int(axes.get("pp", 1)) > 1:
        raise SystemExit(
            "build_model_captured: pipelined (pp>1) meshes refuse capture "
            "(shard_map autodiff limitation) — lint the pp step via "
            "build_model_pp instead")
    mesh = topology.init_mesh(**{k: int(v) for k, v in axes.items()})
    paddle.seed(0)
    model = paddle.nn.Sequential(
        paddle.nn.Linear(8, 16), paddle.nn.ReLU(), paddle.nn.Linear(16, 4))
    if int(axes.get("mp", 1)) > 1:
        model[0].weight.dist_spec = (None, "mp")
    opt = paddle.optimizer.Adam(
        learning_rate=1e-2, parameters=model.parameters())
    loss_fn = paddle.nn.CrossEntropyLoss()
    shard_params(model, mesh)
    batch_sh = NamedSharding(mesh, P(tuple(
        a for a in ("dp", "sharding") if int(axes.get(a, 1)) > 1) or None))
    rng = np.random.default_rng(7)
    bsz = 4 * max(1, int(axes.get("dp", 1)) * int(axes.get("sharding", 1)))
    x = paddle.to_tensor(rng.standard_normal((bsz, 8)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 4, (bsz,)))
    x._value = jax.device_put(x._value, batch_sh)
    y._value = jax.device_put(y._value, batch_sh)

    lazy._tls.observer = None
    paddle.set_flags({
        "FLAGS_eager_lazy_dispatch": True,
        "FLAGS_eager_step_capture": True,
        "FLAGS_eager_async_compile": False,
    })
    try:
        for _ in range(12):
            loss = loss_fn(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            if prof.dispatch_counters().get("capture_replays", 0) >= 1:
                break
        else:
            raise SystemExit(
                "build_model_captured: capture never armed in 12 steps "
                f"(counters: {prof.dispatch_counters()})")
    finally:
        paddle.set_flags({"FLAGS_eager_lazy_dispatch": False})
    return lazy.captured_step_handle(), None


def main():
    import numpy as np

    step, specs = build_model()
    x = paddle.randint(0, VOCAB, [int(specs[0].shape[0]), SEQ])
    y = paddle.randint(0, VOCAB, [int(specs[0].shape[0]), SEQ])
    loss = step(x, y)
    print(f"dryrun loss: {float(np.asarray(loss.numpy())):.4f}")


if __name__ == "__main__":
    main()
