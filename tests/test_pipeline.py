"""Pipeline-parallelism tests (parallel/pipeline.py).

Strategy mirrors the reference's PP validation (SURVEY.md §4: pipeline losses
must match the single-process run): the compiled GPipe-over-ppermute schedule
on a virtual pp mesh must reproduce, step for step, the losses of plain
microbatched gradient accumulation on one device — the two are
mathematically identical. Reference:
fleet/meta_parallel/pipeline_parallel.py:80 forward_backward_pipeline.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import fleet
from paddle_tpu.models import GPTConfig, GPTForPretraining, GPTPretrainingCriterion

M = 4  # microbatches
VOCAB, HID, LAYERS, HEADS, SEQ = 128, 32, 4, 4, 16


def _make(seed, lr=1e-3, wd=0.01):
    paddle.seed(seed)
    cfg = GPTConfig(
        vocab_size=VOCAB, hidden_size=HID, num_layers=LAYERS, num_heads=HEADS,
        max_seq_len=SEQ * 2, dropout=0.0, attn_dropout=0.0,
    )
    model = GPTForPretraining(cfg)
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(
        learning_rate=lr, parameters=model.parameters(), weight_decay=wd
    )
    return model, crit, opt


def _reference_losses(X, steps=2):
    """Single-device microbatched grad accumulation (== GPipe math)."""
    model, crit, opt = _make(7)
    losses = []
    for s in range(steps):
        x = paddle.to_tensor(X[s][:, :-1])
        y = paddle.to_tensor(X[s][:, 1:].astype(np.int64))
        mb = x.shape[0] // M
        total = None
        for i in range(M):
            loss = crit(model(x[i * mb:(i + 1) * mb]), y[i * mb:(i + 1) * mb])
            (loss / M).backward()
            total = loss.detach() if total is None else total + loss.detach()
        opt.step()
        opt.clear_grad()
        losses.append(float(total) / M)
    return losses


def _batch(steps=2, bsz=8):
    rng = np.random.default_rng(0)
    return rng.integers(0, VOCAB, (steps, bsz, SEQ + 1)).astype(np.int32)


def _fleet_pp(dp, mp, pp, stage=0):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "mp_degree": mp, "pp_degree": pp}
    strategy.pipeline_configs = {"accumulate_steps": M}
    if stage:
        strategy.sharding = True
        strategy.sharding_configs = {"stage": stage}
    fleet.init(is_collective=True, strategy=strategy)
    return strategy


def test_pp4_matches_single_device():
    X = _batch()
    ref = _reference_losses(X)
    _fleet_pp(dp=2, mp=1, pp=4)
    model, crit, opt = _make(7)
    model = fleet.distributed_model(model)
    step = fleet.distributed_train_step(model, crit, opt)
    got = []
    for s in range(2):
        x = paddle.to_tensor(X[s][:, :-1])
        y = paddle.to_tensor(X[s][:, 1:].astype(np.int64))
        got.append(float(step(x, y)))
    np.testing.assert_allclose(ref, got, rtol=3e-4)


def test_pp_composes_with_tp_and_dp():
    X = _batch()
    ref = _reference_losses(X)
    _fleet_pp(dp=2, mp=2, pp=2)
    model, crit, opt = _make(7)
    model = fleet.distributed_model(model)
    step = fleet.distributed_train_step(model, crit, opt)
    got = []
    for s in range(2):
        x = paddle.to_tensor(X[s][:, :-1])
        y = paddle.to_tensor(X[s][:, 1:].astype(np.int64))
        got.append(float(step(x, y)))
    np.testing.assert_allclose(ref, got, rtol=3e-4)
    # stage weights are PHYSICALLY pp-sharded: each device holds L/pp layers
    v0 = step._stacked[0]
    assert v0.shape[0] == LAYERS
    for sh in v0.addressable_shards:
        assert sh.data.shape[0] == LAYERS // 2
    # and TP shards the qkv output dim on top of pp
    qkv = [v for v in step._stacked if v.ndim == 3 and v.shape[-1] == 3 * HID][0]
    assert "mp" in str(qkv.sharding.spec)


def test_pipeline_layer_train_batch_runs_schedule():
    """PipelineLayer + PipelineParallel.train_batch drive the compiled
    schedule (reference API: model.train_batch(data, opt))."""
    X = _batch()
    ref = _reference_losses(X)
    strategy = _fleet_pp(dp=2, mp=1, pp=4)
    model, crit, opt = _make(7)

    descs = [
        model.gpt.embeddings,
        *model.gpt.layers,
        model.gpt.final_ln,
    ]
    pipe = fleet.PipelineLayer(descs, num_stages=4)

    lo, hi = pipe._homogeneous_middle()
    assert (lo, hi) == (1, 1 + LAYERS)

    # head (tied embedding matmul) + criterion as the loss_fn
    def loss_fn(h, y):
        w = model.gpt.embeddings.word_embeddings.weight
        logits = paddle.matmul(h, w, transpose_y=True)
        return crit(logits, y)

    pipe._loss_fn = loss_fn
    wrapper = fleet.meta_parallel.PipelineParallel(pipe, strategy=strategy)
    got = []
    for s in range(2):
        x = paddle.to_tensor(X[s][:, :-1])
        y = paddle.to_tensor(X[s][:, 1:].astype(np.int64))
        loss = wrapper.train_batch((x, y), opt)
        got.append(float(loss))
    np.testing.assert_allclose(ref, got, rtol=3e-4)


def test_pp_with_zero_sharding():
    X = _batch()
    ref = _reference_losses(X)
    _fleet_pp(dp=1, mp=1, pp=2, stage=2)
    # sharding degree folds into the free mesh: dp=1*sharding left at 1 here;
    # use sharding axis explicitly
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 2, "mp_degree": 1, "pp_degree": 2, "sharding_degree": 2,
    }
    strategy.pipeline_configs = {"accumulate_steps": M}
    strategy.sharding = True
    strategy.sharding_configs = {"stage": 2}
    fleet.init(is_collective=True, strategy=strategy)
    model, crit, opt = _make(7)
    model = fleet.distributed_model(model)
    step = fleet.distributed_train_step(model, crit, opt)
    got = []
    for s in range(2):
        x = paddle.to_tensor(X[s][:, :-1])
        y = paddle.to_tensor(X[s][:, 1:].astype(np.int64))
        got.append(float(step(x, y)))
    np.testing.assert_allclose(ref, got, rtol=3e-4)


def test_pp_grad_clip_and_state_sync():
    """Clipping applies under pp (parity with ShardedTrainStep), and
    state_dict on model/optimizer lazily pulls the stacked values."""
    X = _batch()
    # reference WITH clip
    model, crit, _ = _make(7)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=model.parameters(), weight_decay=0.01,
        grad_clip=paddle.nn.ClipGradByGlobalNorm(0.01),
    )
    ref = []
    for s in range(2):
        x = paddle.to_tensor(X[s][:, :-1])
        y = paddle.to_tensor(X[s][:, 1:].astype(np.int64))
        mb = x.shape[0] // M
        grads_accum = None
        total = None
        for i in range(M):
            loss = crit(model(x[i * mb:(i + 1) * mb]), y[i * mb:(i + 1) * mb])
            (loss / M).backward()
            total = loss.detach() if total is None else total + loss.detach()
        opt.step()
        opt.clear_grad()
        ref.append(float(total) / M)

    _fleet_pp(dp=2, mp=1, pp=2)
    model2, crit2, _ = _make(7)
    opt2 = paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=model2.parameters(), weight_decay=0.01,
        grad_clip=paddle.nn.ClipGradByGlobalNorm(0.01),
    )
    model2 = fleet.distributed_model(model2)
    step = fleet.distributed_train_step(model2, crit2, opt2)
    got = []
    for s in range(2):
        x = paddle.to_tensor(X[s][:, :-1])
        y = paddle.to_tensor(X[s][:, 1:].astype(np.int64))
        got.append(float(step(x, y)))
    np.testing.assert_allclose(ref, got, rtol=3e-4)

    # lazy sync: model state_dict reflects the trained stacked weights and
    # matches the single-device reference parameters
    sd_ref = {k: v.numpy() for k, v in model.state_dict().items()}
    sd_pp = {k: v.numpy() for k, v in model2.state_dict().items()}
    for k in sd_ref:
        np.testing.assert_allclose(sd_ref[k], sd_pp[k], rtol=2e-3, atol=2e-5)
    # optimizer moments flow back through the lazy hook too
    osd = opt2.state_dict()
    assert any(k.endswith(".exp_avg") or ".moment" in k for k in osd)


def test_pp_checkpoint_resume_uses_restored_moments():
    """set_state_dict → pipelined step must start from the restored Adam
    moments, not zeros (same continuation as the single-device run)."""
    X = _batch(steps=4)
    # reference: 4 steps straight through
    model, crit, opt = _make(7)
    ref = []
    for s in range(4):
        x = paddle.to_tensor(X[s][:, :-1])
        y = paddle.to_tensor(X[s][:, 1:].astype(np.int64))
        mb = x.shape[0] // M
        total = None
        for i in range(M):
            loss = crit(model(x[i * mb:(i + 1) * mb]), y[i * mb:(i + 1) * mb])
            (loss / M).backward()
            total = loss.detach() if total is None else total + loss.detach()
        opt.step()
        opt.clear_grad()
        ref.append(float(total) / M)

    # pp run: 2 steps, checkpoint, new process-sim (fresh objects), 2 more
    _fleet_pp(dp=2, mp=1, pp=2)
    m1, c1, o1 = _make(7)
    m1 = fleet.distributed_model(m1)
    step1 = fleet.distributed_train_step(m1, c1, o1)
    got = []
    for s in range(2):
        x = paddle.to_tensor(X[s][:, :-1])
        y = paddle.to_tensor(X[s][:, 1:].astype(np.int64))
        got.append(float(step1(x, y)))
    msd = {k: v.numpy() for k, v in m1.state_dict().items()}
    osd = o1.state_dict()

    m2, c2, o2 = _make(99)  # different init — must be overwritten by ckpt
    m2.set_state_dict(msd)
    o2.set_state_dict(osd)
    m2 = fleet.distributed_model(m2)
    step2 = fleet.distributed_train_step(m2, c2, o2)
    for s in range(2, 4):
        x = paddle.to_tensor(X[s][:, :-1])
        y = paddle.to_tensor(X[s][:, 1:].astype(np.int64))
        got.append(float(step2(x, y)))
    np.testing.assert_allclose(ref, got, rtol=3e-3, atol=1e-4)


def test_pp_per_token_loss_fn_mean_reduced():
    """A loss_fn returning per-token losses works under pp (parity with the
    pp==1 fallback's loss.mean())."""
    X = _batch()
    _fleet_pp(dp=2, mp=1, pp=2)
    model, _, opt = _make(7)
    model = fleet.distributed_model(model)

    def loss_fn(logits, y):
        return F.cross_entropy(logits, y, reduction="none")  # [B, S]

    step = fleet.distributed_train_step(model, loss_fn, opt)
    x = paddle.to_tensor(X[0][:, :-1])
    y = paddle.to_tensor(X[0][:, 1:].astype(np.int64))
    loss = float(step(x, y))
    assert np.isfinite(loss) and 3.0 < loss < 7.0


def test_pp_rejects_buffered_models_and_bad_batch():
    _fleet_pp(dp=2, mp=1, pp=2)
    model = nn.Sequential(
        nn.Linear(8, 8), nn.BatchNorm1D(8), nn.Linear(8, 8), nn.Linear(8, 8)
    )
    from paddle_tpu.parallel.pipeline import PipelinedTrainStep

    class Wrap(nn.Layer):
        def __init__(self):
            super().__init__()
            self.m = model

        def pp_embed(self, x):
            return x

        @property
        def pp_blocks(self):
            return [self.m[2], self.m[3]]

        def pp_head(self, h):
            return self.m[1](self.m[0](h))

    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    with pytest.raises(ValueError, match="buffers"):
        PipelinedTrainStep(Wrap(), None, opt)

    # divisibility error is clear, not an XLA reshape failure
    X = _batch()
    m, c, o = _make(7)
    m = fleet.distributed_model(m)
    step = fleet.distributed_train_step(m, c, o)
    bad_x = paddle.to_tensor(X[0][:6, :-1])
    bad_y = paddle.to_tensor(X[0][:6, 1:].astype(np.int64))
    with pytest.raises(ValueError, match="not divisible"):
        step(bad_x, bad_y)


def test_gpipe_loss_schedule_correctness():
    """The raw schedule: a 4-stage pipeline of y = x + w_l must equal the
    direct stacked sum, microbatch by microbatch."""
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu._jax_compat import shard_map
    from paddle_tpu.parallel.pipeline import gpipe_loss

    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("pp",))
    S, Mm, mb, d = 4, 3, 2, 5
    w = jnp.arange(float(S)).reshape(S, 1) * jnp.ones((S, d))  # [S, d]
    x = jnp.arange(float(Mm * mb * d)).reshape(Mm, mb, d) / 10.0
    y = jnp.ones((Mm, mb, d))

    def body(w_local, x_mb, y_mb):
        def stage_fn(wl, h):
            return h + wl[0]

        def inject(xt):
            return xt * 2.0

        def head_loss(h, yt):
            return jnp.sum(h * yt)

        return gpipe_loss(
            stage_fn, inject, head_loss, w_local, x_mb, y_mb,
            num_stages=S, num_micro=Mm, remat=False,
        )

    out = jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=(P("pp"), P(), P()), out_specs=P(),
            axis_names={"pp"}, check_vma=False,
        )
    )(w, x, y)
    expected = np.mean(
        [np.sum(2.0 * np.asarray(x[m]) + w.sum(0)) for m in range(Mm)]
    )
    np.testing.assert_allclose(float(out), expected, rtol=1e-6)
