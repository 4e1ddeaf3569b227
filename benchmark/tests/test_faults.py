"""The rest of a run with the timed path broken underneath: ``correct`` has
to come out false. Driven through ``run.py`` at the cells' rehearsal size
(which skips the look for a chip), once for each fault a cell can have: a step
that returns its state unchanged; half of the batch left out, the mean taken
over the rest. (No cell here exchanges anything between chips or produces
tokens.) A sound run beside them shows the harness is not
simply always false."""
import json

import pytest

from benchmark import run as bench_run


def result(capsys, workload, seed=2**31 + 77):
    rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", "1", "--trace", "0", "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


class BrokenStep:
    """Stands where ``compile_train_step``'s object stands."""

    def __init__(self, real):
        self.real = real

    def __getattr__(self, name):
        return getattr(self.real, name)


class StateUnchanged(BrokenStep):
    def __call__(self, x, y):
        import jax.numpy as jnp

        real = self.real
        before = [jnp.copy(p._value) for p in real._params]
        loss = real(x, y)
        for p, v in zip(real._params, before):
            p._value = v
        real.optimizer._accumulators.clear()
        real._opt_state = real._init_opt_state()
        return loss


class HalfBatch(BrokenStep):
    def __call__(self, x, y):
        half = x.shape[0] // 2
        T = type(x)
        return self.real(T(x._value[:half], stop_gradient=True),
                         T(y._value[:half], stop_gradient=True))


def test_sound_runs_are_correct(capsys):
    assert result(capsys, "gpt2m-train-s1024")["correct"] is True


@pytest.mark.parametrize("fault", [StateUnchanged, HalfBatch])
@pytest.mark.parametrize("cell", ["gpt2m-train-s1024", "gpt2l-train-s1024"])
def test_broken_train_step_is_not_correct(capsys, monkeypatch, fault, cell):
    import paddle_tpu as paddle

    real = paddle.jit.compile_train_step
    monkeypatch.setattr(paddle.jit, "compile_train_step",
                        lambda *a, **kw: fault(real(*a, **kw)))
    line = result(capsys, cell)
    assert line["correct"] is False
    over = [k for k, c in line["checks"].items() if not c["value"] <= c["limit"]]
    assert over, line["checks"]
