"""The control has to come out as not correct: the reference put in the
program's place with every matrix product's operands rounded to float8_e4m3,
the next precision below the bfloat16 the cells state. Kept here at a size a
test run can hold; on the chip it was read at the cells' own sizes
(``tools/calibrate.py``, readings in PERF.md). The benchmark's own runs never
run it."""
import argparse

import pytest

from benchmark import run as bench_run
from benchmark.lib import reference_gpt2, traffic

SMALL = dict(n_layer=4, n_embd=256, n_head=4, n_positions=128,
             vocab_size=2000, padded_vocab=2048)
SEED = 2**31 + 9


def cell_at_small_size(workload, **mix):
    cell = bench_run.load_cell(argparse.Namespace(
        workload=workload, seed=SEED, seconds=1, trace=0, rehearse=True))
    cell.sizes.update(SMALL)
    cell.traffic.update(mix)
    return cell


@pytest.mark.parametrize("workload", ["gpt2m-train-s1024",
                                      "gpt2l-train-s1024"])
def test_fp8_control_fails_a_train_cell(workload):
    from benchmark.drivers import train_step as drv

    cell = cell_at_small_size(workload, batch=4, seq=127, ring=3)
    ring = traffic.train_batches(cell.traffic, SEED, SMALL["vocab_size"])
    ref = drv.reference_readings(cell, ring)
    control = drv.reference_readings(
        cell, ring, operands=reference_gpt2.fp8_operands)

    def over(readings):
        return [name for name, (value, _) in drv.numbers(readings, ref).items()
                if name in cell.limits and not value <= cell.limits[name]]

    assert over(ref) == []
    assert "grad_sum_gap" in over(control)
    # and the planted faults: each fails a number too
    assert over(drv.reference_readings(cell, ring, frozen=True))
    assert over(drv.reference_readings(cell, ring, rows=slice(0, 2)))
