"""Every seed is dealt the same amount of work, with other token ids."""
import json
import os

import numpy as np

from benchmark.lib import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BIG = 2**31 + 12345  # more than 32 signed bits hold


def mix(cell):
    with open(os.path.join(HERE, "..", "workloads", cell + ".json")) as f:
        return json.load(f)["traffic"]


def test_train_batches_rows_all_differ_and_repeat_by_seed():
    m = dict(mix("gpt2m-train-s1024"), batch=4, seq=32, ring=3)
    a = traffic.train_batches(m, BIG, 50257)
    assert [x.shape for x in a] == [(4, 33)] * 3 and a[0].dtype == np.int32
    rows = np.concatenate(a)
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert int(rows.max()) < 50257
    b = traffic.train_batches(m, BIG, 50257)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    c = traffic.train_batches(m, BIG + 1, 50257)
    assert not np.array_equal(a[0], c[0])
