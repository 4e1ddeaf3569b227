"""Seconds spent making the model's leaves: ``dur_ns`` summed over the
``create_parameter`` spans that closed before the first ``compile_train_step``
step opened, from the program's ring."""
from ..lib import program_setup


def read(record):
    got = program_setup.records()
    if got is None or not got.params:
        return None
    return sum(p["dur_ns"] for p in got.params) / 1e9
