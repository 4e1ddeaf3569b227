"""Seconds of the first ``compile_train_step`` step's root span, from the
program's ring. What the step's trace, lowering and compile-or-fetch
(``setup_step_*_s``) leave of it is the optimizer state's set-up, leaf
gathering and the first dispatch."""
from ..lib import program_setup


def read(record):
    got = program_setup.records()
    return None if got is None else got.root["dur_ns"] / 1e9
