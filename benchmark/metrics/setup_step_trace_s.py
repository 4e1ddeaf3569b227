"""Seconds the first step spent tracing the programs it built: ``trace_s``
(each program's outermost trace) summed over the ``compile`` events sited
inside the first ``compile_train_step`` step, from the program's ring."""
from ..lib import program_setup


def read(record):
    return program_setup.step_sum("trace_s")
