"""Seconds the first step spent lowering the programs it built to MLIR:
``lower_s`` summed over the ``compile`` events sited inside the first
``compile_train_step`` step, from the program's ring."""
from ..lib import program_setup


def read(record):
    return program_setup.step_sum("lower_s")
