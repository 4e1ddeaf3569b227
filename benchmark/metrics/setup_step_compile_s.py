"""Seconds the first step spent compiling the programs it built, or hashing
their keys and fetching them where the persistent cache held them: the
``compile`` events' ``seconds`` summed over those sited inside the first
``compile_train_step`` step, from the program's ring."""
from ..lib import program_setup


def read(record):
    return program_setup.step_sum("seconds")
