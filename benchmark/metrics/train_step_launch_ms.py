"""Mean duration of the ``compile_train_step/launch`` span (the jitted call
alone, until it returns) over the window's steps, from the program's ring."""
from ..lib import program_spans


def read(record):
    steps = program_spans.window_steps(record)
    if not steps:
        return None
    return program_spans.mean_ms([launch["dur_ns"] for _, launch in steps])
