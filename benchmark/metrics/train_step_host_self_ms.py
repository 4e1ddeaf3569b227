"""Mean of the root ``compile_train_step`` span's duration minus its
``/launch`` child's, over the window's steps: the Python the framework adds
around ``jax.jit`` (gathering the leaves, lr and key; writing the results
back; the root's own time). With ``train_step_launch_ms`` it sums to the root
span, which ``train_dispatch_ms`` times from outside."""
from ..lib import program_spans


def read(record):
    steps = program_spans.window_steps(record)
    if not steps:
        return None
    return program_spans.mean_ms(
        [root["dur_ns"] - launch["dur_ns"] for root, launch in steps])
