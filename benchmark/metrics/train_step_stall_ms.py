"""Over the traced stretch's steps (the window's first ``traced_steps``; the
profiler's stop after them is a pause of the window's, not a stall), the
longest interval between the starts of consecutive root spans minus the
median interval. A clean run reads a millisecond or two; a run whose host
stalled reads the stall."""
import statistics

from ..lib import program_spans


def read(record):
    steps = program_spans.window_steps(record)
    traced = record["window"].get("traced_steps")
    if not steps or not traced:
        return None
    starts = [root["start_ns"] for root, _ in steps[:traced]]
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    if len(gaps) < 2:
        return None
    return (max(gaps) - statistics.median(gaps)) / 1e6
