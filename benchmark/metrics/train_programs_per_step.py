"""Compiled programs launched on the device per training step, counted on
the trace's ``XLA Modules`` line over the traced steps."""


def read(record):
    trace, steps = record["trace"], record["window"].get("traced_steps")
    if not trace or not steps or not trace["launches"]:
        return None
    return sum(trace["launches"].values()) / trace["chips"] / steps
