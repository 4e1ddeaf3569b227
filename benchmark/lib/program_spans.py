"""The training step's own spans, read back from the program's flight
recorder (``paddle_tpu.profiler.trace``): ``compile_train_step`` opens a root
span per call with ``/args``, ``/launch`` and ``/writeback`` inside it, and
keeps each as a ``span`` event (``start_ns`` on the profiler's clock,
``dur_ns``, ``id``, ``parent``) in a bounded ring, traced or not. A program
that records no such events (the ring off, or a commit from before the spans)
gives every reader here nothing to read, and the reader returns ``None``.
"""
ROOT = "compile_train_step"
LAUNCH = ROOT + "/launch"


def span_events():
    try:
        from paddle_tpu.profiler import trace
    except ImportError:
        return []
    return trace.events(kind="span")


def window_steps(record):
    """[(root attrs, launch attrs)] of the window's steps, oldest first: the
    last ``record["window"]["steps"]`` root spans (nothing calls the step
    after the window closes) with the launch each one holds. ``None`` where
    the ring holds no root span, or a root lacks its launch."""
    events = span_events()
    n = record["window"].get("steps")
    roots = [e.attrs for e in events if e.site == ROOT]
    if not roots or not n:
        return None
    roots = roots[-n:]
    launch = {e.attrs["parent"]: e.attrs for e in events if e.site == LAUNCH}
    if any(r["id"] not in launch for r in roots):
        return None
    return [(r, launch[r["id"]]) for r in roots]


def mean_ms(values_ns):
    return sum(values_ns) / len(values_ns) / 1e6
