"""The program's own set-up, read back from its flight recorder
(``paddle_tpu.profiler.trace``): a ``create_parameter`` span round each leaf a
layer makes, and one ``compile`` event a program built, sited at the span open
round it (``span`` = that span's id) and carrying its outermost trace
(``trace_s``), its lowering (``lower_s``) and its compile, or key hashing and
cache fetch where ``cache_hit`` (``seconds``). The first step is the ring's
root ``compile_train_step`` span of step 0 with every span whose parent chain
leads to it.

``records()`` is ``None`` where the ring holds no such root (a program from
before these records, or the ring off), where the ring has dropped events (it
keeps the newest ``FLAGS_trace_ring_size``, so set-up's records would be short
or gone), or where a ``compile`` event lacks ``trace_s`` (a program from before
the phases were recorded). A reader returns ``None`` then, and where its
reading does not exist.
"""
import types

from . import program_spans

PARAM = "create_parameter"


def ring():
    """(events oldest first, the ring's size); nothing without the program."""
    try:
        from paddle_tpu.core import flags
        from paddle_tpu.profiler import trace
    except ImportError:
        return [], 0
    return trace.events(), int(flags.flag("trace_ring_size"))


def records():
    """``root`` (the first step's root span's attrs), ``step_compiles`` (the
    ``compile`` events sited inside the first step), ``params`` (the
    ``create_parameter`` spans closed before the first step opened) and
    ``built`` (the ``compile`` events sited inside a ``create_parameter`` span
    or inside the first step: the programs set-up builds or fetches), or
    ``None``."""
    events, size = ring()
    if not events or len(events) >= size:
        return None
    spans = [e for e in events if e.kind == "span"]
    compiles = [e.attrs for e in events if e.kind == "compile"]
    root = next((e.attrs for e in spans
                 if e.site == program_spans.ROOT and e.step == 0), None)
    if root is None or any("trace_s" not in c for c in compiles):
        return None
    parent = {e.attrs["id"]: e.attrs["parent"] for e in spans}
    param_ids = {e.attrs["id"] for e in spans if e.site == PARAM}

    def inside(span_id, tops):
        while span_id is not None and span_id not in tops:
            span_id = parent.get(span_id)
        return span_id is not None

    return types.SimpleNamespace(
        root=root,
        step_compiles=[c for c in compiles
                       if inside(c.get("span"), {root["id"]})],
        params=[e.attrs for e in spans if e.site == PARAM
                and e.attrs["start_ns"] + e.attrs["dur_ns"]
                <= root["start_ns"]],
        built=sum(inside(c.get("span"), param_ids | {root["id"]})
                  for c in compiles))


def step_sum(field):
    """Seconds of ``field`` summed over the first step's ``compile`` events."""
    got = records()
    return None if got is None else sum(c[field] for c in got.step_compiles)
