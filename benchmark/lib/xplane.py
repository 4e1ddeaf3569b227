"""From a profiler trace (``.xplane.pb``) to busy / idle, per-op device time
and idle gaps labelled by what the host was doing.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO op, named by its HLO text (``%fusion.12 = ...``), and
their ``XLA Modules`` line one event per launched program. Asynchronous
copies sit on a line of their own and do not count as busy. Host spans (``jax.profiler.TraceAnnotation``) are
events on the ``/host:CPU`` plane's thread lines, on the same clock.
"""
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
ARRAY = re.compile(r"[a-z][a-z0-9]*\[[\d,]*\]")


def short_name(event_name):
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def stable_name(name):
    """``fusion.123`` -> ``fusion``: XLA's numbering changes with any edit of
    the program; the kind of op and a kernel's own name do not."""
    return re.sub(r"[.\d]+$", "", short_name(name)) or name


def kernel_operands(event_name):
    """The operands of a custom call as HLO writes them, layouts dropped:
    ``('bf16[128,1024,64]', 'bf16[128,1024,64]', ...)``."""
    call = event_name.split(" custom-call(", 1)
    if len(call) < 2:
        return ()
    return tuple(ARRAY.findall(call[1].split("), custom_call_target", 1)[0]))


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_events(data):
    """{plane name: [(start_ns, end_ns, op name)]} sorted by start."""
    out = {}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = sorted(
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events)
    return out


def module_launches(data):
    """{program name: launches} over all chips, from ``XLA Modules``."""
    out = defaultdict(int)
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for ev in line.events:
                        out[stable_name(re.sub(r"\(.*\)$", "", ev.name))] += 1
    return dict(out)


def host_spans(data, names):
    """[(start_ns, end_ns, name)] of the benchmark's own spans."""
    names = set(names)
    out = []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            out.extend((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in line.events if ev.name in names)
    return sorted(out)


def union(intervals):
    """Merged [start, end] intervals of sorted (start, end, ...) tuples."""
    merged = []
    for iv in intervals:
        s, e = iv[0], iv[1]
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def label_gap(start, end, spans):
    """The span that covers most of [start, end], else ``untracked``."""
    best, name = 0.0, "untracked"
    for s, e, n in spans:
        if s >= end:
            break
        cover = min(e, end) - max(s, start)
        if cover > best:
            best, name = cover, n
    return name


def reduce(data, span_names=()):
    """The trace's facts, times in seconds; ``None`` without a device plane.

    busy_s / window_s are averaged over the chips; the window runs from the
    first device op's start to the last one's end on each chip."""
    per_chip = device_events(data)
    if not per_chip:
        return None
    spans = host_spans(data, span_names)
    busy, window = [], []
    ops = defaultdict(float)
    kernels = defaultdict(float)  # (stable name, operands) -> ns
    gaps = defaultdict(float)
    for events in per_chip.values():
        merged = union(events)
        busy.append(sum(e - s for s, e in merged))
        window.append(merged[-1][1] - merged[0][0])
        for s, e, name in events:
            ops[stable_name(name)] += e - s
            if KERNEL_MARK in name:  # a Pallas / Mosaic kernel
                kernels[stable_name(name), kernel_operands(name)] += e - s
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps[label_gap(e0, s1, spans)] += s1 - e0
    n = len(per_chip)
    return {
        "chips": n,
        "busy_s": sum(busy) / n / 1e9,
        "window_s": sum(window) / n / 1e9,
        "op_seconds": {k: v / n / 1e9 for k, v in ops.items()},
        "kernels": [{"name": k, "operands": list(o), "seconds": v / n / 1e9}
                    for (k, o), v in kernels.items()],
        "gap_seconds": {k: v / n / 1e9 for k, v in gaps.items()},
        "spans": len(spans),
        "launches": module_launches(data),
    }


def reduce_file(path, span_names=()):
    return reduce(load(path), span_names)


def idle_percent(reduced):
    """1 - the union of device-op intervals over the traced window, in %."""
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def kernel_time(reduced, operands):
    """Device seconds of the Pallas kernels that take every array shape in
    ``operands`` (``'[128,1024,64]'``, as often as it is listed; a kernel may
    take more). A kernel is known by what it is handed: the program may give
    it no name of its own."""
    total = 0.0
    for k in reduced["kernels"]:
        shapes = [o[o.index("["):] for o in k["operands"]]
        if all(shapes.count(s) >= operands.count(s) for s in set(operands)):
            total += k["seconds"]
    return total


def breakdown(reduced, top=10):
    """``breakdown`` of the result line: the ops that took most device time
    under stable names, and idle time by what the host was doing."""
    def first(d):
        return [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": first(reduced["op_seconds"]),
            "idle_gaps": first(reduced["gap_seconds"])}
