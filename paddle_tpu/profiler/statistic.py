"""Statistics report over collected profiler events.

Reference analogue: python/paddle/profiler/profiler_statistic.py
(StatisticData + _build_table: Device/Overview/Operator/Memory summaries
over the NodeTrees event tree). Here the host-span list is flat (XLA owns
the device-side tree via XPlane), so the report classifies spans by name
into the reference's views and aggregates totals/averages/percentiles.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional

__all__ = ["StatisticData", "build_summary_report", "device_view",
           "build_device_report", "classify_scope"]

_FRAMEWORK_PREFIXES = ("dataloader", "optimizer", "backward", "forward", "step",
                       "compile_train_step")


class StatisticData:
    def __init__(self, events: List[dict]):
        self.events = events

    def _agg(self, names=None):
        agg: Dict[str, dict] = {}
        for e in self.events:
            if names is not None and e["name"] not in names:
                continue
            a = agg.setdefault(
                e["name"], {"calls": 0, "total_us": 0.0, "max_us": 0.0, "min_us": float("inf")}
            )
            a["calls"] += 1
            a["total_us"] += e["dur"]
            a["max_us"] = max(a["max_us"], e["dur"])
            a["min_us"] = min(a["min_us"], e["dur"])
        return agg

    def overview(self):
        """Totals per category — the reference's Overview Summary."""
        cats = {"Framework": 0.0, "Operator": 0.0, "UserDefined": 0.0}
        for e in self.events:
            name = e["name"].lower()
            if any(name.startswith(p) for p in _FRAMEWORK_PREFIXES):
                cats["Framework"] += e["dur"]
            elif name.isidentifier() and name == name.lower():
                cats["Operator"] += e["dur"]
            else:
                cats["UserDefined"] += e["dur"]
        return cats

    def operator_summary(self):
        return self._agg()


def _fmt_table(title, header, rows):
    widths = [max(len(str(h)), *(len(str(r[i])) for r in rows)) if rows else len(str(h))
              for i, h in enumerate(header)]
    sep = "-" * (sum(widths) + 2 * len(widths))
    out = [sep, title, sep,
           "  ".join(str(h).ljust(w) for h, w in zip(header, widths))]
    for r in rows:
        out.append("  ".join(str(c).ljust(w) for c, w in zip(r, widths)))
    out.append(sep)
    return "\n".join(out)


def build_summary_report(events, sorted_by="total", time_unit="ms") -> str:
    """The reference's _build_table equivalent: Overview + Operator views."""
    data = StatisticData(events)
    div = {"ms": 1e3, "us": 1.0, "s": 1e6}[time_unit]

    cats = data.overview()
    total = sum(cats.values()) or 1.0
    over_rows = [
        (k, f"{v/div:.3f}", f"{100*v/total:.1f}%")
        for k, v in sorted(cats.items(), key=lambda kv: -kv[1])
    ]
    parts = [_fmt_table("Overview Summary", ("Category", f"Total({time_unit})", "Ratio"), over_rows)]

    agg = data.operator_summary()
    keyfns = {
        "total": lambda a: a["total_us"],
        "max": lambda a: a["max_us"],
        "calls": lambda a: a["calls"],
        "avg": lambda a: a["total_us"] / a["calls"],
    }
    keyfn = keyfns[sorted_by]
    op_rows = [
        (
            name[:48],
            a["calls"],
            f"{a['total_us']/div:.3f}",
            f"{a['total_us']/a['calls']/div:.3f}",
            f"{a['max_us']/div:.3f}",
            f"{a['min_us']/div:.3f}",
        )
        for name, a in sorted(agg.items(), key=lambda kv: -keyfn(kv[1]))
    ]
    parts.append(
        _fmt_table(
            "Operator Summary",
            ("Name", "Calls", f"Total({time_unit})", f"Avg({time_unit})",
             f"Max({time_unit})", f"Min({time_unit})"),
            op_rows,
        )
    )
    return "\n\n".join(parts)


# ---------------------------------------------------------------------------
# Device view: where the device time of a traced stretch went, read from the
# ``.xplane.pb`` that jax.profiler wrote.
#
# Where the scope lives (looked at on one v5e trace and one XLA:CPU trace,
# jax 0.9). On the TPU each event of a ``/device:TPU:n`` plane's ``XLA Ops``
# line is named by its HLO text (``%fusion.12 = ...``, which holds no
# op_name); the op_name that jax.named_scope wrote
# (``jit(step_fn)/transpose(jvp(forward))/GPTForPretraining/layers.3/attn/...``)
# is the ``tf_op`` stat of the event's METADATA (XEventMetadata.stats), beside
# ``hlo_category``, ``flops`` and ``source``. On the CPU the ops are events of
# the ``/host:CPU`` thread lines with ``hlo_module`` / ``hlo_op`` stats, and
# the op_name is in the module's serialized HloProto, the ``Hlo Proto`` stat
# of the ``/host:metadata`` plane's event metadata. jax.profiler.ProfileData
# gives planes, lines, events and an event's own stats, but no metadata
# stats, so those two maps are read from the file's protobuf wire format
# (XSpace / XPlane / XEventMetadata / XStat field numbers, fixed since the
# format was published).
#
# A fusion carries ONE op_name, its root op's: time inside a fusion that
# belongs to a neighbouring layer is booked to the root's layer. Events with
# no op_name at all (copy-start/-done, async slices, the compiler's own
# data formatting) are booked to ``unscoped``, never spread.
# ---------------------------------------------------------------------------
SECTIONS = ("forward", "backward", "recompute", "loss", "grad_clip",
            "optimizer", "other", "unscoped")
_SCOPES = ("forward", "loss", "grad_clip", "optimizer")
_TRANSFORM = re.compile(r"(\w+)\((.*)\)")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_NUMBERING = re.compile(r"[.\d]+$")
_LAYER_DEPTH = 2  # below the root: layers.*/attn, embeddings/word_embeddings


def _varint(buf, i):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"xplane: unsupported wire type {wire}")
        yield key >> 3, v


def _metadata_stats(path, wanted):
    """{plane name: {event metadata name: {stat name: bytes}}} for the string
    / bytes stats in ``wanted`` of every XEventMetadata in the file."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:  # XSpace.planes
            continue
        name, stat_names, metas = "", {}, []
        for pf, pv in _fields(plane):
            if pf == 2:  # XPlane.name
                name = bytes(pv).decode()
            elif pf == 5:  # stat_metadata map entry: value = XStatMetadata
                for ef, ev in _fields(pv):
                    if ef == 2:
                        d = dict(_fields(ev))
                        stat_names[d.get(1, 0)] = bytes(d.get(2, b"")).decode()
            elif pf == 4:  # event_metadata map entry: value = XEventMetadata
                metas.extend(ev for ef, ev in _fields(pv) if ef == 2)
        keep = {i for i, n in stat_names.items() if n in wanted}
        found = {}
        for meta in metas:
            mname, stats = "", {}
            for mf, mv in _fields(meta):
                if mf == 2:  # XEventMetadata.name
                    mname = bytes(mv).decode()
                elif mf == 5:  # XEventMetadata.stats: XStat
                    d = dict(_fields(mv))
                    if d.get(1) not in keep:
                        continue
                    if 7 in d:  # ref_value: a string kept as a stat name
                        value = stat_names.get(d[7], "").encode()
                    else:  # str_value / bytes_value
                        value = bytes(d.get(5, d.get(6, b"")))
                    stats[stat_names[d[1]]] = value
            if stats:
                found[mname] = stats
        if found:
            out[name] = found
    return out


def _hlo_op_names(proto):
    """(module name, {instruction name: op_name}) of a serialized HloProto."""
    module = next((v for f, v in _fields(memoryview(proto)) if f == 1), None)
    name, ops = "", {}
    if module is None:
        return name, ops
    for f, v in _fields(module):
        if f == 1:  # HloModuleProto.name
            name = bytes(v).decode()
        elif f == 3:  # computations
            for cf, cv in _fields(v):
                if cf != 2:  # HloComputationProto.instructions
                    continue
                d = dict(_fields(cv))
                meta = dict(_fields(d[7])) if 7 in d else {}
                if 2 in meta:  # OpMetadata.op_name
                    ops[bytes(d[1]).decode()] = bytes(meta[2]).decode()
    return name, ops


def _stable_name(event_name):
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``: the kind of op
    or a kernel's own name, without XLA's numbering."""
    return _NUMBERING.sub("", event_name.split(" = ", 1)[0].lstrip("%"))


def _self_seconds(events):
    """[(self seconds, payload)] of (start, end, payload) events on one
    line: an event's time minus what its nested events cover."""
    out, stack = [], []  # stack of [end, self_ns, payload]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, self_ns, payload = stack.pop()
            out.append((self_ns / 1e9, payload))

    for start, end, payload in sorted(events, key=lambda e: (e[0], -e[1])):
        close(start)
        if stack:  # nested: the parent gives this stretch up
            end = min(end, stack[-1][0])
            stack[-1][1] -= end - start
        stack.append([end, end - start, payload])
    close(float("inf"))
    return out


def classify_scope(op_name: str, depth: int = _LAYER_DEPTH):
    """(section, layer path or None, direction) of one op_name.

    ``jit(step_fn)/transpose(jvp(forward))/GPT/layers.3/attn/jit(linear)/dot_general``
    -> ("backward", "layers.*/attn", "backward"). The section is the first
    component that names one of compile_train_step's scopes under any
    transforms; ``transpose`` among them means backward, a later
    ``rematted_computation`` means recompute. The layer path is the plain
    scope names that follow (a ``jit(...)``, ``checkpoint`` or
    ``rematted_computation`` among them is stepped over, so that a scope
    opened inside a jitted op, or a recomputed sub-layer, keeps its name;
    where a section's name comes again, a trace inside names its path from
    the root anew), below the root layer, indices collapsed, ``depth`` names
    deep."""
    parts = op_name.rstrip(":").split("/")
    for i, part in enumerate(parts):
        transforms, inner = [], part
        while (m := _TRANSFORM.fullmatch(inner)):
            transforms.append(m[1])
            inner = m[2]
        if inner not in _SCOPES or "jit" in transforms:
            continue
        backward = "transpose" in transforms
        direction = "backward" if backward else "forward"
        if inner != "forward":
            return inner, None, direction
        rest = parts[i + 1:-1]  # the last component is the primitive
        if "rematted_computation" in rest:
            section = "recompute"
        else:
            section = direction
        layers = []
        for comp in rest:
            if "(" in comp:
                while (m := _TRANSFORM.fullmatch(comp)):
                    comp = m[2]
                if comp in _SCOPES:  # a trace inside (a recomputed segment's
                    layers = []      # backward) names its path from the root
                continue
            if comp in ("checkpoint", "rematted_computation"):
                continue
            layers.append(re.sub(r"(^|\.)\d+$", r"\1*", comp))
        below_root = layers[1:1 + depth]
        path = "/".join(below_root) if below_root else (
            layers[0] if layers else None)
        return section, path, direction
    return "other", None, "forward"


def _op_lines(data, hlo_ops):
    """Per line that holds device ops: [(start_ns, end_ns, (event name,
    op_name or None))]. TPU: the XLA Ops line of each device plane, op_name
    looked up by the caller; CPU: events with an ``hlo_op`` stat."""
    lines = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    lines.append((plane.name, [
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         (ev.name, None)) for ev in line.events]))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = []
                for ev in line.events:
                    st = dict(ev.stats)
                    if "hlo_op" not in st:
                        continue
                    scope = hlo_ops.get(st.get("hlo_module"), {}).get(
                        st["hlo_op"])
                    evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                (st["hlo_op"], scope)))
                if evs:
                    lines.append((plane.name, evs))
    return lines


def device_view(paths: Iterable[str],
                layer_depth: int = _LAYER_DEPTH) -> Optional[dict]:
    """Seconds of device time by section, by layer path (forward / backward;
    ``layer_depth`` names below the root) and by named kernel, over the
    ``.xplane.pb`` files of one traced stretch.
    Each op's self time is booked once, so the sections sum to ``busy_s``
    (chip-seconds, or thread-seconds on the CPU). None without device ops."""
    from jax.profiler import ProfileData

    sections = dict.fromkeys(SECTIONS, 0.0)
    layers = defaultdict(lambda: {"forward": 0.0, "backward": 0.0})
    kernels = defaultdict(float)
    unscoped_ops = defaultdict(float)
    busy = window = 0.0
    n_lines = 0
    for path in paths:
        meta = _metadata_stats(path, ("tf_op", "Hlo Proto"))
        hlo_ops = dict(_hlo_op_names(st["Hlo Proto"])
                       for st in meta.get("/host:metadata", {}).values()
                       if "Hlo Proto" in st)
        for plane, events in _op_lines(ProfileData.from_file(path), hlo_ops):
            tf_ops = meta.get(plane, {})
            n_lines += 1
            window += (max(e[1] for e in events)
                       - min(e[0] for e in events)) / 1e9
            for secs, (name, scope) in _self_seconds(events):
                busy += secs
                if scope is None and name in tf_ops:
                    scope = tf_ops[name].get("tf_op", b"").decode()
                if 'custom_call_target="tpu_custom_call"' in name:
                    kernels[_stable_name(name)] += secs
                if not scope:
                    sections["unscoped"] += secs
                    unscoped_ops[_stable_name(name)] += secs
                    continue
                section, layer, direction = classify_scope(scope, layer_depth)
                sections[section] += secs
                if layer is not None:
                    layers[layer][direction] += secs
    if not n_lines:
        return None
    return {"devices": n_lines, "busy_s": busy, "window_s": window,
            "sections": sections, "layers": dict(layers),
            "kernels": dict(kernels), "unscoped_ops": dict(unscoped_ops)}


def build_device_report(view: dict, top: int = 24) -> str:
    """The device view as tables: ModelView by section and by layer,
    KernelView by named kernel; shares are of busy time."""
    busy = view["busy_s"] or 1.0

    def row(name, secs, *more):
        return (name, f"{secs:.6f}", f"{100 * secs / busy:.2f}%", *more)

    title = (f"Device Summary: busy {view['busy_s']:.6f} s of a "
             f"{view['window_s']:.6f} s window over {view['devices']} "
             "device line(s); a fusion is booked to its root op's scope")
    parts = [_fmt_table(
        title, ("Section", "Seconds", "Share of busy"),
        [row(k, v) for k, v in view["sections"].items()
         if v or k == "unscoped"])]
    by_layer = sorted(view["layers"].items(),
                      key=lambda kv: -sum(kv[1].values()))[:top]
    parts.append(_fmt_table(
        "Model Summary (layer path, indices collapsed)",
        ("Layer", "Seconds", "Share of busy", "Forward", "Backward"),
        [row(k, sum(v.values()), f"{v['forward']:.6f}",
             f"{v['backward']:.6f}") for k, v in by_layer]))
    parts.append(_fmt_table(
        "Kernel Summary (named Pallas kernels)",
        ("Kernel", "Seconds", "Share of busy"),
        [row(k, v) for k, v in sorted(view["kernels"].items(),
                                      key=lambda kv: -kv[1])]))
    loose = sorted(view["unscoped_ops"].items(), key=lambda kv: -kv[1])[:8]
    parts.append(_fmt_table(
        "Unscoped ops (no op_name in the trace)",
        ("Op", "Seconds", "Share of busy"), [row(k, v) for k, v in loose]))
    return "\n\n".join(parts)
