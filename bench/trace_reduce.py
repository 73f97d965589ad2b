"""From a profiler trace to the numbers the per-layer metrics read.

Two stages. `extract` reads an `.xplane.pb` (the profiler's XSpace proto)
and keeps three lists of plain records: the device's op events (with the
scope path and HLO category the compiler attached), the device's program
executions, and the harness's own host spans (`dispatch`, `fetch`,
`check`). `summarize` reduces those records alone, so it can be checked
on a small recorded fixture.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

HOST_SPANS = ("dispatch", "fetch", "check")
# ops that only contain others (a cond's or a loop's body ops are events of
# their own): they count towards busy time and are left out of per-op time
CONTAINERS = ("conditional", "while")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def _xplane_classes():
    """The XSpace message class of the profiler's trace format (the fields
    of tsl's xplane.proto that this reduction reads), built here so that
    reading a trace needs only the protobuf runtime."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                            package="bench_xplane",
                                            syntax="proto3")
    I64, U64, STR, DBL, MSG = (F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING,
                               F.TYPE_DOUBLE, F.TYPE_MESSAGE)
    p = ".bench_xplane."

    def msg(name, fields, parent=None):
        """Fields (name, number, type); a message-typed field names its
        type and is repeated unless it is a map entry's value."""
        m = (parent.nested_type if parent else fd.message_type).add(
            name=name)
        for fname, num, ftype in fields:
            f = m.field.add(name=fname, number=num, label=F.LABEL_OPTIONAL)
            if isinstance(ftype, str):
                f.type, f.type_name = MSG, p + ftype
                if fname != "value":
                    f.label = F.LABEL_REPEATED
            else:
                f.type = ftype
        return m

    msg("XStat", [("metadata_id", 1, I64), ("double_value", 2, DBL),
                  ("uint64_value", 3, U64), ("int64_value", 4, I64),
                  ("str_value", 5, STR), ("ref_value", 7, U64)])
    msg("XEvent", [("metadata_id", 1, I64), ("offset_ps", 2, I64),
                   ("duration_ps", 3, I64)])
    msg("XLine", [("name", 2, STR), ("timestamp_ns", 3, I64),
                  ("events", 4, "XEvent")])
    msg("XEventMetadata", [("name", 2, STR), ("stats", 5, "XStat")])
    msg("XStatMetadata", [("name", 2, STR)])
    plane = msg("XPlane", [("name", 2, STR), ("lines", 3, "XLine"),
                           ("event_metadata", 4, "XPlane.EventMetadataEntry"),
                           ("stat_metadata", 5, "XPlane.StatMetadataEntry")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = msg(entry, [("key", 1, I64), ("value", 2, value)], parent=plane)
        e.options.map_entry = True
    msg("XSpace", [("planes", 1, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _stat_value(st, names):
    if st.str_value:
        return st.str_value
    if st.ref_value:
        return names.get(st.ref_value, "")
    return st.int64_value or st.uint64_value or st.double_value


def extract(path: str) -> Dict[str, List]:
    """{"ops": [[start_ns, dur_ns, name, scope, category, device, flops]],
        "modules": [[start_ns, dur_ns, name, device]],
        "spans": [[start_ns, dur_ns, name]]} from one xplane file. An op's
    scope is the `tf_op` path the compiler attached (the named scopes of
    the jitted function), its category XLA's `hlo_category` and its flops
    the compiler's count for one execution."""
    space = _xplane_classes()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops, modules, spans = [], [], []
    for plane in space.planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        info = {}
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                md = plane.event_metadata[ev.metadata_id]
                start = line.timestamp_ns + ev.offset_ps / 1000.0
                dur = ev.duration_ps / 1000.0
                if not device:
                    if md.name in HOST_SPANS:
                        spans.append([start, dur, md.name])
                elif line.name == MODULES_LINE:
                    modules.append([start, dur, md.name, plane.name])
                else:
                    if ev.metadata_id not in info:
                        st = {names.get(s.metadata_id, ""):
                              _stat_value(s, names) for s in md.stats}
                        info[ev.metadata_id] = (
                            md.name.split(" = ")[0].lstrip("%"),
                            str(st.get("tf_op", "")),
                            str(st.get("hlo_category", "")),
                            float(st.get("flops") or 0))
                    name, scope, cat, flops = info[ev.metadata_id]
                    ops.append([start, dur, name, scope, cat, plane.name,
                                flops])
    return {"ops": ops, "modules": modules, "spans": spans}


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _name_gap(s: float, e: float, spans) -> str:
    """The host span that covers most of the gap [s, e), or "other"."""
    best, best_ns = "other", 0.0
    for ss, sd, name in spans:
        ov = min(e, ss + sd) - max(s, ss)
        if ov > best_ns:
            best, best_ns = name, ov
    return best


def summarize(ev: Dict[str, List], module_key: str = "bench_step",
              top: int = 10) -> Dict:
    """Busy and idle time of the device over the traced window, per-op
    device time with scope, category, flops and name, the step program's
    executions, and the longest idle gaps named by what the host was
    doing.

    The window runs from the first host `dispatch` span, or the first
    step execution where that is earlier (the device's clock is aligned
    to the host's only to a millisecond or so), to the end of the last
    `fetch` span or of the last step execution. With several devices,
    busy time is the mean over devices."""
    spans = sorted(ev["spans"])
    steps = sorted(m for m in ev["modules"] if module_key in m[2])
    starts = [s for s, _, n in spans if n == "dispatch"]
    ends = [s + d for s, d, n in spans if n == "fetch"]
    if not starts or not ends or not steps:
        return {}
    lo = min(starts + [steps[0][0]])
    hi = max(ends + [m[0] + m[1] for m in steps])
    devices = sorted({o[5] for o in ev["ops"]})
    busy_ns = 0.0
    gaps = []
    for dev in devices:
        busy = _clip(union([(o[0], o[0] + o[1]) for o in ev["ops"]
                            if o[5] == dev]), lo, hi)
        busy_ns += sum(e - s for s, e in busy) / len(devices)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(e - s, s, e) for s, e in zip(edges[::2], edges[1::2])
                 if e > s]
    ops = [o for o in ev["ops"]
           if lo <= o[0] < hi and o[4] not in CONTAINERS]
    by_kind: Dict[str, float] = {}
    for o in ops:
        k = f"{short_scope(o[3]) or '-'} [{o[4]}]"
        by_kind[k] = by_kind.get(k, 0.0) + o[1]
    gaps.sort(reverse=True)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "ops": [[o[1] * 1e-9, o[3], o[4], o[6], o[2]] for o in ops],
        "steps_s": [m[1] * 1e-9 for m in steps],
        "device_ops": sorted(([k, v * 1e-9] for k, v in by_kind.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_name_gap(s, e, spans), d * 1e-9]
                      for d, s, e in gaps[:top]],
    }


def short_scope(scope: str) -> str:
    """A scope path without its leading `jit(...)` frames."""
    parts = [p for p in scope.split("/") if p and not p.startswith("jit(")]
    return "/".join(parts)


def site_of(scope: str, sites) -> Optional[str]:
    """The protected site a scope path lies under, if any."""
    for part in scope.split("/"):
        if part in sites:
            return part
    return None
