"""Reduction of a ``jax.profiler`` trace to the device's busy and idle time.

Two steps, so that the arithmetic can be checked on a small recorded trace
(``tests/data``) without a chip:

1. ``load_trace`` reads the ``.xplane.pb`` the profiler wrote, with nothing
   but JAX, into plain lists: for every device plane the operations that ran
   on it, and from the host planes the benchmark's own query annotations.
2. ``reduce_trace`` takes those lists. Busy is the union of the intervals in
   which an operation ran on a device, inside the traced span; idle is what is
   left of the span. Every idle gap is split at the query boundaries and named
   ``in_query`` (some query was in flight) or ``between_queries``.

Times are nanoseconds on the profiler's clock. Query intervals come from the
driver on the host's monotonic clock; one annotation that both clocks saw
gives the offset between them.
"""

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"          # one event per operation the chip executed
MODULES_LINE = "XLA Modules"  # one event per program run: jit_<fn>(<fingerprint>)
PROGRAMS_IN_TOP = 3           # of the breakdown's ten device entries
Interval = Tuple[int, int]    # start_ns, end_ns


def load_trace(trace_dir: str, annotation: str) -> Dict:
    """``{"devices": {plane: [[name, start_ns, dur_ns], ...]}, "modules": the
    same for whole programs, "annotations": [[start_ns, dur_ns], ...]}`` from
    the newest trace under ``trace_dir``; raises if the profiler wrote none."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"the profiler wrote no trace under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices: Dict[str, List] = {}
    modules: Dict[str, List] = {}
    annotations: List = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                into = {OPS_LINE: devices, MODULES_LINE: modules}.get(line.name)
                if into is not None:
                    into.setdefault(plane.name, []).extend(
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                annotations.extend(
                    [int(e.start_ns), int(e.duration_ns)]
                    for e in line.events if e.name == annotation)
    annotations.sort()
    return {"devices": devices, "modules": modules,
            "annotations": annotations,
            "file_bytes": os.path.getsize(files[-1])}


def op_label(hlo_text: str) -> str:
    """``%sort.11 = (u32[...]) sort(...)`` is ``sort.11``: the trace names an
    operation by its whole HLO line."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def labelled_ops(ops: Sequence, modules: Sequence) -> List:
    """Every operation as ``<program>/<instruction>``, the program being the
    module event that holds the operation's start. Instruction names repeat
    from program to program, and the engine's programs are all ``jit_fn``
    with a fingerprint of their own."""
    spans = sorted((s, s + d, name) for name, s, d in modules)
    starts = [s for s, _e, _n in spans]
    out = []
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        program = spans[i][2] if i >= 0 and s < spans[i][1] else "?"
        out.append([f"{program}/{op_label(name)}", s, d])
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def clip(intervals: Sequence[Interval], span: Interval) -> List[Interval]:
    lo, hi = span
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def complement(covered: Sequence[Interval], span: Interval) -> List[Interval]:
    """The parts of ``span`` that the disjoint, sorted ``covered`` leaves."""
    gaps, at = [], span[0]
    for start, end in covered:
        if start > at:
            gaps.append((at, start))
        at = max(at, end)
    if at < span[1]:
        gaps.append((at, span[1]))
    return gaps


def total(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def clock_offset(annotations: Sequence[Sequence[int]],
                 annotated_starts_ns: Sequence[int]) -> Optional[int]:
    """Profiler clock minus host monotonic clock, from the earliest query
    annotation and the earliest start the driver noted under the profiler."""
    if not annotations or not annotated_starts_ns:
        return None
    return min(a[0] for a in annotations) - min(annotated_starts_ns)


def reduce_trace(trace: Dict, queries_ns: Sequence[Interval],
                 span: Optional[Interval] = None, top: int = 10) -> Optional[Dict]:
    """``queries_ns`` are the query intervals and ``span`` the traced part of
    the window, both on the profiler's clock. Without ``span`` it runs from
    the first operation or query to the last. Returns None when no operation
    ran on any device: there is nothing to reduce."""
    devices = {name: ops for name, ops in trace["devices"].items() if ops}
    if not devices:
        return None
    if span is None:
        points = [(s, s + d) for ops in devices.values() for _n, s, d in ops]
        points += list(queries_ns)
        span = (min(p[0] for p in points), max(p[1] for p in points))
    in_flight = union(clip(queries_ns, span))
    busy_ns, by_op, by_program, gaps = [], {}, {}, []
    for plane, ops in sorted(devices.items()):
        modules = trace.get("modules", {}).get(plane, [])
        for name, s, d in modules:
            part = total(clip([(s, s + d)], span))
            if part:
                by_program[name] = by_program.get(name, 0) + part
        ops = labelled_ops(ops, modules)
        ran = clip([(s, s + d) for _n, s, d in ops], span)
        covered = union(ran)
        busy_ns.append(total(covered))
        for name, s, d in ops:
            part = total(clip([(s, s + d)], span))
            if part:
                by_op[name] = by_op.get(name, 0) + part
        for gap in complement(covered, span):
            inside = clip(in_flight, gap)
            gaps += [("in_query", e - s) for s, e in inside]
            gaps += [("between_queries", e - s)
                     for s, e in complement(inside, gap)]
    chips = len(devices)
    span_ns = span[1] - span[0]
    busy = sum(busy_ns) / chips
    by_label: Dict[str, int] = {}
    for label, ns in gaps:
        by_label[label] = by_label.get(label, 0) + ns
    longest = sorted(gaps, key=lambda g: -g[1])[:max(0, top - len(by_label))]
    idle_gaps = [[f"{label}.total", ns / chips / 1e9]
                 for label, ns in sorted(by_label.items(), key=lambda kv: -kv[1])]
    idle_gaps += [[f"{label}.gap{i + 1}", ns / 1e9]
                  for i, (label, ns) in enumerate(longest)]
    # the programs that held the device longest, then the single operations
    ranked = sorted(by_program.items(), key=lambda kv: -kv[1])[:PROGRAMS_IN_TOP]
    device_ops = [[f"program:{name}", ns / chips / 1e9] for name, ns in ranked]
    device_ops += [[f"op:{name}", ns / chips / 1e9] for name, ns in
                   sorted(by_op.items(),
                          key=lambda kv: -kv[1])[:top - len(device_ops)]]
    return {
        "chips": chips,
        "window_s": span_ns / 1e9,
        "busy_s": busy / 1e9,
        "idle_share": 1.0 - busy / span_ns,
        "op_events": sum(len(ops) for ops in devices.values()),
        "breakdown": {"device_ops": device_ops, "idle_gaps": idle_gaps},
    }
