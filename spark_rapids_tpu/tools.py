"""Qualification + profiling tools (the reference's `tools` module:
qualification — "how much of this workload would accelerate" — and
profiling — per-operator metrics after a run; user-facing-tools/
spark-qualification-tool.md is the shape being mirrored).

API:
  qualify(session, df)       -> QualificationReport
  qualify_sql(session, sql)  -> QualificationReport
  profile(session, df)       -> ProfileReport (runs the query)

CLI:
  python -m spark_rapids_tpu.tools qualify "SELECT ..." --view name=path
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple


@dataclass
class QualificationReport:
    """Per-operator device placement + fallback reasons."""

    device_ops: List[str] = field(default_factory=list)
    cpu_ops: List[Tuple[str, List[str]]] = field(default_factory=list)
    plan_string: str = ""

    @property
    def op_coverage(self) -> float:
        total = len(self.device_ops) + len(self.cpu_ops)
        return (len(self.device_ops) / total) if total else 1.0

    def format(self) -> str:
        lines = ["=== TPU Qualification Report ===",
                 f"operator coverage: {self.op_coverage:.0%} "
                 f"({len(self.device_ops)} on TPU, "
                 f"{len(self.cpu_ops)} on CPU)", ""]
        if self.device_ops:
            lines.append("runs on TPU:")
            lines += [f"  + {o}" for o in self.device_ops]
        if self.cpu_ops:
            lines.append("stays on CPU:")
            for name, reasons in self.cpu_ops:
                lines.append(f"  - {name}")
                lines += [f"      because {r}" for r in reasons]
        lines += ["", "physical plan:", self.plan_string]
        return "\n".join(lines)


def qualify(session, df) -> QualificationReport:
    """Rewrite the plan (without executing) and report placement —
    the qualification tool's core signal."""
    from spark_rapids_tpu.exec.base import TpuExec
    physical = session.plan_physical(df.plan)
    report = QualificationReport(
        plan_string=session.explain_string(df.plan, physical=physical))
    rewrite = session.last_rewrite_report
    if rewrite is not None:
        for name, reasons in rewrite.fallbacks:
            report.cpu_ops.append((name, list(reasons)))

    def walk(p):
        if isinstance(p, TpuExec):
            report.device_ops.append(p.simple_string().split()[0])
        # constituents of a fused stage, SHALLOW (their child links
        # point back into the chain)
        for op in getattr(p, "fused_ops", []):
            report.device_ops.append(op.simple_string().split()[0])
        for c in p.children:
            walk(c)
    walk(physical)
    return report


def qualify_sql(session, sql: str) -> QualificationReport:
    return qualify(session, session.sql(sql))


@dataclass
class ProfileReport:
    """Executed-query metrics per operator (profiling tool)."""

    rows: int = 0
    operators: List[Tuple[str, Dict[str, int]]] = field(
        default_factory=list)

    def format(self) -> str:
        lines = ["=== TPU Profile Report ===", f"output rows: {self.rows}"]
        for name, metrics in self.operators:
            lines.append(f"  {name}")
            for k, v in sorted(metrics.items()):
                lines.append(f"      {k}: {v}")
        return "\n".join(lines)


def profile(session, df) -> ProfileReport:
    """Execute the query and collect every device operator's metric
    registry (the write-only metrics VERDICT round 1 flagged — this is
    where they surface)."""
    from spark_rapids_tpu.exec.base import TpuExec
    physical = session.plan_physical(df.plan)
    result = physical.execute_collect()
    out = ProfileReport(rows=result.num_rows)

    def visit(p):
        vals = {name: m.value
                for name, m in p.metrics.metrics.items() if m.value}
        out.operators.append((p.simple_string().split()[0], vals))

    def walk(p):
        if isinstance(p, TpuExec):
            visit(p)
        # constituents of a fused stage keep their own metric
        # registries (the fan-back contract, docs/fusion.md) — visited
        # SHALLOW, their child links point back into the chain
        for op in getattr(p, "fused_ops", []):
            visit(op)
        for c in p.children:
            walk(c)
    walk(physical)
    return out


# -- offline (event-log) tools ---------------------------------------------
# (Qualification.scala:34 / Profiler.scala:31 roles: score and profile a
# PAST workload from its logs, no live session required)

def qualify_log(log_path: str) -> str:
    """Score logged queries for device suitability: per-query operator
    coverage + a histogram of fallback reasons."""
    from spark_rapids_tpu.event_log import read_events
    lines = ["=== TPU Qualification Report (offline) ===",
             f"log: {log_path}", ""]
    reason_counts: Dict[str, int] = {}
    n_q = 0
    covs: List[float] = []
    for ev in read_events(log_path):
        if ev.get("event") != "queryCompleted":
            continue
        n_q += 1
        ops = ev.get("ops", [])
        rated = [o for o in ops
                 if not o["op"].startswith(("TpuRowToColumnar",
                                            "TpuColumnarToRow"))]
        dev = sum(1 for o in rated if o.get("device"))
        total = len(rated) or 1
        cov = dev / total
        covs.append(cov)
        lines.append(f"query {ev.get('queryId')}: "
                     f"{cov:.0%} of operators on TPU, "
                     f"{ev.get('wallSeconds', 0):.3f}s, "
                     f"{ev.get('outputRows', 0)} rows")
        for fb in ev.get("fallbacks", []):
            for r in fb.get("reasons", []):
                reason_counts[r] = reason_counts.get(r, 0) + 1
    if not n_q:
        lines.append("no queryCompleted events found")
        return "\n".join(lines)
    score = sum(covs) / len(covs)
    lines += ["", f"queries: {n_q}",
              f"mean operator coverage: {score:.0%}",
              ("recommendation: ACCELERATE" if score >= 0.5 else
               "recommendation: investigate fallbacks first")]
    if reason_counts:
        lines += ["", "fallback reasons (by frequency):"]
        for r, c in sorted(reason_counts.items(), key=lambda kv: -kv[1]):
            lines.append(f"  {c:4d}x {r}")
    return "\n".join(lines)


def profile_log(log_path: str) -> str:
    """Aggregate per-operator metrics + a text timeline across logged
    queries (GenerateTimeline.scala's role, in text)."""
    from spark_rapids_tpu.event_log import read_events
    lines = ["=== TPU Profile Report (offline) ===",
             f"log: {log_path}", ""]
    op_metrics: Dict[str, Dict[str, int]] = {}
    events = [ev for ev in read_events(log_path)
              if ev.get("event") == "queryCompleted"]
    if not events:
        lines.append("no queryCompleted events found")
        return "\n".join(lines)
    t0 = min(ev["ts"] - ev.get("wallSeconds", 0) for ev in events)
    span = max(max(ev["ts"] for ev in events) - t0, 1e-9)
    lines.append("timeline (each bar spans the query's wall time):")
    width = 50
    for ev in events:
        start = ev["ts"] - ev.get("wallSeconds", 0) - t0
        dur = ev.get("wallSeconds", 0)
        a = int(start / span * width)
        b = max(a + 1, int((start + dur) / span * width))
        bar = " " * a + "#" * (b - a)
        lines.append(f"  q{ev.get('queryId'):>3} |{bar:<{width}}| "
                     f"{dur:.3f}s")
        for o in ev.get("ops", []):
            for k, v in o.get("metrics", {}).items():
                d = op_metrics.setdefault(o["op"], {})
                d[k] = d.get(k, 0) + v
        st = ev.get("storeStats")
        if st and st.get("spillCount"):
            lines.append(f"       spills: {st['spillCount']} "
                         f"({st.get('spilledDeviceBytes', 0)} bytes)")
    lines += ["", "aggregate operator metrics:"]
    for op, ms in sorted(op_metrics.items()):
        lines.append(f"  {op}")
        for k, v in sorted(ms.items()):
            lines.append(f"      {k}: {v}")
    return "\n".join(lines)


# -- offline trace analysis -------------------------------------------------
# (the span-trace half of the profiling tool: critical path, exclusive
# self-time, per-chip occupancy over one query's Chrome-trace file —
# docs/observability.md explains how to read each section)

def _trace_bounds(spans: List[dict]) -> Tuple[float, float]:
    t0 = min(s["t0"] for s in spans)
    t1 = max(s["t1"] for s in spans)
    return t0, max(t1, t0 + 1e-9)


def critical_path(spans: List[dict]) -> Tuple[Dict[str, float], float]:
    """Backward walk from the last span end to the first span start: at
    each point the *most immediate* covering span (the one with the
    latest start) owns the segment; where nothing covers, the gap is
    idle. Returns (microseconds attributed per span name, idle us) —
    the chain of work that determined the query wall, so shrinking
    anything NOT on it cannot speed the query up."""
    if not spans:
        return {}, 0.0
    import heapq
    t_begin, t_end = _trace_bounds(spans)
    desc = sorted(spans, key=lambda s: -s["t1"])
    attr: Dict[str, float] = {}
    idle = 0.0
    heap: List[Tuple[float, int]] = []  # (-t0, index into desc)
    i = 0
    cur = t_end
    while cur > t_begin + 1e-9:
        while i < len(desc) and desc[i]["t1"] >= cur - 1e-9:
            heapq.heappush(heap, (-desc[i]["t0"], i))
            i += 1
        # a span whose t0 >= cur can never cover this or any smaller cur
        while heap and -heap[0][0] >= cur - 1e-9:
            heapq.heappop(heap)
        if heap:
            neg_t0, idx = heap[0]
            s = desc[idx]
            seg_start = max(-neg_t0, t_begin)
            attr[s["name"]] = attr.get(s["name"], 0.0) + (cur - seg_start)
            cur = seg_start
        elif i < len(desc):
            nxt = min(cur, max(desc[i]["t1"], t_begin))
            idle += cur - nxt
            cur = nxt
        else:
            idle += cur - t_begin
            cur = t_begin
    return attr, idle


def exclusive_times(spans: List[dict]) -> Dict[str, Dict[str, float]]:
    """Per span name: count, total us, and EXCLUSIVE us (total minus
    directly nested child spans on the same lane). This undoes
    double counting at the reporting layer — e.g. the ``retryBlock``
    span nested inside an operator's timer span is subtracted from the
    operator's self-time, fixing the documented retryBlockTime-inside-
    opTime overlap (docs/robustness.md)."""
    out: Dict[str, Dict[str, float]] = {}
    by_tid: Dict[int, List[dict]] = {}
    for s in spans:
        by_tid.setdefault(s["tid"], []).append(s)
    for ss in by_tid.values():
        ss.sort(key=lambda s: (s["t0"], -(s["t1"] - s["t0"])))
        stack: List[dict] = []
        for s in ss:
            s["_child"] = 0.0
            while stack and stack[-1]["t1"] <= s["t0"] + 1e-9:
                stack.pop()
            if stack:
                stack[-1]["_child"] += s["t1"] - s["t0"]
            stack.append(s)
        for s in ss:
            d = out.setdefault(s["name"],
                               {"count": 0, "total": 0.0,
                                "exclusive": 0.0})
            d["count"] += 1
            dur = s["t1"] - s["t0"]
            d["total"] += dur
            d["exclusive"] += max(0.0, dur - s.pop("_child"))
    return out


def enqueue_occupancy(spans: List[dict]) -> Dict[int, Dict]:
    """Per chip, the union of the HOST intervals of chip-attributed
    spans (uploads, dispatches) over the trace window, and the top
    gaps between them. jax dispatch is asynchronous, so this is when
    programs were ENQUEUED for a chip, not when the chip ran them:
    true device occupancy comes from a profiler trace's device planes
    (``device_occupancy`` / ``tools trace <profile dir>``). Mesh skew
    in the enqueue order still shows up here."""
    t_begin, t_end = _trace_bounds(spans) if spans else (0.0, 1.0)
    per: Dict[int, List[Tuple[float, float]]] = {}
    for s in spans:
        chip = s.get("args", {}).get("chip")
        if chip is not None:
            per.setdefault(int(chip), []).append((s["t0"], s["t1"]))
    out: Dict[int, Dict] = {}
    for chip, ivs in sorted(per.items()):
        ivs.sort()
        merged: List[List[float]] = []
        for a, b in ivs:
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        busy = sum(b - a for a, b in merged)
        gaps = []
        prev = t_begin
        for a, b in merged:
            if a > prev:
                gaps.append((prev, a - prev))
            prev = max(prev, b)
        if t_end > prev:
            gaps.append((prev, t_end - prev))
        gaps.sort(key=lambda g: -g[1])
        out[chip] = {
            "busy_us": round(busy, 1),
            "occupancy": round(busy / (t_end - t_begin), 4),
            "dispatches": len(ivs),
            "topIdleGaps_us": [round(g[1], 1) for g in gaps[:3]],
        }
    return out


def top_spans(spans: List[dict], n: int = 10) -> List[dict]:
    ranked = sorted(spans, key=lambda s: -(s["t1"] - s["t0"]))[:n]
    return [{"name": s["name"], "dur_us": round(s["t1"] - s["t0"], 1),
             "t0_us": round(s["t0"], 1), "tid": s["tid"],
             "args": s.get("args", {})} for s in ranked]


ROOT_SPAN = "srt.query"  # the query's extent, not a piece of its work


def work_spans(spans: List[dict]) -> List[dict]:
    """Spans without the per-query roots: a root covers its whole
    query, so on a critical path or in a self-time table it would
    swallow everything under it."""
    return [s for s in spans if s["name"] != ROOT_SPAN]


def _of_query(records: List[dict], query) -> List[dict]:
    """The records of one query id (all of them for None)."""
    if query is None:
        return records
    return [r for r in records if r.get("args", {}).get("q") == query]


def per_query(spans: List[dict]) -> Dict:
    """Per query id (``args.q``; None = recorded outside any query):
    span count, first start to last end, and the three longest span
    kinds by summed duration (microseconds)."""
    out: Dict = {}
    for s in spans:
        d = out.setdefault(s.get("args", {}).get("q"),
                           {"spans": 0, "t0": s["t0"], "t1": s["t1"],
                            "kinds": {}})
        d["spans"] += 1
        d["t0"] = min(d["t0"], s["t0"])
        d["t1"] = max(d["t1"], s["t1"])
        d["kinds"][s["name"]] = d["kinds"].get(s["name"], 0.0) \
            + s["t1"] - s["t0"]
    for d in out.values():
        d["top"] = sorted(d.pop("kinds").items(),
                          key=lambda kv: -kv[1])[:3]
    return out


def analyze_trace(path: str, query=None) -> Dict:
    """Machine-readable analysis of one trace file (bench detail.trace
    consumes this); ``query`` restricts it to one query id."""
    from spark_rapids_tpu.trace import load_trace
    tr = load_trace(path)
    spans = _of_query(tr["spans"], query)
    out: Dict = {"file": path, "meta": tr["meta"],
                 "spanCount": len(spans),
                 "instantCount": len(tr["instants"])}
    out["queries"] = sorted({s["args"]["q"] for s in spans
                             if "q" in s.get("args", {})})
    spans = work_spans(spans)
    if not spans:
        return out
    cp, idle = critical_path(spans)
    total = sum(cp.values()) + idle
    out["criticalPath_s"] = {
        k: round(v / 1e6, 4)
        for k, v in sorted(cp.items(), key=lambda kv: -kv[1])}
    out["criticalPathIdle_s"] = round(idle / 1e6, 4)
    out["criticalPathSpan_s"] = round(total / 1e6, 4)
    out["enqueueOccupancy"] = enqueue_occupancy(spans)
    out["topSpans"] = top_spans(spans, 5)
    return out


def format_trace_report(path: str, top: int = 10, query=None) -> str:
    """Human-readable trace report (the `tools trace` CLI output);
    ``query`` restricts every section to one query id."""
    from spark_rapids_tpu.trace import load_trace
    tr = load_trace(path)
    meta = tr["meta"]
    spans = _of_query(tr["spans"], query)
    instants = _of_query(tr["instants"], query)
    lines = ["=== TPU Trace Report ===", f"trace: {path}"
             + (f" (query {query} only)" if query is not None else ""),
             f"query {meta.get('queryId')}: "
             f"{meta.get('wallSeconds', 0):.3f}s wall, "
             f"{meta.get('outputRows', 0)} rows, "
             f"{len(spans)} spans, {len(instants)} markers", ""]
    if not spans:
        lines.append("no spans recorded")
        return "\n".join(lines)
    t_begin, t_end = _trace_bounds(spans)
    window = t_end - t_begin
    lines.append("queries (every record carries its query's id; "
                 "--query <id> restricts the report to one):")
    for q, d in sorted(per_query(spans).items(),
                       key=lambda kv: (kv[0] is None, kv[0] or 0)):
        tops = ", ".join(f"{k} {us / 1e6:.3f}s" for k, us in d["top"])
        lines.append(f"  q={q}: {d['spans']} spans over "
                     f"{(d['t1'] - d['t0']) / 1e6:.3f}s; {tops}")
    lines.append("")
    spans = work_spans(spans)
    cp, idle = critical_path(spans)
    lines.append(f"critical path ({window / 1e6:.3f}s traced window):")
    for name, us in sorted(cp.items(), key=lambda kv: -kv[1]):
        lines.append(f"  {us / 1e6:8.3f}s  {us / window:5.1%}  {name}")
    lines.append(f"  {idle / 1e6:8.3f}s  {idle / window:5.1%}  (idle)")
    lines += ["", "exclusive self-time per operator (retry/compile "
              "blocks subtracted from their enclosing spans):"]
    excl = exclusive_times(spans)
    ranked = sorted(excl.items(), key=lambda kv: -kv[1]["exclusive"])
    lines.append(f"  {'span':44s} {'count':>6s} {'total_s':>9s} "
                 f"{'self_s':>9s}")
    for name, d in ranked[:top]:
        lines.append(f"  {name:44s} {d['count']:6d} "
                     f"{d['total'] / 1e6:9.3f} "
                     f"{d['exclusive'] / 1e6:9.3f}")
    occ = enqueue_occupancy(spans)
    lines += ["", "per-chip enqueue occupancy (host intervals of "
              "chip-attributed spans: when programs were ENQUEUED; the "
              "device's own busy time is in a profiler trace, `tools "
              "trace <profile dir>`):"]
    if occ:
        for chip, d in occ.items():
            gaps = ", ".join(f"{g / 1e3:.1f}ms"
                             for g in d["topIdleGaps_us"]) or "-"
            lines.append(f"  chip {chip}: {d['occupancy']:6.1%} busy, "
                         f"{d['dispatches']} dispatches, "
                         f"top idle gaps: {gaps}")
    else:
        lines.append("  (no chip-attributed spans)")
    lines += ["", f"top {top} slowest spans:"]
    for s in top_spans(spans, top):
        extra = ""
        if s["args"]:
            extra = "  " + ", ".join(
                f"{k}={v}" for k, v in sorted(s["args"].items()))
        lines.append(f"  {s['dur_us'] / 1e3:9.1f}ms  {s['name']}{extra}")
    if instants:
        counts: Dict[str, int] = {}
        for ins in instants:
            counts[ins["name"]] = counts.get(ins["name"], 0) + 1
        lines += ["", "instant markers:"]
        for name, c in sorted(counts.items(), key=lambda kv: -kv[1]):
            lines.append(f"  {c:5d}x {name}")
    return "\n".join(lines)


# -- profiler-trace analysis -------------------------------------------------
# `tools trace <profile dir>`: a jax.profiler trace holds the engine's
# spans (TraceAnnotations, trace.py) on the host planes and what the
# chip ran on the device planes, on ONE clock. load_profile turns the
# xplane into plain lists; everything after it is arithmetic on lists
# (as benchmarks/harness/profiler.reduce_trace is), tested without a
# chip. Times are microseconds from the trace's first event.

GAP_MIN_US = 1000.0     # idle gaps shorter than 1 ms are not attributed
NO_SPAN = "no_span"
_DEVICE_PLANE = "/device:TPU:"
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"
# the stat of an op's EVENT METADATA that carries the HLO op_name (named
# scopes), as "jit(srt_agg_partial)/groupby_sort/scatter:"
_OP_NAME_STAT = "tf_op"


def profile_files(path: str) -> List[str]:
    """The ``.xplane.pb`` files of a profile directory (``<dir>/plugins/
    profile/<time>/*.xplane.pb``), or the file itself."""
    import glob
    import os
    if os.path.isfile(path):
        return [path] if path.endswith(".xplane.pb") else []
    return sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))


def scope_of_op(op_name: str) -> str:
    """``jit(srt_agg_partial)/jit(main)/groupby_sort/sort`` ->
    ``groupby_sort``: the named-scope path of an HLO op_name, without
    the jit wrappers, the primitive's own name and the ``:type`` tail
    the profiler appends."""
    parts = [p for p in str(op_name or "").split(":", 1)[0].split("/")
             if p and not p.startswith(("jit(", "pjit("))]
    return "/".join(parts[:-1]) or "(no scope)"


def _pb_fields(buf: bytes):
    """Protobuf wire format, one level: ``(field, wire type, value)``
    with a length-delimited value as bytes and a varint as int (64-
    and 32-bit fixed fields are skipped as bytes). Enough to read the
    few xplane fields ``jax.profiler.ProfileData`` does not expose."""
    def varint(i: int) -> Tuple[int, int]:
        v = shift = 0
        while True:
            b = buf[i]
            i += 1
            v |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return v, i

    i, n = 0, len(buf)
    while i < n:
        key, i = varint(i)
        field, wt = key >> 3, key & 7
        if wt == 0:
            v, i = varint(i)
            yield field, wt, v
        elif wt == 2:
            ln, i = varint(i)
            yield field, wt, buf[i:i + ln]
            i += ln
        elif wt in (1, 5):
            size = 8 if wt == 1 else 4
            yield field, wt, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"xplane: unexpected wire type {wt}")


def xplane_op_names(path: str) -> Dict[str, Dict[str, str]]:
    """``{device plane: {event name: op_name}}`` from the EVENT METADATA
    of an xplane file: the device's op events carry their HLO op_name
    (with the ``jax.named_scope`` path) as the ``tf_op`` stat of their
    metadata, which ``ProfileData`` does not hand out. Schema
    (tsl/profiler/protobuf/xplane.proto): XSpace.planes=1;
    XPlane.name=2, event_metadata=4 and stat_metadata=5 (maps: key=1,
    value=2); XEventMetadata.name=2, stats=5; XStat.metadata_id=1,
    str_value=5, ref_value=7 (a stat_metadata id); XStatMetadata
    name=2."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, str]] = {}
    for field, wt, plane in _pb_fields(space):
        if field != 1 or wt != 2:
            continue
        name, ev_meta, stat_names = "", [], {}
        for f2, w2, v in _pb_fields(plane):
            if f2 == 2 and w2 == 2:
                name = v.decode("utf-8", "replace")
            elif f2 == 4 and w2 == 2:
                ev_meta.append(v)
            elif f2 == 5 and w2 == 2:
                key, sname = None, ""
                for f3, w3, v3 in _pb_fields(v):
                    if f3 == 1 and w3 == 0:
                        key = v3
                    elif f3 == 2 and w3 == 2:
                        for f4, w4, v4 in _pb_fields(v3):
                            if f4 == 2 and w4 == 2:
                                sname = v4.decode("utf-8", "replace")
                if key is not None:
                    stat_names[key] = sname
        if not name.startswith(_DEVICE_PLANE):
            continue
        want = {k for k, n in stat_names.items() if n == _OP_NAME_STAT}
        names: Dict[str, str] = {}
        for entry in ev_meta:
            for f3, w3, v3 in _pb_fields(entry):
                if f3 != 2 or w3 != 2:
                    continue
                ev_name, op_name = "", ""
                for f4, w4, v4 in _pb_fields(v3):
                    if f4 == 2 and w4 == 2:
                        ev_name = v4.decode("utf-8", "replace")
                    elif f4 == 5 and w4 == 2:
                        sid, sval = None, ""
                        for f5, w5, v5 in _pb_fields(v4):
                            if f5 == 1 and w5 == 0:
                                sid = v5
                            elif f5 == 5 and w5 == 2:
                                sval = v5.decode("utf-8", "replace")
                            elif f5 == 7 and w5 == 0:
                                sval = stat_names.get(v5, "")
                        if sid in want:
                            op_name = sval
                if ev_name and op_name:
                    names[ev_name] = op_name
        out[name] = names
    return out


def load_profile(path: str) -> Dict:
    """One xplane file as plain lists: ``spans`` (the engine's host
    annotations in the shape every analyzer here takes: name, t0, t1 in
    microseconds, tid, args), ``ops`` and ``modules`` per device plane
    (``[name, t0_us, t1_us, scope]`` — what the chip ran, and whole
    programs ``jit_srt_...(<fingerprint>)``)."""
    import warnings

    from jax.profiler import ProfileData
    from spark_rapids_tpu.trace import INSTANT_CATALOG, SPAN_CATALOG
    # jaxlib's event_stats iterator trips a DeprecationWarning per call
    warnings.filterwarnings("ignore", category=DeprecationWarning,
                            message=".*event_stats.*")
    data = ProfileData.from_file(path)
    op_names = xplane_op_names(path)
    raw_spans: List[Tuple] = []
    ops: Dict[str, List] = {}
    modules: Dict[str, List] = {}
    tid = 0
    for plane in data.planes:
        if plane.name.startswith(_DEVICE_PLANE):
            for line in plane.lines:
                into = {_OPS_LINE: ops, _MODULES_LINE: modules}.get(
                    line.name)
                if into is None:
                    continue
                rows = into.setdefault(plane.name, [])
                scopes = op_names.get(plane.name, {})
                for e in line.events:
                    scope = (scope_of_op(scopes.get(e.name, ""))
                             if into is ops else "")
                    rows.append([e.name, float(e.start_ns),
                                 float(e.start_ns + e.duration_ns),
                                 scope])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tid += 1
                for e in line.events:
                    # the engine's: a catalog kind, or a metric mirror
                    # `<Exec>.<metric>` carrying a query id
                    known = e.name in SPAN_CATALOG \
                        or e.name in INSTANT_CATALOG
                    if not known and "." not in e.name:
                        continue
                    stats = dict(e.stats)
                    if not known and "q" not in stats:
                        continue  # someone else's dotted name
                    raw_spans.append((e.name, float(e.start_ns),
                                      float(e.start_ns + e.duration_ns),
                                      tid, stats))
    starts = [s[1] for s in raw_spans] + [
        r[1] for rows in list(ops.values()) + list(modules.values())
        for r in rows]
    base = min(starts) if starts else 0.0
    for rows in list(ops.values()) + list(modules.values()):
        for r in rows:
            r[1] = (r[1] - base) / 1e3
            r[2] = (r[2] - base) / 1e3
    spans = [{"name": n, "t0": (a - base) / 1e3, "t1": (b - base) / 1e3,
              "tid": t, "args": st} for n, a, b, t, st in raw_spans]
    return {"file": path, "spans": spans, "ops": ops, "modules": modules}


def _union(ivs: List[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(i for i in ivs if i[1] > i[0]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def device_occupancy(ops: Dict[str, List],
                     window: Optional[Tuple[float, float]] = None
                     ) -> Dict[str, Dict]:
    """Per device plane: true busy time (the union of the intervals in
    which an operation ran), idle, occupancy over ``window`` (default:
    first operation to last, over all planes) and the idle gaps as
    ``(t0, t1)``. This is what "occupancy" means; ``enqueue_occupancy``
    is the host's view of when it asked."""
    rows = [r for rs in ops.values() for r in rs]
    if not rows:
        return {}
    if window is None:
        window = (min(r[1] for r in rows), max(r[2] for r in rows))
    w0, w1 = window
    out: Dict[str, Dict] = {}
    for plane, rs in sorted(ops.items()):
        busy = [[max(a, w0), min(b, w1)] for a, b in
                _union([(r[1], r[2]) for r in rs])
                if min(b, w1) > max(a, w0)]
        gaps, at = [], w0
        for a, b in busy:
            if a > at:
                gaps.append((at, a))
            at = max(at, b)
        if at < w1:
            gaps.append((at, w1))
        busy_us = sum(b - a for a, b in busy)
        out[plane] = {"busy_us": busy_us,
                      "idle_us": (w1 - w0) - busy_us,
                      "occupancy": busy_us / max(w1 - w0, 1e-9),
                      "ops": len(rs), "gaps": gaps}
    return out


WAIT_SPANS = ("scanWait", "semaphoreWait")  # a thread waiting on another


def _owner_rank(s: dict) -> Tuple:
    """Which covering span owns an idle instant: a work span over a
    wait span (a consumer that began waiting after its producer's
    ``scanParse`` began must not take the gap from it) over the query's
    root; within a rank the deepest, the one that started last (ties:
    the shorter)."""
    name = s["name"]
    rank = 0 if name == ROOT_SPAN else 1 if name in WAIT_SPANS else 2
    return rank, s["t0"], -s["t1"]


def attribute_gaps(gaps: List[Tuple[float, float]], spans: List[dict],
                   min_gap_us: float = GAP_MIN_US) -> Dict:
    """Put every idle gap of at least ``min_gap_us`` down to what the
    host was doing in it. Each instant of a gap belongs to one engine
    span covering it on any host thread (``_owner_rank``: the deepest
    work span, else the deepest wait, else the query's root) or to
    ``no_span``; a gap two spans share is split between them at the
    boundary. Returns the totals by span kind and by query id (exact,
    from the pieces), the gaps themselves with the kind that covers
    most of each, and the idle time in gaps too short to
    attribute."""
    by_kind: Dict[str, float] = {}
    by_q: Dict = {}
    labelled: List[Dict] = []
    short_us = 0.0
    cands = sorted(spans, key=lambda s: s["t0"])
    for g0, g1 in gaps:
        if g1 - g0 < min_gap_us:
            short_us += g1 - g0
            continue
        over = [s for s in cands if s["t0"] < g1 and s["t1"] > g0]
        cuts = sorted({g0, g1} | {min(max(t, g0), g1) for s in over
                                  for t in (s["t0"], s["t1"])})
        shares: Dict[Tuple, float] = {}
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = [s for s in over if s["t0"] <= mid < s["t1"]]
            if cover:
                s = max(cover, key=_owner_rank)
                key = (s["name"], s.get("args", {}).get("q"))
            else:
                key = (NO_SPAN, None)
            shares[key] = shares.get(key, 0.0) + b - a
        for (kind, q), us in shares.items():
            by_kind[kind] = by_kind.get(kind, 0.0) + us
            by_q[q] = by_q.get(q, 0.0) + us
        (kind, q), _us = max(shares.items(), key=lambda kv: kv[1])
        labelled.append({"t0": g0, "t1": g1, "kind": kind, "q": q,
                         "shares": {k[0]: v for k, v in shares.items()}})
    return {"byKind": by_kind, "byQuery": by_q, "gaps": labelled,
            "shortGaps_us": short_us}


def device_time(ops: List, modules: List) -> Dict:
    """Device microseconds by program (the ``XLA Modules`` events,
    fingerprints stripped: ``jit_srt_agg_partial``) and, within each
    program, by named scope of the operations that ran inside one of
    its runs (``groupby_sort``, ``Filter``, ...). Control-flow ops
    (``while``) hold their bodies' time, so scopes of one program can
    sum past its total."""
    import bisect
    import re

    runs = sorted((m[1], m[2], re.sub(r"[(_]\d+[)_]?$", "", m[0]))
                  for m in modules)
    starts = [r[0] for r in runs]
    by_program: Dict[str, float] = {}
    for a, b, p in runs:
        by_program[p] = by_program.get(p, 0.0) + b - a
    by_scope: Dict[str, Dict[str, float]] = {}
    for _name, a, b, scope in ops:
        i = bisect.bisect_right(starts, a) - 1
        p = runs[i][2] if i >= 0 and a < runs[i][1] else "?"
        d = by_scope.setdefault(p, {})
        scope = scope or "(no scope)"
        d[scope] = d.get(scope, 0.0) + b - a
    return {"byProgram": by_program, "byScope": by_scope}


def analyze_profile(path: str, query=None,
                    loaded: Optional[Dict] = None) -> Dict:
    """Machine-readable form of ``tools trace <profile dir>`` for one
    xplane file: per chip busy/idle, idle attributed to host spans,
    device time by program and scope. ``query`` narrows the host spans
    (and with them the window) to one query id; ``loaded`` is that
    file's ``load_profile`` result when the caller has it already."""
    pr = loaded if loaded is not None else load_profile(path)
    spans = _of_query(pr["spans"], query)
    window = None
    roots = [s for s in spans if s["name"] == "srt.query"]
    if roots:
        # the queries' own extent: idle before the first and after the
        # last query is the caller's, not the engine's
        window = (min(s["t0"] for s in roots), max(s["t1"] for s in roots))
    occ = device_occupancy(pr["ops"], window)
    out: Dict = {"file": path, "spanCount": len(spans),
                 "queries": sorted({s["args"]["q"] for s in spans
                                    if "q" in s["args"]}),
                 "chips": {}}
    for plane, d in occ.items():
        att = attribute_gaps(d["gaps"], spans)
        out["chips"][plane] = {
            "busy_s": d["busy_us"] / 1e6, "idle_s": d["idle_us"] / 1e6,
            "occupancy": d["occupancy"], "ops": d["ops"],
            "idleByKind_s": {k: v / 1e6 for k, v in sorted(
                att["byKind"].items(), key=lambda kv: -kv[1])},
            "idleByQuery_s": {str(k): v / 1e6
                              for k, v in att["byQuery"].items()},
            "idleShortGaps_s": att["shortGaps_us"] / 1e6,
            "longestGaps": [
                {"at_s": g["t0"] / 1e6, "s": (g["t1"] - g["t0"]) / 1e6,
                 "kind": g["kind"], "q": g["q"]}
                for g in sorted(att["gaps"],
                                key=lambda g: g["t0"] - g["t1"])[:8]],
            "device": {k: {n: us / 1e6 for n, us in v.items()}
                       if k == "byProgram" else
                       {p: {n: us / 1e6 for n, us in sc.items()}
                        for p, sc in v.items()}
                       for k, v in device_time(
                           pr["ops"].get(plane, []),
                           pr["modules"].get(plane, [])).items()},
        }
    return out


def format_profile_report(path: str, top: int = 10, query=None) -> str:
    """Human-readable ``tools trace <profile dir>`` report: the host
    analyzers over the engine's annotations, then per chip what the
    device planes say."""
    pr = load_profile(path)
    a = analyze_profile(path, query, loaded=pr)
    pr_spans = work_spans(_of_query(pr["spans"], query))
    lines = ["=== TPU Profile Report ===", f"profile: {path}"
             + (f" (query {query} only)" if query is not None else ""),
             f"{a['spanCount']} engine annotations, queries "
             f"{a['queries'] or '-'}", ""]
    if pr_spans:
        cp, idle = critical_path(pr_spans)
        t_begin, t_end = _trace_bounds(pr_spans)
        window = t_end - t_begin
        lines.append("host critical path over the engine's annotations:")
        for name, us in sorted(cp.items(), key=lambda kv: -kv[1])[:top]:
            lines.append(f"  {us / 1e6:8.3f}s  {us / window:5.1%}  {name}")
        lines.append(f"  {idle / 1e6:8.3f}s  {idle / window:5.1%}  (idle)")
        lines.append("")
    if not a["chips"]:
        lines.append("no device plane in this trace (a CPU run has "
                     "none): occupancy, idle attribution and device "
                     "time need a run on the chip")
        return "\n".join(lines)
    for plane, c in a["chips"].items():
        total = c["busy_s"] + c["idle_s"]
        lines.append(f"{plane}: {c['occupancy']:6.1%} busy "
                     f"({c['busy_s']:.3f}s of {total:.3f}s, "
                     f"{c['ops']} operations), idle {c['idle_s']:.3f}s")
        lines.append("  idle by the host span it falls under (gaps of "
                     f"{GAP_MIN_US / 1e3:.0f} ms and more):")
        for kind, s in list(c["idleByKind_s"].items())[:top]:
            lines.append(f"    {s:8.3f}s  "
                         f"{s / max(c['idle_s'], 1e-12):5.1%}  {kind}")
        lines.append(f"    {c['idleShortGaps_s']:8.3f}s  "
                     f"{c['idleShortGaps_s'] / max(c['idle_s'], 1e-12):5.1%}"
                     "  (gaps under 1 ms, not attributed)")
        lines.append("  idle by query: " + ", ".join(
            f"q={q} {s:.3f}s" for q, s in c["idleByQuery_s"].items()))
        lines.append("  longest gaps:")
        for g in c["longestGaps"][:top]:
            lines.append(f"    {g['s']:8.3f}s at {g['at_s']:.3f}s  "
                         f"{g['kind']} (q={g['q']})")
        lines.append("  device time by program, then by named scope:")
        progs = sorted(c["device"]["byProgram"].items(),
                       key=lambda kv: -kv[1])
        for p, s in progs[:top]:
            lines.append(f"    {s:8.3f}s  {p}")
            scopes = sorted(c["device"]["byScope"].get(p, {}).items(),
                            key=lambda kv: -kv[1])
            for sc, ss in scopes[:top]:
                lines.append(f"        {ss:8.3f}s  {sc}")
    return "\n".join(lines)



def hotspots_report(paths: List[str], top: int = 20) -> str:
    """Rank EXCLUSIVE self-time per span name across a whole trace
    directory (the `tools hotspots` CLI): a span family's summed
    self-time across queries is the ceiling on what rewriting that
    loop can save."""
    from spark_rapids_tpu.trace import load_trace
    agg: Dict[str, Dict[str, float]] = {}
    window = 0.0
    for fp in paths:
        tr = load_trace(fp)
        spans = work_spans(tr["spans"])
        if not spans:
            continue
        t0, t1 = _trace_bounds(spans)
        window += t1 - t0
        for name, d in exclusive_times(spans).items():
            e = agg.setdefault(name, {"count": 0, "total": 0.0,
                                      "exclusive": 0.0})
            e["count"] += d["count"]
            e["total"] += d["total"]
            e["exclusive"] += d["exclusive"]
    lines = ["=== TPU Hotspot Report ===",
             f"{len(paths)} trace file(s), "
             f"{window / 1e6:.3f}s summed traced window", "",
             "exclusive self-time per span family:", ""]
    if not agg:
        lines.append("no spans recorded")
        return "\n".join(lines)
    ranked = sorted(agg.items(), key=lambda kv: -kv[1]["exclusive"])
    lines.append(f"  {'span':44s} {'count':>7s} {'total_s':>9s} "
                 f"{'self_s':>9s} {'self%':>6s}")
    for name, d in ranked[:top]:
        pct = d["exclusive"] / window if window else 0.0
        lines.append(f"  {name:44s} {d['count']:7d} "
                     f"{d['total'] / 1e6:9.3f} "
                     f"{d['exclusive'] / 1e6:9.3f} {pct:6.1%}")
    return "\n".join(lines)


def _main(argv: List[str]) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="spark_rapids_tpu.tools",
        description="TPU qualification/profiling tools")
    ap.add_argument("command",
                    choices=["qualify", "profile", "docs", "trace",
                             "hotspots", "serve", "serve-client",
                             "lint", "top", "soak",
                             "history", "doctor", "tuning"])
    ap.add_argument("sql", nargs="?", help="SQL text to analyze (live "
                    "mode; omit when using --log), the trace "
                    "file/directory for the trace/hotspots commands, "
                    "a profile-*.json file/directory for the "
                    "profile command (spark.rapids.sql.profile.dir "
                    "output), the server port for `top`, the "
                    "history directory for `history`, or the "
                    "queryId/signature selector for `doctor`")
    ap.add_argument("--view", action="append", default=[],
                    help="name=path parquet view registrations")
    ap.add_argument("--log", help="offline mode: event-log file or "
                    "directory (spark.rapids.sql.eventLog.dir output)")
    ap.add_argument("--out", default="docs",
                    help="docs: output directory for generated markdown")
    ap.add_argument("--top", type=int, default=10,
                    help="trace: rows per report section")
    ap.add_argument("--query", type=int, default=None, metavar="ID",
                    help="trace: restrict the report to one query id "
                    "(every span carries its query's `q`)")
    ap.add_argument("--conf", action="append", default=[],
                    help="serve: key=value spark.rapids confs")
    ap.add_argument("--host", default=None, help="serve/serve-client: "
                    "bind/connect host (default 127.0.0.1)")
    ap.add_argument("--port", type=int, default=None,
                    help="serve: bind port (0/unset = ephemeral); "
                    "serve-client: server port (required)")
    ap.add_argument("--tenant", default=None,
                    help="serve-client: tenant id for the request "
                    "(default 'default'); history: restrict the "
                    "report to one tenant")
    ap.add_argument("--since", default=None,
                    help="history: only records newer than this — a "
                    "number of seconds ago (e.g. 3600) or an ISO "
                    "timestamp (2026-08-04T12:00)")
    ap.add_argument("--history", default=None,
                    help="doctor/tuning: the query-history directory "
                    "(spark.rapids.sql.telemetry.history.dir)")
    ap.add_argument("--signature", default=None,
                    help="history: restrict the report to one "
                    "signature digest (full 40-hex or a prefix)")
    ap.add_argument("--all", action="store_true",
                    help="doctor: batch mode — diagnose every "
                    "signature's newest record and rank regressions "
                    "worst-first (--top rows)")
    ap.add_argument("--pin", type=int, default=None, metavar="EPOCH",
                    help="tuning: pin the action (exempt from the "
                    "guardrail's auto-revert)")
    ap.add_argument("--unpin", type=int, default=None, metavar="EPOCH",
                    help="tuning: clear the pin")
    ap.add_argument("--revert", type=int, default=None, metavar="EPOCH",
                    help="tuning: request a rollback — the controller "
                    "honors it at its next tick (or skips the action "
                    "at the next server start)")
    ap.add_argument("--stats", action="store_true",
                    help="serve-client: print server stats instead of "
                    "running SQL")
    ap.add_argument("--json", action="store_true",
                    help="lint: machine-readable JSON output "
                    "(same as --format=json)")
    ap.add_argument("--format", default=None, dest="lint_format",
                    choices=["human", "json", "github"],
                    help="lint: output format; `github` emits "
                    "workflow-command annotations (::error ...) for "
                    "inline PR comments in Actions")
    ap.add_argument("--changed-only", nargs="?", const="HEAD",
                    default=None, metavar="BASE",
                    help="lint: restrict findings to files in `git "
                    "diff --name-only BASE` (default HEAD) plus "
                    "untracked files — the incremental pre-commit "
                    "mode; the analysis still covers the whole "
                    "package so cross-module rules stay sound")
    ap.add_argument("--time-budget", type=float, default=None,
                    help="lint: fail (exit 2) when the analysis wall "
                    "exceeds this many seconds (default: "
                    "time_budget_s in tpu-lint.json, 60s)")
    ap.add_argument("--fix-baseline", action="store_true",
                    help="lint: capture current findings into the "
                    "baseline file as accepted debt (stale entries "
                    "are pruned)")
    ap.add_argument("--root", default=None,
                    help="lint: repo root to analyze (default: the "
                    "installed package's parent directory)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve: also serve GET /metrics (Prometheus "
                    "text) over HTTP on this port (0 = ephemeral)")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="top: seconds between stats polls")
    ap.add_argument("--iterations", type=int, default=0,
                    help="top: frames to render before exiting "
                    "(0 = until interrupted)")
    ap.add_argument("--once", action="store_true",
                    help="top: render exactly one frame and exit "
                    "(scripting mode)")
    ap.add_argument("--rounds", type=int, default=5,
                    help="soak: chaos rounds (fault schedules rotate "
                    "per round)")
    ap.add_argument("--concurrency", type=int, default=8,
                    help="soak: concurrent tenants")
    ap.add_argument("--queries", type=int, default=3,
                    help="soak: queries per tenant per round")
    ap.add_argument("--seed", type=int, default=7,
                    help="soak: deterministic action/schedule seed")
    ap.add_argument("--data", default=None,
                    help="soak: existing data directory (default: "
                    "generate into a temp dir)")
    # intermixed: `serve-client --port N "SELECT ..."` must parse (the
    # plain parser cannot allocate a positional after optionals)
    args = ap.parse_intermixed_args(argv)

    if args.command == "lint":
        # exit contract (docs/linting.md): 0 clean / 1 findings /
        # 2 internal error
        from spark_rapids_tpu.lint import run_cli
        return run_cli(root=args.root, as_json=args.json,
                       fix_baseline=args.fix_baseline,
                       fmt=args.lint_format,
                       changed_only=args.changed_only,
                       time_budget=args.time_budget)

    if args.command == "serve":
        return _serve_main(args)
    if args.command == "serve-client":
        return _serve_client_main(args, ap)

    if args.command == "top":
        from spark_rapids_tpu.telemetry.top import run_top
        target = args.sql or (str(args.port) if args.port else None)
        if not target:
            ap.error("top requires the server port (or host:port)")
        host, _, port_s = target.rpartition(":")
        try:
            port = int(port_s)
        except ValueError:
            ap.error(f"top: not a port: {target!r}")
        return run_top(port, host=host or args.host or "127.0.0.1",
                       interval=args.interval,
                       iterations=args.iterations, once=args.once)

    if args.command == "history":
        return _history_main(args, ap)
    if args.command == "doctor":
        return _doctor_main(args, ap)
    if args.command == "tuning":
        return _tuning_main(args, ap)

    if args.command == "soak":
        # chaos soak harness (docs/serving.md "Query lifecycle"):
        # exit 0 when every round completed with zero hangs, diverged
        # survivors, or post-drain leaks; 1 otherwise
        import json as _json

        from spark_rapids_tpu.soak import run_soak
        report = run_soak(rounds=args.rounds,
                          concurrency=args.concurrency,
                          queries_per_tenant=args.queries,
                          seed=args.seed, data_dir=args.data)
        print(_json.dumps(report, indent=2, default=str))
        return 0 if report["ok"] else 1

    if args.command == "profile":
        # offline renderer: a path argument means "render the written
        # profile artifacts" (spark.rapids.sql.profile.dir output);
        # SQL text keeps the live run-and-profile behavior below
        import os
        # an argument that LOOKS like a path but does not exist must
        # error like the trace command does, not fall through and run
        # "/tmp/.../profile-1.json" as SQL text
        looks_like_path = bool(args.sql) and (
            os.path.exists(args.sql) or args.sql.endswith(".json")
            or (os.sep in args.sql and " " not in args.sql))
        if looks_like_path and not os.path.exists(args.sql):
            print(f"no such profile file or directory: {args.sql}")
            return 1
        path = args.sql if looks_like_path else None
        if path is not None:
            from spark_rapids_tpu.profile import (format_profile,
                                                  read_profiles)
            n = 0
            for prof in read_profiles(path):
                if n:
                    print()
                print(format_profile(prof, top=args.top))
                n += 1
            if not n:
                print(f"no profile-*.json files in {path}")
                return 1
            return 0

    if args.command in ("trace", "hotspots"):
        import os
        path = args.sql or args.log
        if not path:
            ap.error("provide a trace file or directory "
                     "(spark.rapids.sql.trace.dir output)")
        # a path that does not exist is an ERROR (clean message, exit
        # 1, never a stack trace); an existing-but-empty trace dir is
        # a normal answer ("no spans found", exit 0) — an untraced or
        # idle-ring deployment must not fail automation that tails it
        if not os.path.exists(path):
            print(f"no such trace file or directory: {path}")
            return 1
        xplanes = profile_files(path)
        if xplanes and args.command == "trace":
            # a jax.profiler directory (or one .xplane.pb): the
            # engine's annotations beside the device planes
            for i, fp in enumerate(xplanes):
                if i:
                    print()
                print(format_profile_report(fp, top=args.top,
                                            query=args.query))
            return 0
        if os.path.isdir(path):
            files = sorted(
                os.path.join(path, f) for f in os.listdir(path)
                if f.startswith("trace-") and f.endswith(".json"))
            if not files:
                print(f"no spans found (no trace-*.json files in "
                      f"{path})")
                return 0
        else:
            files = [path]
        try:
            if args.command == "hotspots":
                print(hotspots_report(files, top=args.top))
                return 0
            for i, fp in enumerate(files):
                if i:
                    print()
                print(format_trace_report(fp, top=args.top,
                                          query=args.query))
        except (ValueError, KeyError) as e:  # incl. JSONDecodeError
            print(f"not a readable Chrome-trace file: {e}")
            return 1
        return 0

    if args.command == "docs":
        import os

        import spark_rapids_tpu.profile  # noqa: F401 - registers the
        #   spark.rapids.sql.profile.* conf entries before generate_docs
        import spark_rapids_tpu.trace  # noqa: F401 - registers the
        #   spark.rapids.sql.trace.* conf entries before generate_docs
        from spark_rapids_tpu.conf import generate_docs
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "configs.md"), "w") as f:
            f.write(generate_docs())
        with open(os.path.join(args.out, "supported_ops.md"), "w") as f:
            f.write(generate_supported_ops())
        with open(os.path.join(args.out, "observability.md"), "w") as f:
            f.write(generate_observability_docs())
        with open(os.path.join(args.out, "tuning.md"), "w") as f:
            f.write(generate_tuning_docs())
        print(f"wrote {args.out}/configs.md, {args.out}/supported_ops.md, "
              f"{args.out}/observability.md and {args.out}/tuning.md")
        return 0

    if args.log:
        print(qualify_log(args.log) if args.command == "qualify"
              else profile_log(args.log))
        return 0
    if not args.sql:
        ap.error("provide SQL text or --log <path>")

    from spark_rapids_tpu.sql.session import TpuSparkSession
    spark = TpuSparkSession({"spark.rapids.sql.enabled": "true"})
    try:
        for v in args.view:
            name, _, path = v.partition("=")
            spark.read.parquet(path).createOrReplaceTempView(name)
        df = spark.sql(args.sql)
        if args.command == "qualify":
            print(qualify(spark, df).format())
        else:
            print(profile(spark, df).format())
    finally:
        spark.stop()
    return 0




def _parse_since(raw, ap) -> float:
    """`--since` value -> unix-seconds lower bound: a number means
    that many seconds ago, anything else must parse as an ISO
    timestamp."""
    import datetime
    import time as _t
    try:
        return _t.time() - float(raw)
    except (TypeError, ValueError):
        pass
    try:
        return datetime.datetime.fromisoformat(str(raw)).timestamp()
    except ValueError:
        ap.error(f"--since: not seconds-ago or an ISO timestamp: "
                 f"{raw!r}")


def _history_main(args, ap) -> int:
    """`tools history <dir>`: per-signature/per-tenant table over the
    persistent query-history store, with trends
    (docs/observability.md 'Query history'). Exit 0 on a rendered
    report (an EMPTY store is a normal answer), 1 on a missing
    path."""
    import json as _json
    import os

    from spark_rapids_tpu.telemetry.history import (format_history,
                                                    read_records,
                                                    signature_aggregates)
    path = args.sql or args.history
    if not path:
        ap.error("history requires the history directory "
                 "(spark.rapids.sql.telemetry.history.dir output)")
    if not os.path.exists(path):
        print(f"no such history file or directory: {path}")
        return 1
    since = _parse_since(args.since, ap) if args.since else None
    sig = getattr(args, "signature", None)
    if sig and len(sig) == 40:
        # full digest: push the filter into the reader
        records = read_records(path, since=since, tenant=args.tenant,
                               signature=sig)
    else:
        records = read_records(path, since=since, tenant=args.tenant)
        if sig:
            # display prefix (tools print 12-hex): prefix-match here
            records = [r for r in records
                       if str(r.get("signature", "")).startswith(sig)]
    if args.json:
        print(_json.dumps({
            "records": len(records),
            "signatures": signature_aggregates(records),
        }, indent=2, default=str))
        return 0
    print(format_history(records, top=max(args.top, 10)))
    return 0


def _doctor_main(args, ap) -> int:
    """`tools doctor <queryId|signature> --history <dir>`: automated
    slow-query diagnosis against the signature's historical baseline
    (docs/observability.md 'tools doctor'). Exit 0 with a verdict, 1
    when the selector or the directory does not resolve."""
    import json as _json
    import os

    from spark_rapids_tpu.telemetry.doctor import (diagnose,
                                                   format_diagnosis)
    if not args.sql and not args.all:
        ap.error("doctor requires a queryId or signature selector "
                 "(or --all for the batch scan)")
    if not args.history:
        ap.error("doctor requires --history <dir> "
                 "(spark.rapids.sql.telemetry.history.dir output)")
    if not os.path.exists(args.history):
        print(f"no such history file or directory: {args.history}")
        return 1
    if args.all:
        # batch mode: every signature's newest record diagnosed
        # against its own baseline, worst regression first
        from spark_rapids_tpu.telemetry.doctor import (format_scan,
                                                       scan_signatures)
        scans = scan_signatures(args.history, top=max(args.top, 1))
        print(_json.dumps(scans, indent=2, default=str) if args.json
              else format_scan(scans))
        return 0
    d = diagnose(args.history, args.sql)
    print(_json.dumps(d, indent=2, default=str) if args.json
          else format_diagnosis(d))
    return 1 if d.get("error") else 0


def _tuning_main(args, ap) -> int:
    """`tools tuning --history <dir>`: inspect the TuningController's
    action ledger; --pin/--unpin/--revert write control flags into the
    state file, which the controller honors at its next tick (or at
    the next server start) — the CLI never races the live server's
    knob writes (docs/tuning.md). Exit 0 on a rendered report, 1 when
    the directory or the epoch does not resolve."""
    import json as _json
    import os

    from spark_rapids_tpu.telemetry.tuning import (format_tuning,
                                                   load_state,
                                                   save_state)
    path = args.sql or args.history
    if not path:
        ap.error("tuning requires the history directory "
                 "(spark.rapids.sql.telemetry.history.dir output)")
    if not os.path.isdir(path):
        print(f"no such history directory: {path}")
        return 1
    state = load_state(path)
    edits = [(args.pin, "pinned", True), (args.unpin, "pinned", False),
             (args.revert, "revertRequested", True)]
    for epoch, field, value in edits:
        if epoch is None:
            continue
        hit = next((a for a in state.get("actions", [])
                    if int(a.get("epoch", -1)) == epoch), None)
        if hit is None:
            print(f"no tuning action with epoch {epoch}")
            return 1
        hit[field] = value
        save_state(path, state)
        print(f"epoch {epoch}: {field} = {value}")
    if args.json:
        print(_json.dumps(state, indent=2, default=str))
        return 0
    print(format_tuning(state))
    return 0


def _serve_main(args) -> int:
    """`tools serve`: run the query server until interrupted
    (docs/serving.md). Views from --view name=path, confs from
    --conf key=value; --metrics-port adds the Prometheus HTTP twin."""
    import json as _json
    import signal
    import threading

    from spark_rapids_tpu.serve import QueryServer
    conf = {"spark.rapids.sql.enabled": "true"}
    for kv in args.conf:
        k, _, v = kv.partition("=")
        conf[k.strip()] = v.strip()
    srv = QueryServer(conf, host=args.host, port=args.port)
    srv.start()
    metrics_port = None
    if args.metrics_port is not None:
        metrics_port = srv.start_metrics_http(args.metrics_port)
    for v in args.view:
        name, _, path = v.partition("=")
        srv.register_view(name, path)
    print(_json.dumps({"event": "serving", "host": srv.host,
                       "port": srv.port,
                       "metricsPort": metrics_port,
                       "views": sorted(v.partition("=")[0]
                                       for v in args.view)}),
          flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    while not stop.is_set() and not srv._stopping.is_set():
        stop.wait(0.2)
    # graceful drain (docs/serving.md "Query lifecycle"): in-flight
    # queries finish inside serve.drainTimeoutMs, stragglers are
    # cooperatively cancelled, the process exits with the store empty
    from spark_rapids_tpu.conf import SERVE_DRAIN_TIMEOUT_MS, TpuConf
    drain_s = max(1.0, int(TpuConf(conf).get(
        SERVE_DRAIN_TIMEOUT_MS)) / 1000.0)
    drained = srv.shutdown(timeout=drain_s)
    print(_json.dumps({"event": "stopped", "drained": drained,
                       **srv.stats()}), flush=True)
    return 0


def _serve_client_main(args, ap) -> int:
    """`tools serve-client`: the client smoke command — one SQL round
    trip (or --stats) against a running server."""
    import json as _json

    from spark_rapids_tpu.serve import ServeClient
    if args.port is None:
        ap.error("serve-client requires --port")
    with ServeClient(args.port, host=args.host or "127.0.0.1",
                     tenant=args.tenant or "default") as c:
        if args.stats:
            print(_json.dumps(c.stats(), indent=2))
            return 0
        if not args.sql:
            ap.error("provide SQL text (or --stats)")
        batch, header = c.sql(args.sql)
        names = [f.name for f in batch.schema.fields]
        print("\t".join(names))
        for row in batch.rows():
            print("\t".join(str(v) for v in row))
        print(_json.dumps({k: header[k] for k in
                           ("rows", "queueWaitMs", "execMs",
                            "planCacheHit") if k in header}))
    return 0


def generate_supported_ops() -> str:
    """docs/supported_ops.md generator (the reference builds the same
    table from its rule registries, SupportedOpsDocs via
    TypeChecks.scala): one row per exec and per expression rule with
    its conf key, type signature, and compatibility notes. Everything
    is derived FROM the live registries, so the doc cannot drift from
    the code."""
    from spark_rapids_tpu import overrides as O
    from spark_rapids_tpu import typesig as TS

    def sig_str(sig) -> str:
        tags = sorted(sig.tags)
        s = ", ".join(tags)
        if "decimal" in sig.tags and sig.max_decimal_precision:
            s += f" (precision <= {sig.max_decimal_precision})"
        return s or "none"

    lines = [
        "# Supported operators and expressions",
        "",
        "Generated from the rule registries "
        "(`python -m spark_rapids_tpu.tools docs`); the per-op conf "
        "keys disable individual replacements, exactly like the "
        "reference's `spark.rapids.sql.exec.*` / "
        "`spark.rapids.sql.expression.*` keys.",
        "",
        "## Execs",
        "",
        "| Exec | Description | Conf key | Supported types |",
        "|---|---|---|---|",
    ]
    for cls, rule in sorted(O._EXEC_RULES.items(),
                            key=lambda kv: kv[1].name):
        lines.append(f"| {rule.name} | {rule.desc} | `{rule.conf_key}` "
                     f"| {sig_str(rule.checks.sig)} |")
    lines += [
        "",
        "## Expressions",
        "",
        "| Expression | Conf key | Output types | Input types | Notes |",
        "|---|---|---|---|---|",
    ]
    for cls, rule in sorted(O._EXPR_RULES.items(),
                            key=lambda kv: kv[1].name):
        note = rule.incompat or ""
        lines.append(
            f"| {rule.name} | `{rule.conf_key}` "
            f"| {sig_str(rule.checks.output)} "
            f"| {sig_str(rule.checks.inputs)} | {note} |")
    lines += [
        "",
        "## SQL dialect: relation lists, subqueries, WITH and windows",
        "",
        "`FROM a, b JOIN c ON ..., d` is a comma-separated list of "
        "relations, each with its own JOIN chain (JOIN binds tighter "
        "than the comma): a cross join until the WHERE's predicates "
        "say otherwise. An analysis rule "
        "(`sql/logical.py: rewrite_joins_and_subqueries`, Catalyst's "
        "ReorderJoin and PushPredicateThroughJoin without the costs) "
        "pushes the conjuncts that read one relation down to it and "
        "joins the relations in the text's order, each next one the "
        "first that a remaining conjunct connects to those joined so "
        "far, with those conjuncts as its inner-join condition; a "
        "relation nothing connects stays a cross join. Explicit "
        "`JOIN ... ON` chains are planned as written. Both engines "
        "plan the rewritten plan.",
        "",
        "| Form | What it does |",
        "|---|---|",
        "| `x IN (SELECT ...)`, uncorrelated, a conjunct of WHERE or "
        "HAVING | left semi join (RewritePredicateSubquery) on the "
        "side of the inner joins below that holds `x`'s columns, so "
        "the joins above see only the rows it keeps; a row is kept "
        "once however often it matches; a NULL `x` or no match drops "
        "the row |",
        "| `x NOT IN (SELECT ...)` | refused: `NotImplementedError` "
        "naming Spark's null-aware anti join, which it would need |",
        "| `IN (SELECT ...)` under OR/NOT/CASE or outside a filter | "
        "refused: `NotImplementedError` (it would need an existence "
        "join) |",
        "| `(SELECT ...)` as a scalar, uncorrelated | executed once "
        "and substituted as a literal before planning |",
        "| `[NOT] EXISTS (SELECT ... WHERE outer = inner AND ...)`, a "
        "conjunct of WHERE or HAVING | decorrelated: a name in the "
        "subquery's WHERE that only the enclosing FROM resolves is an "
        "outer reference; the top-level AND conjuncts that read outer "
        "columns are lifted into the condition of a left semi "
        "(`NOT EXISTS`: a plain left anti) join — its `outer = inner` "
        "equalities the keys, every other lifted conjunct (`<>`, `<`, "
        "an expression over several columns) the residual, evaluated "
        "on the device over each row's candidate build rows; a pair "
        "passes only where the residual is true, not null. The "
        "subquery's other conjuncts stay a filter beneath the build "
        "side, whose columns get fresh ids (a self-join reads one "
        "table under several aliases); the outer filter's other "
        "conjuncts stay beneath the join; placement as for `IN` "
        "(PushLeftSemiLeftAntiThroughJoin) |",
        "| an outer column read anywhere else in a subquery: its select "
        "list, an aggregate, GROUP BY, HAVING, beneath an aggregate or "
        "a join, under OR or NOT (a NOT over one comparison, `<>`, is "
        "plain), two levels up | refused: `NotImplementedError` naming "
        "the column or the conjunct; a name that resolves nowhere "
        "stays a `KeyError` |",
        "| a correlated scalar subquery (`= (SELECT min(...) WHERE "
        "...)`, TPC-H Q2/Q17/Q20), a correlated `IN (SELECT ...)` | "
        "refused: `NotImplementedError` naming the column and the "
        "form |",
        "| `EXISTS` under OR/NOT NOT/CASE or outside a filter; a "
        "correlation with no `outer = inner` equality (a nested-loop "
        "semi join); an uncorrelated `EXISTS` | refused: "
        "`NotImplementedError` by name |",
        "| `LEFT`/`RIGHT`/`FULL JOIN ... ON` with a residual (non-equi) "
        "conjunct | tagged to the CPU engine by name (`conditional "
        "left join runs on CPU`); under `test.forceDevice` an error. "
        "Inner, cross, left semi and left anti joins evaluate theirs "
        "on the device |",
        "| `WITH a AS (query), b AS (query) query` | a common table "
        "expression is in scope for the ones after it and for the "
        "statement (a derived table's own `WITH` for that derived "
        "table), shadows a catalog view of its name, and every "
        "reference is planned as the derived table it stands for: two "
        "references are two subplans, the second re-aliased under "
        "fresh ids by the join as a view read twice is. The plan "
        "cache and the fingerprints see the inlined plan |",
        "| `WITH RECURSIVE ...`, `WITH a (x, y) AS ...` | refused: "
        "`NotImplementedError` by name |",
        "| a window function in the select list of a grouped query "
        "(`sum(sum(x)) OVER (PARTITION BY k ORDER BY d ROWS BETWEEN "
        "UNBOUNDED PRECEDING AND CURRENT ROW)` under `GROUP BY k, d`) "
        "| Spark's Aggregate -> Window -> Project: the aggregates "
        "inside the window's arguments, partition keys and order keys "
        "are evaluated by the GROUP BY (grouping expressions matched) "
        "as those outside one, the window's own function by a Window "
        "node above it, after any HAVING |",
        "| a window function in HAVING or GROUP BY | refused: "
        "`NotImplementedError` by name |",
        "| a token the grammar has no place for | `ValueError` naming "
        "the token |",
        "",
        "## Window aggregates by source type",
        "",
        "`TpuWindowExec` (`exec/window.py`, the program `srt_window`) "
        "runs ranking and offset functions over any device type; its "
        "aggregates by the type of what they aggregate. Frames: the "
        "whole partition, running (ROWS or RANGE to the current row), "
        "bounded ROWS and value-bounded RANGE for sum / count / avg / "
        "min / max; first / last take the whole and the running "
        "frames. What is not here runs on the CPU engine and says so "
        "in the fallback report (under `test.forceDevice`: an error).",
        "",
        "| Source | On the device | Refused by name |",
        "|---|---|---|",
        "| BYTE, SHORT, INT, LONG, DATE, TIMESTAMP, BOOLEAN | sum, "
        "count, avg, min, max, first, last | DISTINCT aggregates |",
        "| FLOAT, DOUBLE | count, min, max, first, last; sum and avg "
        "under `spark.rapids.sql.variableFloatAgg.enabled` | |",
        "| DECIMAL, 64-bit (p <= 18) and two-limb (p <= 38) | sum "
        "(`decimal(min(38, p + 10), s)`, accumulated in 64 bits while "
        "that fits 18 digits and in two limbs past them; null where "
        "the sum passes the result's precision, non-ANSI), count, min, "
        "max, first, last (the source's type; nulls skipped, a frame "
        "without a value gives null) | avg (`window average over "
        "DecimalType ... runs on CPU`: a division into `decimal(p + 4, "
        "s + 4)`) |",
        "| STRING, BINARY | lag / lead only | every aggregate "
        "(`window aggregate over string runs on CPU`) |",
        "",
        "## Parquet device decode (encoding matrix)",
        "",
        "Device decode is the DEFAULT scan path "
        "(`spark.rapids.sql.format.parquet.deviceDecode.enabled`, on "
        "by default): the scan uploads still-encoded page bytes and "
        "decodes them in one XLA program per batch "
        "(io/device_decode.py + ops/rle.py), pipelined ahead of the "
        "consuming stage (docs/scan.md). Unsupported cells fall back "
        "PER COLUMN to the pyarrow host decode — results are "
        "bit-identical either way, and fallbacks are visible as "
        "`deviceFallbackColumns` / `hostDecodedValues.<ENC>` metrics. "
        "The `PERFILE`/`MULTITHREADED` reader types feed the device "
        "path; `COALESCING` keeps the host decode (its point is the "
        "one-table stitch). Compression is handled on the host: "
        "uncompressed, snappy, zstd, gzip, brotli (lz4 falls back). "
        "Per-encoding enables: `deviceDecode.byteArray.enabled`, "
        "`deviceDecode.delta.enabled`, "
        "`deviceDecode.byteStreamSplit.enabled`.",
        "",
        "| Type | PLAIN | PLAIN_DICTIONARY / RLE_DICTIONARY | "
        "DELTA_BINARY_PACKED / DELTA_LENGTH_BYTE_ARRAY | "
        "BYTE_STREAM_SPLIT | DELTA_BYTE_ARRAY |",
        "|---|---|---|---|---|---|",
        "| BOOLEAN | device (bit-unpack; v2 RLE pages too) | n/a | "
        "n/a | n/a | n/a |",
        "| INT32 (byte/short/int/date/decimal) | device | device | "
        "device (miniblock runs + seg prefix-sum) | device | n/a |",
        "| INT64 (long/timestamp-micros/decimal) | device | device | "
        "device (miniblock runs + seg prefix-sum) | device | n/a |",
        "| INT96 (legacy timestamp) | fallback | fallback | fallback "
        "| fallback | n/a |",
        "| FLOAT | device | device | n/a | device | n/a |",
        "| DOUBLE | device (backends with exact f64 bitcast; TPU "
        "falls back) | same | n/a | same | n/a |",
        "| FIXED_LEN_BYTE_ARRAY (decimal64/decimal128) | device "
        "(big-endian limb build) | device | fallback | fallback "
        "| n/a |",
        "| BYTE_ARRAY (string/binary) | device (offsets = segmented "
        "prefix-sum over lengths, bytes gather) | device (dictionary "
        "gather) | device (DELTA_LENGTH: host decodes lengths, device "
        "builds offsets + gathers bytes) | n/a | fallback |",
        "| nested (LIST/MAP/STRUCT, repeated) | fallback | fallback "
        "| fallback | fallback | fallback |",
    ]
    return "\n".join(lines) + "\n"


def metric_name_constants() -> List[Tuple[str, str]]:
    """Every metric-name constant defined in metrics.py (the drift
    guard's source of truth: a new metric constant MUST appear in the
    generated observability doc or tier-1 fails)."""
    from spark_rapids_tpu import metrics as M
    return sorted(
        (n, v) for n, v in vars(M).items()
        if n.isupper() and not n.startswith("_") and isinstance(v, str))


def generate_observability_docs() -> str:
    """docs/observability.md generator (`python -m spark_rapids_tpu.tools
    docs`): span model, trace configuration, how to open traces in
    Perfetto, how to read the offline reports, and the full metric-name
    reference derived from the LIVE metrics module so the doc cannot
    drift from the code."""
    from spark_rapids_tpu import conf as C
    from spark_rapids_tpu import profile as _profile  # registers confs
    from spark_rapids_tpu import trace as _trace  # registers trace confs

    assert _trace is not None and _profile is not None
    lines = [
        "# Observability: span tracing, metrics, event logs",
        "",
        "Generated by `python -m spark_rapids_tpu.tools docs`.",
        "",
        "## Span model",
        "",
        "With `spark.rapids.sql.trace.enabled` the engine records a",
        "Dapper-style span stream `(query_id, batch_id, chip, thread,",
        "kind, t0, t1, attrs)` at its existing choke points and writes",
        "ONE Chrome-trace JSON file per query (`trace-<pid>-q<n>.json`)",
        "under `spark.rapids.sql.trace.dir`:",
        "",
        "- every `MetricRegistry.timed`/`timed_wall` scope mirrors its",
        "  interval into a span named `<Exec>.<metric>` (reader",
        "  `FileScan.decodeTime`, upload",
        "  `TpuRowToColumnar.copyToDeviceTime` with the target chip,",
        "  exchange `TpuShuffleExchangeExec.partitionTime`, sort/join/",
        "  agg timers, `pipelineDrainTime`, ...) — the trace, the event",
        "  log, and the profiler read the SAME measurement;",
        "- device dispatches are explicit spans with the executing chip:",
        "  `TpuFusedStageExec.dispatch` (stage label, batch sequence) and",
        "  `TpuHashAggregateExec.dispatch` (mode);",
        "- the scan pipeline (docs/scan.md) adds `scanPrefetch` (the",
        "  producer thread's read+pack of one staged batch, mirrored",
        "  into the interval-union `scanPrefetchTime` metric),",
        "  `uploadAhead` (the async raw-chunk device_put issued ahead",
        "  of the consuming stage, with the target chip) and",
        "  `scanWait` (a consumer blocked on an empty prefetch ring);",
        "  inside `FileScan.deviceDecodeTime` a unit's plan is three",
        "  steps, each a span and an interval-union timer on the scan's",
        "  registry: `scanRead` (`what=footer|chunk`, `bytes=`;",
        "  `scanReadTime`, `scanReadBytes`), `scanParse` (one a column",
        "  chunk: page headers, level/run tables, dictionary pages,",
        "  string lengths, DELTA runs; `scanParseTime`, `scanPages`) and,",
        "  nested in it, `scanInflate` (the chunk's pages decompressed;",
        "  `scanInflateTime`, `scanInflatedBytes`; the parse timer",
        "  pauses for it) and `scanStrLengths` (one PLAIN or",
        "  DELTA_LENGTH byte-array page's value lengths, or one string",
        "  chunk with a PLAIN page decoded by pyarrow, `values=`,",
        "  `bytes=`; `scanStrLengthsTime`, a part of the parse);",
        "- JIT compiles are `compile` spans (attr `cache` = which LRU",
        "  missed); a thread that blocks on ANOTHER thread's",
        "  in-progress compile of the same key (single-flight) emits a",
        "  `compileCacheContention` instant and counts in the cache's",
        "  `contention` stat; semaphore waits are `semaphoreWait` spans;",
        "  store",
        "  tier movement is `spillToHost`/`spillToDisk`/",
        "  `promoteFromDisk`/`promoteToDevice`; the ICI exchange adds",
        "  `meshStack`/`meshSizeExchange`/`meshExchange` and",
        "  `exchangeMaterialize`;",
        "- retry machinery emits INSTANT markers (`retryOOM`,",
        "  `splitRetry`, `ioRetry`, `chipFailure`) plus a nested",
        "  `retryBlock` span covering the spill+backoff wall — the same",
        "  interval the `retryBlockTime` metric reads.",
        "",
        "A span that crosses a generator yield can resume on another",
        "thread; the exporter assigns such partially-overlapping spans",
        "to overflow lanes (`<thread>!k`) so every lane's B/E stream is",
        "strictly nested — the schema tests assert this invariant.",
        "",
        "### One id per query, on every record",
        "",
        "`session.execute_plan` opens one `trace.QueryScope` per query",
        "(`q` = process-wide id, tenant, `t_begin`, `parent` = the",
        "enclosing query's id for a scalar subquery) and stamps it on",
        "every metric registry of the executing plan",
        "(`trace.stamp_plan`, the way `memory.stamp_plan_tenant` stamps",
        "the tenant), so pool threads reach it from the registry they",
        "already hold; the task, scan-producer and shuffle pool threads",
        "also re-enter it as their thread's scope (`trace.attach`).",
        "Every span and instant carries `q` in its args (`parent` too",
        "for a subquery). The sinks stay process-wide — a query that",
        "arrives while another's file trace is open still lands in that",
        "file — but every record says whose it is: `tools trace` lists",
        "the queries of a file and `--query <id>` restricts the report",
        "to one. `session.sql` opens the scope ahead of execution (the",
        "parse is the first part of the query's `plan` time) and the",
        "query server opens it before admission, so `serveQueueWait`",
        "and `resultCacheHit` carry the id of the query they belong to.",
        "",
        "### The same spans on the profiler's clock",
        "",
        "Every span is ALSO a `jax.profiler.TraceAnnotation(kind, q=,",
        "batch=, program=, ...)` over the same interval, and every query",
        "a root `srt.query` annotation. This is not gated by",
        "`spark.rapids.sql.trace.enabled`: outside a profiler session an",
        "annotation is a flag test (about a microsecond), and inside",
        "one — the benchmark's `--trace 1`, `chip_smoke.py`, an",
        "operator's `jax.profiler.trace(dir)` — the engine's spans sit",
        "on the host planes beside the device's `XLA Ops`, on one",
        "clock, with no offset to estimate. An annotation must end on",
        "the thread that began it, so three recordings stay in the host",
        "stream only: `compile` through a cache's `get`/`put` pair",
        "(the interval is known only once it is over; `get_or_build`",
        "annotates), `serveQueueWait` billed to a batch-fusion member",
        "after the fact, and the host stream's own `srt.query` root",
        "(the profiler has the live one).",
        "",
        "### Program names",
        "",
        "Every device program is built through",
        "`jit_cache.named_jit(name, fn, **jit_kwargs)`: it sets",
        "`fn.__name__` and returns `jax.jit(fn)` itself (no wrapper, so a",
        "dispatch costs what it did), and XLA names the module",
        "`jit_<name>`. Names are `srt_<family>[_<tag>]`, at most 48",
        "characters of `[A-Za-z0-9_]`: `srt_stage_Filter_Project`,",
        "`srt_agg_partial` / `_merge` / `_merge_partial` / `_final` /",
        "`_complete`, `srt_sort`,",
        "`srt_topn`, `srt_join_build` / `_probe` / `_gather_<type>` /",
        "`_gather_fast_<type>` / `_mask` / `_extras`, `srt_decode`,",
        "`srt_upload_decode`, `srt_fetch_pack`,",
        "`srt_concat`, `srt_shrink`, `srt_compact`, `srt_project`,",
        "`srt_filter`, `srt_window`, `srt_generate`, `srt_expand`,",
        "`srt_exchange_pid` / `_range_keys` / `_range_rank` /",
        "`_split_sort` / `_extract` / `_round_robin`, `srt_ici_exchange`,",
        "`srt_ici_sizes`, `srt_mesh_agg_step`. **The rule:** a name is a",
        "function of the program's structural key and nothing else — no",
        "literal value, no capacity, no `hash()`, no counter, no",
        "address. JAX's persistent compilation cache keys on the",
        "module's name, so a name that varies between processes misses",
        "there every time (a cold set-up compiles for minutes). Inside a",
        "program, `jax.named_scope` marks each constituent operator of a",
        "fused stage (`Filter`, `Project`) and the aggregate's steps",
        "(`agg_inputs`, `groupby_sort`, `groupby_reduce`, `compact`,",
        "`agg_result`) and the lanes of the Parquet page decode",
        "(`srt_decode`: `decode_page_lookup` (only for a column whose",
        "chunk needs its page table: not dictionary pages followed by PLAIN",
        "ones), `decode_bits` with `/bytes` (staging words to bytes, for the",
        "lanes that read bytes: BYTE_STREAM_SPLIT alone),",
        "`/run_fields` (a run's fields to its lanes by prefix sum)",
        "and `/window` (the two aligned staging words a packed value",
        "lies in, gathered and shifted together) inside",
        "it, `decode_dict`, `decode_plain` with `/window` (one",
        "contiguous window of the staging words, de-interleaved at the",
        "value's fixed stride) and `/lanes` (the values moved to their",
        "first dense lane) inside it, `decode_chars` (a string value's",
        "window of `char_cap/4 + 1` staging words gathered, shifted into",
        "place by its phase and read as its bytes; `deviceDecodeStringWords`",
        "counts the words),",
        "`decode_delta`, `decode_rows` — docs/scan.md §1) and the three",
        "steps of a conditional semi/anti join's rank loop",
        "(`srt_join_cond_mask`: `join_cond/gather`, `/eval`, `/reduce`)",
        "and the steps of a join's key plan (`srt_join_probe`,",
        "`srt_join_mask`; `srt_join_build` runs the middle two:",
        "`join_plan/keys`, `/sort` (both sides' key words sorted",
        "together), `/extents` (each key's run and its counts by prefix",
        "sum), `/inverse` (the counts back to input order by a second",
        "sort) and `/right_order` (the build rows in key order by a",
        "third)) and the grouping-sets expansion's (`srt_expand`:",
        "`expand/compact` (a batch whose rows fit a smaller capacity",
        "bucket moved to the front of it), `expand/project` (each",
        "distinct expression of the sets evaluated once, a key a set",
        "leaves out nulled) and `expand/write` (the sets written one",
        "after another into one output batch); `expandTime` and",
        "`expandRows` time and count it on the host, `aggKeyWords` the",
        "group-key words the aggregate passes after it sort or gather",
        "(the benchmark's `expand_host_s`, `expand_rows` and",
        "`agg_key_words` read the three);",
        "scopes are",
        "op_name metadata and change no compiled code. The tpu-lint",
        "`jit-direct` rule treats",
        "`named_jit` as `jax.jit`. Dispatch spans, `compile` spans and",
        "the `firstDispatch` instant carry `program=<name>`.",
        "",
        "### Intervals no operator timer covers",
        "",
        "Always-on timers of one process-wide registry (owner `Query`;",
        "two clock reads each), each mirrored to a span kind:",
        "",
        "| timer | span | where | what |",
        "|---|---|---|---|",
        "| `planTime` | `plan` (`phase=parse` / `rewrite`, `cacheHit=`,"
        " `folded=`;"
        " inside `rewrite`, untimed, `phase=subquery` when a comma list,"
        " an `IN (subquery)` or an `EXISTS` is rewritten into joins, and"
        " inside that `phase=decorrelate`, one per correlated"
        " `[NOT] EXISTS`)"
        " | `session.sql`; `execute_plan` up to `execute_collect` |"
        " parse, analysis, overrides, plan cache, fingerprints, on the"
        " calling thread |",
        "| `firstDispatchTime` | `firstDispatch` (instant, `program=`)"
        " | the query's first enqueue of any device program: page"
        " decode (`columnar/transfer.py`), fused stage, aggregate, join"
        " probe, sort | ns from the query's begin to that enqueue,"
        " once per query |",
        "| `deviceSyncTime` | `deviceSync` (`site=`) | every host read"
        " of a device value: `rowCount` (`DeviceBatch.row_count`),"
        " `fetch` (`finish_fetch`, so `to_host`/collect), `aggCounts`"
        " and `aggMerge` (the aggregate's counts and overflow flags),"
        " `joinSize`, `joinBuild`, `joinCondPairs` (the candidate pairs"
        " of a conditional semi/anti join, read from the count program"
        " once the mask program is enqueued), `exchangeSplit`, `iciSizes`,"
        " `ansiError`, `expandRows` (a batch's row count, which sizes the"
        " grouping-sets expansion's output) | thread-ns blocked, summed"
        " over task threads |",
        "| `scanStarvedTime` | `scanWait` (`chip=`; only a pull that"
        " found the ring empty) | `TpuRowToColumnarExec`'s consumer of a"
        " scan stream (`exec/base.py`); `QueryScope.scan_streams` counts"
        " the query's open streams (first pull to last batch handed on)"
        " and those waiting | ns in which every open stream of the query"
        " waited at once: host starvation of the scan streams, including"
        " time the device is busy with queued work (not device idle) |",
        "| `gcPauseTime`, `gcCollections.<gen>` | `gcPause` (`gen=`,"
        " generations 1 and 2 only) | one `gc.callbacks` hook registered"
        " at engine start (`trace.install_gc_hook`) | the interpreter's"
        " collections on any thread, process-wide |",
        "",
        "## Configuration",
        "",
        "| Key | Default | Description |",
        "|---|---|---|",
    ]
    for e in sorted(C.registered_entries(), key=lambda e: e.key):
        if e.key.startswith(("spark.rapids.sql.trace.",
                             "spark.rapids.sql.profile.",
                             "spark.rapids.sql.telemetry.")) \
                or e.key == "spark.rapids.sql.explain":
            lines.append(f"| {e.key} | {e.default} | {e.doc} |")
    lines += [
        "",
        "Sampling: with `sampleRate < 1.0` the Nth traced-candidate",
        "query of the process is traced iff the Nth draw of the",
        "`sampleSeed`-seeded stream falls below the rate — a fixed seed",
        "gives a deterministic, reproducible sample (production traces",
        "a stable subset at bounded overhead; the bench measures the",
        "overhead in `detail.trace`).",
        "",
        "## Opening traces in Perfetto",
        "",
        "1. run a query with `spark.rapids.sql.trace.enabled=true`;",
        "2. open https://ui.perfetto.dev (or chrome://tracing) and drag",
        "   the `trace-<pid>-q<n>.json` file in;",
        "3. lanes are the engine's real threads (`srt-task-*` task",
        "   threads, `srt-multifile-*` reader pool, `srt-pack` upload",
        "   stagers); click a span for its attrs (chip, batch, rows,",
        "   path, cache); instant markers show retries/splits.",
        "",
        "## Reading the offline reports",
        "",
        "`python -m spark_rapids_tpu.tools trace <file-or-dir>` prints:",
        "",
        "- **critical path** — backward walk from the last span end:",
        "  at every instant the most-recently-started covering span",
        "  owns the segment, uncovered gaps are idle. Only work ON this",
        "  chain bounds the query wall; optimize it first.",
        "- **exclusive self-time** — per span name, total minus",
        "  directly nested spans (same lane). This undoes the",
        "  documented double counts at the reporting layer: e.g.",
        "  `retryBlock` (spill+backoff) nests inside operator timers,",
        "  so operators' self-time no longer absorbs retry stalls.",
        "- **queries** — the query ids in the file with their span",
        "  counts and longest kinds; `--query <id>` restricts every",
        "  section to one.",
        "- **per-chip enqueue occupancy** — the union of the HOST",
        "  intervals of chip-attributed spans (uploads, dispatches):",
        "  when programs were enqueued for a chip, not when it ran",
        "  them (dispatch is asynchronous). Mesh skew shows up here;",
        "  the device's own busy time is in a profiler trace.",
        "- **top slowest spans** and **instant marker counts** (retry",
        "  storms surface here).",
        "",
        "`python -m spark_rapids_tpu.tools trace <profile dir>` takes a",
        "`jax.profiler` directory (`plugins/profile/*/*.xplane.pb`, or",
        "one such file) instead: the host analyzers run over the",
        "engine's annotations, and per chip the device planes give",
        "**occupancy** (busy = union of the `XLA Ops` intervals, within",
        "the queries' extent), **idle by host span** (each gap of 1 ms",
        "or more goes, instant by instant, to the deepest engine span",
        "covering it on any host thread — the one that started last —",
        "or to `no_span`; a wait span, `scanWait` or `semaphoreWait`,",
        "owns an instant only where no work span covers it, and the",
        "`srt.query` root only where nothing else does; totals by kind",
        "and by `q`), and **device time by program and named scope**.",
        "",
        "How to find why the device was idle: (1) record a profile"
        " around the queries (`jax.profiler.trace(dir)`, or the"
        " benchmark's `--trace 1`); (2) `tools trace <dir>`; (3) read"
        " `idle by the host span`: `plan`/`scanPrefetch` before"
        " `firstDispatch` is start-up, `scanRead`/`scanInflate`/"
        "`scanParse` name the step of a unit's decode plan,"
        " `scanWait` a consumer with nothing to hand on, `gcPause` the"
        " interpreter collecting, `deviceSync site=...` is the"
        " host waiting for a result it needs before it can enqueue"
        " more, `no_span` is host code nobody has instrumented; (4)"
        " `longest gaps` gives each gap's time, owner and `q`; (5)"
        " `device time by program` says what the busy part ran.",
        "",
        "`bench.py` runs a traced q1 leg (`detail.trace`): occupancy,",
        "critical-path breakdown, and measured tracing overhead vs the",
        "untraced wall (the overhead budget is <= 15%, asserted by",
        "tests/test_trace.py on the smoke input).",
        "",
        "## Reading a query profile",
        "",
        "With `spark.rapids.sql.profile.enabled` every executed query",
        "writes ONE artifact (`profile-<pid>-q<n>.json` under",
        "`spark.rapids.sql.profile.dir`) unifying the annotated plan,",
        "the HBM accounting, and the rewrite explain. Render it with",
        "`python -m spark_rapids_tpu.tools profile <file-or-dir>`:",
        "",
        "- **annotated plan tree** — the final physical plan (fused",
        "  stages with their constituents), each node with its full",
        "  metric registry: rows/batches, operator timers, jit-cache",
        "  hits/misses, retry/split/spill counters. A `*` marks device",
        "  operators.",
        "- **top memory consumers** — the owner-attributed HBM ledger:",
        "  every `SpillableBatch` is tagged with the registering",
        "  operator (`TpuExec.register_spillable`), so the store keeps",
        "  live/peak bytes PER OPERATOR next to the pool watermarks.",
        "  The per-op live bytes always sum to the pool's live bytes;",
        "  the pool peak never exceeds the sum of per-op peaks. Spills",
        "  are billed to the owning operator (`spillBytes`), and each",
        "  op's `peakDeviceMemory` metric mirrors its ledger peak.",
        "- **fallback summary** — operator coverage plus the explain",
        "  reasons aggregated by frequency (see below).",
        "",
        "With tracing ALSO enabled, the store emits Chrome-trace",
        "counter events (`deviceStoreBytes`/`hostStoreBytes`), so",
        "Perfetto shows the HBM/host pool occupancy timeline in a",
        "`counters` lane next to the query's spans.",
        "",
        "`bench.py` runs a profiled q1+q3 leg (`detail.profile`):",
        "per-op peak HBM, explain coverage counts, and the measured",
        "profiling overhead vs the clean wall (budget <= 15%).",
        "",
        "## Explain / fallback reasons",
        "",
        "`spark.rapids.sql.explain=NOT_ON_TPU` prints one line per",
        "operator/expression that stayed on CPU:",
        "",
        "    !Exec <CpuProjectExec> cannot run on TPU because",
        "    expression PythonUDF <...> is not supported on TPU",
        "",
        "`ALL` additionally lists `*Exec <...> will run on TPU` for",
        "every placed operator (`NOT_ON_GPU` is accepted as an alias).",
        "Expression-level reasons name the OFFENDING SUBTREE, so a",
        "failure deep inside a projection is attributable without",
        "replaying the rewrite. The same report aggregates per query",
        "into the profile artifact's `explain` section (device ops,",
        "coverage, reason histogram) and the event log's",
        "`fallbackSummary` field; `tools qualify` scores whole",
        "workloads with it.",
        "",
        "## Event log (v2)",
        "",
        "Event lines (`spark.rapids.sql.eventLog.dir`) carry",
        "`version: 2`: per-op metrics now INCLUDE zero values (an op",
        "that saw 0 rows is distinguishable from one whose metric never",
        "existed), plus a compact snapshot of the session's explicit",
        "conf settings and the fault-injector summary when injection is",
        "active; each line also carries the per-query `fallbackSummary`",
        "(coverage + reason histogram) and `memoryByOperator` (the",
        "per-op peak/live HBM ledger). `read_events` still reads v1",
        "lines (version normalized to 1). Queries executed through the",
        "query server additionally carry `tenant` (docs/serving.md) —",
        "the same id appears in the profile artifact and the trace",
        "file's `otherData.tenant`, and admission waits show up as",
        "`serveQueueWait` spans.",
        "",
        "## Live telemetry",
        "",
        "The serving tier's always-on observability layer",
        "(spark_rapids_tpu/telemetry/): file traces and profile",
        "artifacts are opt-in *per query*, but on a long-lived",
        "multi-tenant server the interesting query is the one you",
        "didn't pre-instrument — the p99 outlier, the retry storm, the",
        "tenant whose ledger tripped an over-share spill.",
        "",
        "### Flight recorder (`spark.rapids.sql.trace.mode=ring`)",
        "",
        "The existing Tracer grows a second sink: a fixed-size,",
        "lock-free ring buffer keeping the last",
        "`spark.rapids.sql.trace.ringSpans` spans/instants/counter",
        "samples PER THREAD, always on (query server sessions default",
        "to it), bounded memory, near-zero overhead (the bench's",
        "`detail.telemetry` leg measures the q1 ring-on/off ratio",
        "against a <= 1.05x budget). `telemetry.dump_ring(dir)` — or a",
        "trigger firing — writes the rings as a standard Chrome-trace",
        "file (`trace-ring-<pid>-<n>.json`), so Perfetto,",
        "`tools trace` and `tools hotspots` work unchanged on dumps.",
        "`tools trace`/`tools hotspots` on an empty or span-free trace",
        "directory print `no spans found` and exit 0 (an idle recorder",
        "is a normal answer, not an error); a nonexistent path errors",
        "with exit 1.",
        "",
        "### Triggers and slow-query bundles",
        "",
        "Declarative conditions evaluated where they become true, each",
        "emitting one *bundle* (`bundle-<pid>-<n>-<trigger>.json`",
        "under `spark.rapids.sql.telemetry.dir`) that ties together",
        "the ring dump, the query's profile-artifact path (when",
        "profiling is on), a server stats snapshot (when a QueryServer",
        "is up), the device-store stats, and the triggering condition:",
        "",
        "| Trigger | Condition | Evaluated at |",
        "|---|---|---|",
        "| slowQuery | query wall > telemetry.slowQueryMs | query "
        "close |",
        "| retryCount | per-query retry+split deltas > telemetry."
        "retryCountThreshold | query close |",
        "| retryStorm | > telemetry.retryStormThreshold OOM retries "
        "in a 60 s window | retry time |",
        "| hbmWatermark | store live bytes > telemetry.hbmWatermark x "
        "pool budget | every store transition |",
        "| queueSaturation | admission depth > telemetry."
        "queueWatermark x serve.maxQueued | every enqueue |",
        "| stuckQuery | elapsed wall > serve.watchdogFactor x the "
        "plan-cache signature's observed p99 | the lifecycle "
        "watchdog's periodic scan (docs/serving.md 'Query "
        "lifecycle'; with serve.watchdogCancel the query is also "
        "cancelled) |",
        "| sloBurn | a tenant's observed p99 over the history window "
        "> its serve.slo.p99Ms objective | query close on the server "
        "(see 'SLO tracking' below) |",
        "",
        "Per-trigger rate limiting (`telemetry.triggerMinIntervalS`)",
        "bounds disk pressure under a storm (suppressed firings count",
        "in the engine stats and on the endpoint); bundle IO runs on a",
        "dedicated daemon thread so no query, store or admission path",
        "blocks on a file write. The store/admission/retry triggers",
        "arm when any session sets a `spark.rapids.sql.telemetry.*`",
        "conf. Artifact sprawl is bounded: bundles and ring dumps in",
        "`telemetry.dir` beyond `telemetry.maxBundles` (or",
        "`telemetry.maxBundleBytes` total) are pruned OLDEST-FIRST by",
        "the bundle-worker thread after each write — never under a",
        "hot-path lock; pruned counts show in the engine stats, the",
        "server stats `telemetry` section, and",
        "`srt_telemetry_bundles_pruned_total`.",
        "",
        "### Prometheus endpoint",
        "",
        "The QueryServer's `metrics` protocol verb (alias",
        "`stats-stream`; `ServeClient.metrics()`), and the",
        "`tools serve --metrics-port N` HTTP twin (`GET /metrics`),",
        "export one text exposition per scrape: every registry metric",
        "as `srt_<snake_case>[_seconds]_total` (prefix families like",
        "`deviceDecodedValues.PLAIN` become one family with a",
        "`key` label; `*Time` metrics convert ns to seconds; `peak*`",
        "metrics are gauges folded by MAX across registries, not",
        "summed — a high-watermark, never a sum of dead plans' peaks),",
        "HELP text from `describe_metric` — an",
        "undescribed key is NOT exported and counts in",
        "`srt_undescribed_metric_keys`, which tier-1 asserts is 0.",
        "Scrapes run through a registry-delta aggregator: per-registry",
        "snapshots are cached against metric mutation counters (a",
        "scrape re-reads only registries that changed) and registries",
        "garbage-collected with their plans fold into a retired base,",
        "so counters stay MONOTONE across plan lifetimes. Server-level",
        "families:",
        "",
        "| Family | Type | Help |",
        "|---|---|---|",
    ]
    from spark_rapids_tpu.telemetry.prometheus import SERVER_FAMILY_HELP
    for name, (ftype, help_text) in sorted(SERVER_FAMILY_HELP.items()):
        lines.append(f"| `{name}` | {ftype} | {help_text} |")
    lines += [
        "",
        "`tools top <port>` renders a refreshing terminal table over",
        "the same stats (tenants x QPS / p50 / p99 / queue wait / live",
        "HBM / in-flight / rejections; `--interval`, `--iterations`,",
        "`--once` for scripting). A server that goes away mid-poll is",
        "a clean exit (message + code 0); a failed initial connect",
        "exits 1.",
        "",
        "### Query history",
        "",
        "`spark.rapids.sql.telemetry.history.dir` turns on the",
        "persistent query-history store: ONE compact JSONL record per",
        "finished query, appended at query close by",
        "`session.execute_plan` (every terminal status it sees) and by",
        "the query server (outcomes the session never starts, e.g.",
        "cancelled while queued). Storage is crash-safe and bounded:",
        "records are single JSON lines in rotated segments",
        "(`history-<ms>-<pid>-<seq>.jsonl`), compacted",
        "whole-segment-at-a-time by `telemetry.history.maxBytes` and",
        "`telemetry.history.maxAgeDays` (a torn tail line from a crash",
        "is skipped by the reader, never propagated). The record",
        "schema (`HISTORY_FIELD_CATALOG`; the tpu-lint `history-field`",
        "rule pins record construction to it):",
        "",
        "| Field | Meaning |",
        "|---|---|",
    ]
    from spark_rapids_tpu.telemetry.history import HISTORY_FIELD_CATALOG
    for fname, fdesc in sorted(HISTORY_FIELD_CATALOG.items()):
        lines.append(f"| `{fname}` | {fdesc} |")
    lines += [
        "",
        "**Warm-start** (`telemetry.history.warmStart`, on by default",
        "when the dir is set): at server start the history replays",
        "into the lifecycle layer — finished records seed the",
        "stuck-query watchdog's per-signature p99 reservoirs and clear",
        "failure streaks, failed records replay the quarantine",
        "streaks — so a restarted server can tell \"stuck\" from",
        "\"first time\" from query one, and a poison signature stays",
        "fail-fast across restarts. Cancelled/timed-out/quarantined",
        "records never count, the same rules as the live paths.",
        "",
        "### SLO tracking",
        "",
        "`spark.rapids.sql.serve.slo.p99Ms` (per-tenant override",
        "`serve.slo.p99Ms.<tenant>`) sets a latency objective: the",
        "tenant's observed p99 wall over the last `serve.slo.window`",
        "seconds of query history must stay under it. The server",
        "evaluates objectives over the history store (cached ~1 s),",
        "exposes them in its stats (`slo` section) and as the",
        "`srt_slo_*` Prometheus families (objective, observed p99,",
        "window queries, violations, burn ratio — gauges, because the",
        "window slides), and fires a rate-limited `sloBurn` bundle",
        "through the trigger engine when the observed p99 exceeds the",
        "objective.",
        "",
        "### `tools history`",
        "",
        "`tools history <dir> [--since N|ISO] [--tenant T]",
        "[--signature D] [--json]` renders the store as a",
        "per-signature table (count, wall p50/p99, trend slope in",
        "seconds-of-wall per hour-of-history, retry/fallback rates,",
        "status histogram, tenants) plus a per-tenant rollup.",
        "`--signature` restricts the report to one signature digest —",
        "the full 40-hex form is pushed into the reader's",
        "`read_records(signature=)` filter, a shorter prefix (the",
        "12-hex display form the tools print) matches by prefix. An",
        "empty store is a normal answer (exit 0); a missing path",
        "exits 1.",
        "",
        "### `tools doctor`",
        "",
        "`tools doctor <queryId|signature> --history <dir> [--json]`",
        "answers \"why was this query slow\" automatically: it joins",
        "the query's history record, profile artifact, and trace",
        "against the signature's historical baseline (the other",
        "finished records of the same shape), diffs per-stage",
        "self-times stage by stage (profile time metrics aggregated by",
        "stage key — `retryBlockTime` -> `retryBlock`), and emits a",
        "ranked verdict with evidence lines. `tools doctor --all",
        "--history <dir> [--top N]` is the batch mode: every",
        "signature's NEWEST finished record is diagnosed against its",
        "own baseline in one store read, ranked regressed-first then",
        "by slowdown — the triage view after a bad deploy (the",
        "TuningController's scan tick runs the same walk,",
        "docs/tuning.md). The verdict taxonomy:",
        "",
        "| Verdict | Meaning |",
        "|---|---|",
    ]
    from spark_rapids_tpu.telemetry.doctor import VERDICT_CLASSES
    for vname, vdesc in sorted(VERDICT_CLASSES.items()):
        lines.append(f"| `{vname}` | {vdesc} |")
    lines += [
        "",
        "### Self-tuning (`tools tuning`)",
        "",
        "`spark.rapids.sql.serve.tuning.enabled` closes the",
        "observe-diagnose-act loop: the server embeds a",
        "TuningController that scores the query history through the",
        "aggregate + doctor pipeline at start and on a periodic tick",
        "and applies bounded, logged, reversible actions from the",
        "declared ACTION_CATALOG — see docs/tuning.md for the action",
        "table, the guardrail/rollback state machine, and the",
        "pin/revert workflow. Every action lands in the history store",
        "as a `tuning` record (rollbacks as `revert`); both statuses",
        "are control-plane records EXCLUDED from signature aggregates,",
        "SLO windows, doctor baselines, and warm-start replay, so the",
        "controller's own audit trail never moves the statistics it",
        "steers by. Controller state exports as the `srt_tuning_*`",
        "families above; `tools tuning --history <dir>` renders the",
        "action ledger, `--pin/--unpin/--revert <epoch>` write control",
        "flags the controller honors at its next tick.",
        "",
        "### Span catalog",
        "",
        "Every explicit span/instant kind the engine records (the",
        "tpu-lint `span-kind` rule pins literal recording sites to",
        "these tables; metric-mirror spans are the dynamic",
        "`<Exec>.<metric>` family covered by `metric-key`):",
        "",
        "| Span kind | Meaning | Read by |",
        "|---|---|---|",
    ]
    from spark_rapids_tpu.trace import (INSTANT_CATALOG, KIND_READERS,
                                        SPAN_CATALOG)
    for kind, desc in sorted(SPAN_CATALOG.items()):
        lines.append(f"| `{kind}` | {desc} | {KIND_READERS[kind]} |")
    lines += ["", "| Instant kind | Meaning | Read by |", "|---|---|---|"]
    for kind, desc in sorted(INSTANT_CATALOG.items()):
        lines.append(f"| `{kind}` | {desc} | {KIND_READERS[kind]} |")
    lines += [
        "",
        "## Metric-name reference",
        "",
        "Derived from the central description table",
        "(`spark_rapids_tpu.metrics.METRIC_DESCRIPTIONS`); tier-1",
        "asserts every metric-name constant appears here AND that every",
        "metric a `Tpu*Exec` registers at runtime resolves in the table",
        "(the \"new metric, stale docs\" drift guard, now a lint over",
        "the live registries).",
        "",
        "| Metric key | Description |",
        "|---|---|",
    ]
    from spark_rapids_tpu.metrics import (METRIC_DESCRIPTIONS,
                                          METRIC_PREFIX_DESCRIPTIONS)
    for name, desc in sorted(METRIC_DESCRIPTIONS.items()):
        lines.append(f"| `{name}` | {desc} |")
    for prefix, desc in sorted(METRIC_PREFIX_DESCRIPTIONS.items()):
        lines.append(f"| `{prefix}*` | {desc} |")
    # the constants table keeps the original drift guard anchored: a
    # new metrics.py constant must surface here (and therefore in
    # METRIC_DESCRIPTIONS, which the lint test cross-checks)
    lines += ["", "| Constant | Metric key |", "|---|---|"]
    for const, name in metric_name_constants():
        lines.append(f"| {const} | `{name}` |")
    return "\n".join(lines) + "\n"


def generate_tuning_docs() -> str:
    """docs/tuning.md generator (`python -m spark_rapids_tpu.tools
    docs`): the feedback-control loop, the action catalog rendered
    LIVE from ACTION_CATALOG (so docs cannot drift from the declared
    vocabulary), the guardrail state machine, and the operator
    pin/revert workflow."""
    from spark_rapids_tpu import conf as C
    from spark_rapids_tpu.telemetry.tuning import ACTION_CATALOG
    lines = [
        "# Self-tuning: history-driven feedback control",
        "",
        "Generated by `python -m spark_rapids_tpu.tools docs`.",
        "",
        "`spark.rapids.sql.serve.tuning.enabled` (requires",
        "`spark.rapids.sql.telemetry.history.dir`) embeds a",
        "**TuningController** in the query server. At server start and",
        "every `serve.tuning.intervalS` seconds it scores the",
        "persistent query history through the `signature_aggregates` +",
        "doctor-verdict pipeline (the same walk `tools doctor --all`",
        "runs) and applies per-signature actions from the declared",
        "catalog below. Tuning never changes what a query COMPUTES —",
        "only admission shaping and cache residency,",
        "both bit-identity-preserving by their own contracts",
        "(tier-1 asserts results are identical with tuning on vs",
        "off).",
        "",
        "Every action is:",
        "",
        "- **bounded** — per-knob min/max clamps declared in the",
        "  catalog; at most `serve.tuning.maxActionsPerTick` new",
        "  actions per tick;",
        "- **logged** — a `tuning` history record (action, scope,",
        "  knob, old->new value, evidence, epoch) in the same store as",
        "  query records; rollbacks log a `revert` record. Both are",
        "  control-plane statuses EXCLUDED from aggregates, SLO",
        "  windows, doctor baselines, and warm-start replay;",
        "- **exported** — the `srt_tuning_*` Prometheus families",
        "  (ticks, actions by name, reverts, active/pinned counts,",
        "  pre-warmed signatures);",
        "- **inspectable and reversible** — `tools tuning` below;",
        "- **guarded** — the post-action baseline is watched and the",
        "  action auto-reverts on regression (state machine below).",
        "",
        "## Action catalog",
        "",
        "Rendered from `telemetry.tuning.ACTION_CATALOG` — the",
        "tpu-lint `tuning-action` rule pins every action the",
        "controller constructs to this table, and every",
        "`spark.rapids.*` knob in it to a registered conf key.",
        "Internal knobs (`signatureConcurrency`, `tenantWeight`,",
        "`prewarm`) actuate the admission controller and the pre-warm",
        "ledger directly.",
        "",
        "| Action | Trigger verdict | Knob | Bounds | What it does |",
        "|---|---|---|---|---|",
    ]
    for name, cat in sorted(ACTION_CATALOG.items()):
        lines.append(
            f"| `{name}` | {cat['verdict']} | `{cat['knob']}` | "
            f"[{cat['min']}, {cat['max']}] | {cat['doc']} |")
    lines += [
        "",
        "## Guardrail / rollback state machine",
        "",
        "Each applied action captures the pre-action p50/p99 baseline",
        "of its scope (a signature digest, or `tenant:<id>`) in its",
        "evidence. States:",
        "",
        "```",
        "            apply                      window fills, no",
        " (decided) -------> applied ---------> regression: accepted",
        "                      |  \\",
        "                      |   \\ tools tuning --revert",
        "                      |    \\ (honored at next tick)",
        "   guardrail:         |     v",
        "   p50/p99 regressed  +--> reverted  (a `revert` record",
        "   past threshold            logs old value restored)",
        "```",
        "",
        "- once `serve.tuning.guardWindowQueries` post-action",
        "  finished records exist for the scope (cache-served and",
        "  control-plane records excluded), the controller computes",
        "  `change = (baseline - observed) / baseline` for p50 and",
        "  p99, lower being better;",
        "- `change < -serve.tuning.revertThreshold` on either",
        "  percentile auto-reverts: the knob's old value is restored",
        "  and a `revert` record lands with the observed window as",
        "  evidence;",
        "- otherwise the action graduates to **accepted** (still",
        "  manually revertible);",
        "- **pinned** actions are exempt from auto-revert;",
        "",
        "Applied/accepted actions persist in",
        "`<history.dir>/tuning-state.json` and re-actuate at the next",
        "server start: a retry-storm shape admitted narrowly today is",
        "admitted narrowly tomorrow, and the pre-warm ledger's",
        "recorded SQL replays through the planning path before the",
        "first client request.",
        "",
        "## Fault injection (`site:tuning:N`)",
        "",
        "`spark.rapids.sql.test.injectOOM=site:tuning:N` makes the Nth",
        "controller tick apply a deliberately HARMFUL synthetic action",
        "(a concurrency clamp recorded against an epsilon baseline),",
        "so the observe-and-revert loop is deterministically testable",
        "end to end — the injected action must auto-revert within the",
        "guard window, visible in `tools tuning`, the history store,",
        "and the `srt_tuning_*` families.",
        "",
        "## Operator workflow (`tools tuning`)",
        "",
        "```",
        "tools tuning --history <dir>            # the action ledger",
        "tools tuning --history <dir> --json     # machine-readable",
        "tools tuning --history <dir> --pin 7    # exempt from revert",
        "tools tuning --history <dir> --unpin 7",
        "tools tuning --history <dir> --revert 7 # request rollback",
        "```",
        "",
        "Pin/revert write control flags into the STATE FILE, not the",
        "live server: the controller merges them at its next tick (a",
        "revert request on a stopped server simply skips the action at",
        "the next start), so the CLI never races the controller's own",
        "knob writes.",
        "",
        "## Configuration",
        "",
        "| Key | Default | Description |",
        "|---|---|---|",
    ]
    for e in sorted(C.registered_entries(), key=lambda e: e.key):
        if e.key.startswith("spark.rapids.sql.serve.tuning."):
            lines.append(f"| {e.key} | {e.default} | {e.doc} |")
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    import sys
    raise SystemExit(_main(sys.argv[1:]))
