"""Trace-driven step-time attribution, goodput, and stragglers.

``merged_trace.json`` (and the per-process ``*.trace.jsonl`` shards it
is folded from) were write-only artifacts: a human could stare at the
Perfetto timeline, but nothing computed where a PPO step's wall-clock
actually went. This module reconstructs training steps from the span
tree the runtime already emits -- ``step`` roots, ``dispatch:<mfc>``
children in the master, ``mfc:<name>`` / ``data_fetch`` / ``realloc``
/ ``compute:<mfc>`` spans in the workers (cross-process parentage
rides in the span args) -- and answers the questions MegaScale-class
systems treat as table stakes (arXiv:2402.15627):

- **Per-step attribution**: each instant of the step window is
  assigned to exactly one of ``compute`` > ``data_fetch`` >
  ``realloc`` > ``dispatch`` (RPC/queueing overhead inside
  ``dispatch:*``/``mfc:*`` not covered by the finer categories) >
  ``idle``, by that priority, so the components SUM to the step wall.
- **Critical path**: the latest-finisher chain from the step root
  through ``dispatch:* -> mfc:* -> compute:*``, naming the bottleneck
  MFC of each step (and the modal bottleneck across steps).
- **Straggler skew**: per-worker busy seconds (union of that worker's
  compute/data_fetch/realloc spans) vs the median worker.
- **Goodput**: busy-compute seconds / step wall (union across
  workers), plus the per-worker normalized variant.

Entry points: :func:`analyze_path` (merged JSON, a ``.jsonl`` shard,
or a trace directory), :func:`analyze_events`,
:func:`format_report` (human table) and :func:`one_line_summary`
(the teardown log line next to the Perfetto pointer).
``scripts/analyze_trace.py`` is the CLI.
"""

import json
import os
from typing import Dict, List, Optional, Tuple

from realhf_tpu.base import logging

logger = logging.getLogger("obs.analyze")

#: attribution categories in claim-priority order (first match wins)
CATEGORIES = ("compute", "data_fetch", "realloc", "dispatch")

Interval = Tuple[float, float]


# ----------------------------------------------------------------------
# Interval algebra (all half-open [start, end) wall-clock seconds).
# ----------------------------------------------------------------------
def _merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _measure(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def _subtract(intervals: List[Interval],
              cover: List[Interval]) -> List[Interval]:
    """``intervals`` minus ``cover`` (both already merged/sorted)."""
    out: List[Interval] = []
    for s, e in intervals:
        cur = s
        for cs, ce in cover:
            if ce <= cur:
                continue
            if cs >= e:
                break
            if cs > cur:
                out.append((cur, cs))
            cur = max(cur, ce)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def _clip(intervals: List[Interval], lo: float, hi: float
          ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered_seconds(window: Interval,
                    intervals: List[Interval]) -> float:
    """Seconds of ``window`` that the union of ``intervals`` covers."""
    return _measure(_clip(_merge(list(intervals)), *window))


# ----------------------------------------------------------------------
# Loading.
# ----------------------------------------------------------------------
def load_events(path: str) -> List[Dict]:
    """Chrome trace events from a merged ``traceEvents`` JSON, a
    per-process ``.trace.jsonl`` shard (one event per line), or a
    directory of shards. Unparseable lines are skipped -- a worker
    killed mid-write must not void the analysis."""
    if os.path.isdir(path):
        events: List[Dict] = []
        for fn in sorted(os.listdir(path)):
            if fn.endswith(".trace.jsonl"):
                events.extend(load_events(os.path.join(path, fn)))
            elif fn == "merged_trace.json":
                events.extend(load_events(os.path.join(path, fn)))
        return events
    events = []
    with open(path) as f:
        head = f.read(1)
        f.seek(0)
        if head == "{":
            try:
                doc = json.load(f)
                return list(doc.get("traceEvents", []))
            except ValueError:
                f.seek(0)  # fall through: maybe JSONL starting with {
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except ValueError:
                continue
    return events


def _category(name: str) -> Optional[str]:
    if name.startswith("compute:"):
        return "compute"
    if name == "data_fetch" or name.startswith("data_fetch:"):
        return "data_fetch"
    if name == "realloc" or name.startswith("realloc:"):
        return "realloc"
    if name.startswith(("dispatch:", "mfc:", "rpc:")):
        return "dispatch"
    return None


def _mfc_of(event: Dict) -> Optional[str]:
    name = event.get("name", "")
    for prefix in ("dispatch:", "mfc:", "compute:"):
        if name.startswith(prefix):
            return name[len(prefix):]
    return event.get("args", {}).get("mfc")


# ----------------------------------------------------------------------
# Analysis.
# ----------------------------------------------------------------------
def analyze_events(events: List[Dict]) -> Dict:
    """The full report (module doc) from raw Chrome trace events."""
    pid_names = {e.get("pid"): e.get("args", {}).get("name")
                 for e in events
                 if e.get("ph") == "M"
                 and e.get("name") == "process_name"}
    spans = [e for e in events if e.get("ph") == "X"]
    for e in spans:  # seconds once, up front (trace ts/dur are in us)
        e["_start"] = e.get("ts", 0.0) / 1e6
        e["_end"] = e["_start"] + e.get("dur", 0.0) / 1e6
    steps = sorted((e for e in spans if e.get("name") == "step"),
                   key=lambda e: e["_start"])
    if not steps:
        return dict(n_steps=0,
                    error="no `step` spans in trace (was the run "
                          "traced with REALHF_TPU_TRACE=1?)")
    by_trace: Dict[str, List[Dict]] = {}
    children: Dict[str, List[Dict]] = {}
    for e in spans:
        args = e.get("args", {})
        tid = args.get("trace_id")
        if tid is not None:
            by_trace.setdefault(tid, []).append(e)
        pid = args.get("parent_id")
        if pid is not None:
            children.setdefault(pid, []).append(e)

    def worker_of(e: Dict) -> str:
        w = e.get("args", {}).get("worker")
        if w:
            return str(w)
        return str(pid_names.get(e.get("pid"))
                   or f"pid:{e.get('pid')}")

    step_reports: List[Dict] = []
    totals = {c: 0.0 for c in CATEGORIES}
    totals["idle"] = 0.0
    total_wall = 0.0
    total_compute_union = 0.0
    worker_busy: Dict[str, float] = {}
    mfc_secs: Dict[str, float] = {}
    bottleneck_counts: Dict[str, int] = {}
    per_worker_ratio_num = per_worker_ratio_den = 0.0

    for idx, step in enumerate(steps):
        lo, hi = step["_start"], step["_end"]
        wall = hi - lo
        subtree = [e for e in by_trace.get(
            step.get("args", {}).get("trace_id"), [])
            if e is not step and e.get("name") != "step"]
        # intervals per category, claimed by priority so the
        # components sum exactly to the step wall
        attribution: Dict[str, float] = {}
        covered: List[Interval] = []
        for cat in CATEGORIES:
            ivs = _merge(_clip([(e["_start"], e["_end"])
                                for e in subtree
                                if _category(e.get("name", "")) == cat],
                               lo, hi))
            attribution[cat] = round(_measure(_subtract(ivs, covered)),
                                     9)
            covered = _merge(covered + ivs)
        attribution["idle"] = round(max(0.0, wall - _measure(covered)),
                                    9)
        compute_union = _measure(_merge(_clip(
            [(e["_start"], e["_end"]) for e in subtree
             if _category(e.get("name", "")) == "compute"], lo, hi)))

        # critical path: latest-finisher chain from the step root
        path: List[str] = []
        node = step
        seen = set()
        while True:
            sid = node.get("args", {}).get("span_id")
            if sid is None or sid in seen:
                break
            seen.add(sid)
            kids = children.get(sid, [])
            if not kids:
                break
            node = max(kids, key=lambda e: e["_end"])
            path.append(node.get("name", ""))
        bottleneck = next((m for m in (_mfc_of(dict(name=n))
                                       for n in path) if m), None)
        if bottleneck:
            bottleneck_counts[bottleneck] = \
                bottleneck_counts.get(bottleneck, 0) + 1

        # per-worker busy time (compute + data_fetch + realloc)
        busy_by_worker: Dict[str, List[Interval]] = {}
        for e in subtree:
            if _category(e.get("name", "")) in ("compute",
                                                "data_fetch",
                                                "realloc"):
                busy_by_worker.setdefault(worker_of(e), []).append(
                    (e["_start"], e["_end"]))
        step_workers = {w: round(_measure(_merge(_clip(iv, lo, hi))), 9)
                        for w, iv in busy_by_worker.items()}
        for w, b in step_workers.items():
            worker_busy[w] = worker_busy.get(w, 0.0) + b
        if step_workers:
            per_worker_ratio_num += sum(step_workers.values())
            per_worker_ratio_den += wall * len(step_workers)

        for e in subtree:
            if e.get("name", "").startswith("dispatch:"):
                mfc = _mfc_of(e)
                if mfc:
                    mfc_secs[mfc] = mfc_secs.get(mfc, 0.0) \
                        + (e["_end"] - e["_start"])
        if not any(n.startswith("dispatch:")
                   for n in (e.get("name", "") for e in subtree)):
            # inline mode: no master dispatch layer; mfc:* spans carry
            # the per-MFC walls instead
            for e in subtree:
                if e.get("name", "").startswith("mfc:"):
                    mfc = _mfc_of(e)
                    if mfc:
                        mfc_secs[mfc] = mfc_secs.get(mfc, 0.0) \
                            + (e["_end"] - e["_start"])

        args = step.get("args", {})
        step_reports.append(dict(
            step=idx,
            global_step=args.get("global_step"),
            batch_id=args.get("batch_id"),
            start=lo, wall_secs=round(wall, 9),
            attribution=attribution,
            critical_path=path,
            bottleneck_mfc=bottleneck,
            workers=step_workers))
        for c, v in attribution.items():
            totals[c] += v
        total_wall += wall
        total_compute_union += compute_union

    # modal bottleneck; dispatch-seconds break ties deterministically
    bottleneck_mfc = None
    if bottleneck_counts:
        bottleneck_mfc = max(
            bottleneck_counts,
            key=lambda m: (bottleneck_counts[m],
                           mfc_secs.get(m, 0.0), m))
    busy_values = sorted(worker_busy.values())
    median_busy = 0.0
    if busy_values:
        mid = len(busy_values) // 2
        median_busy = busy_values[mid] if len(busy_values) % 2 \
            else (busy_values[mid - 1] + busy_values[mid]) / 2
    stragglers = sorted(
        (dict(worker=w, busy_secs=round(b, 6),
              skew_vs_median_secs=round(b - median_busy, 6))
         for w, b in worker_busy.items()),
        key=lambda d: (-d["skew_vs_median_secs"], d["worker"]))

    return dict(
        n_steps=len(steps),
        wall_secs=round(total_wall, 6),
        attribution={c: round(v, 6) for c, v in totals.items()},
        attribution_frac={
            c: round(v / total_wall, 4) if total_wall else 0.0
            for c, v in totals.items()},
        goodput=round(total_compute_union / total_wall, 4)
        if total_wall else 0.0,
        goodput_per_worker=round(
            per_worker_ratio_num / per_worker_ratio_den, 4)
        if per_worker_ratio_den else None,
        bottleneck_mfc=bottleneck_mfc,
        bottleneck_counts=bottleneck_counts,
        mfc_secs={m: round(v, 6)
                  for m, v in sorted(mfc_secs.items())},
        stragglers=stragglers,
        steps=step_reports)


def analyze_path(path: str) -> Dict:
    return analyze_events(load_events(path))


# ----------------------------------------------------------------------
# Rendering.
# ----------------------------------------------------------------------
def format_report(report: Dict) -> str:
    """The human-readable report (docs/observability.md "Trace
    analytics" shows how to read it)."""
    if report.get("n_steps", 0) == 0:
        return f"trace report: {report.get('error', 'no steps')}"
    lines = [
        f"Trace report: {report['n_steps']} step(s), "
        f"{report['wall_secs']:.2f}s wall, "
        f"goodput {report['goodput']:.1%}"
        + (f" (per-worker {report['goodput_per_worker']:.1%})"
           if report.get("goodput_per_worker") is not None else ""),
        "",
        "  attribution          secs     frac",
    ]
    for cat in (*CATEGORIES, "idle"):
        lines.append(f"  {cat:<16} {report['attribution'][cat]:>9.3f}"
                     f"  {report['attribution_frac'][cat]:>6.1%}")
    if report.get("bottleneck_mfc"):
        counts = report.get("bottleneck_counts", {})
        lines += ["", f"  critical-path MFC: "
                      f"{report['bottleneck_mfc']} "
                      f"(bottleneck in "
                      f"{counts.get(report['bottleneck_mfc'], 0)}"
                      f"/{report['n_steps']} steps)"]
    if report.get("mfc_secs"):
        lines += ["", "  per-MFC wall (dispatch spans):"]
        for mfc, secs in sorted(report["mfc_secs"].items(),
                                key=lambda kv: -kv[1]):
            lines.append(f"    {mfc:<24} {secs:>9.3f}s")
    if report.get("stragglers"):
        lines += ["", "  worker busy-time skew vs median:"]
        for s in report["stragglers"]:
            lines.append(f"    {s['worker']:<24} "
                         f"{s['busy_secs']:>9.3f}s  "
                         f"{s['skew_vs_median_secs']:>+8.3f}s")
    return "\n".join(lines)


def one_line_summary(report: Dict) -> str:
    if report.get("n_steps", 0) == 0:
        return f"trace report: {report.get('error', 'no steps')}"
    parts = [f"{report['n_steps']} steps",
             f"goodput {report['goodput']:.0%}"]
    if report.get("bottleneck_mfc"):
        parts.append(f"bottleneck MFC {report['bottleneck_mfc']}")
    stragglers = report.get("stragglers") or []
    if len(stragglers) > 1 \
            and stragglers[0]["skew_vs_median_secs"] > 0:
        parts.append(f"straggler {stragglers[0]['worker']} "
                     f"(+{stragglers[0]['skew_vs_median_secs']:.2f}s "
                     "vs median)")
    return "trace report: " + ", ".join(parts)


def summarize_path(path: Optional[str]) -> Optional[str]:
    """One-line summary of a trace file for teardown logs; never
    raises (teardown must not mask the trial's outcome)."""
    if not path:
        return None
    try:
        return one_line_summary(analyze_path(path))
    except Exception as e:  # noqa: BLE001
        logger.debug("Trace summary of %s failed: %s", path, e)
        return None
