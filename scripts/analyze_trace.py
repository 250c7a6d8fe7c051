#!/usr/bin/env python
"""Trace analytics CLI: step-time attribution, goodput, stragglers.

Turns the write-only trace artifacts (``merged_trace.json`` / the
per-process ``*.trace.jsonl`` shards) into the report
``realhf_tpu.obs.analyze`` computes: per-step attribution
(compute / data_fetch / realloc / dispatch / idle), the critical path
through ``dispatch:* -> mfc:*`` naming the bottleneck MFC, per-worker
straggler skew, and goodput. See docs/observability.md "Trace
analytics" for how to read the tables.

Usage::

    python scripts/analyze_trace.py <merged_trace.json | trace dir | shard.jsonl>
        [--json OUT.json]     # also write the machine-readable report
        [--quiet]             # one-line summary only

    python scripts/analyze_trace.py --demo [--steps N]
        # self-contained proof: run a tiny traced inline PPO trial
        # (CPU, random-init models) and analyze its own merged trace;
        # prints the report JSON as the last stdout line.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_demo(steps: int = 2) -> dict:
    """Tiny traced inline PPO run -> analyze its merged trace. Must
    set the trace env BEFORE realhf_tpu imports configure anything."""
    import tempfile

    os.environ["REALHF_TPU_TRACE"] = "1"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    root = tempfile.mkdtemp(prefix="trace_report_demo_")
    os.environ["REALHF_TPU_ROOT"] = root
    import realhf_tpu.base.constants as constants
    constants.ROOT_DIR = root  # env is read at import time

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from bench_async import build_runner

    from realhf_tpu.obs import analyze, tracing

    runner = build_runner(train_bs=2, gen_bs=2, prompt_len=8,
                          new_tokens=4, steps=steps, max_staleness=4,
                          seed=0, name="tracereport")
    runner.spec.ctl.benchmark_steps = steps
    runner.run()  # merges the trace at teardown (tracing enabled)
    merged = os.path.join(tracing.trace_dir(), tracing.MERGED_TRACE_NAME)
    report = analyze.analyze_path(merged)
    report["merged_trace"] = merged
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        "analyze_trace",
        description="Trace-driven step-time attribution / goodput / "
                    "straggler report.")
    ap.add_argument("trace", nargs="?", default=None,
                    help="merged_trace.json, a .trace.jsonl shard, or "
                         "a trace directory")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write the machine-readable report here")
    ap.add_argument("--quiet", action="store_true",
                    help="print only the one-line summary")
    ap.add_argument("--demo", action="store_true",
                    help="run a tiny traced inline PPO trial and "
                         "analyze it; prints the report JSON")
    ap.add_argument("--steps", type=int, default=2,
                    help="steps for --demo")
    args = ap.parse_args(argv)

    if args.demo:
        report = run_demo(steps=args.steps)
        from realhf_tpu.obs import analyze
        print(analyze.one_line_summary(report), file=sys.stderr)
        print(json.dumps(report))
        return 0 if report.get("n_steps", 0) > 0 else 1

    if not args.trace:
        ap.error("a trace path is required (or --demo)")
    from realhf_tpu.obs import analyze
    report = analyze.analyze_path(args.trace)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(report, f, indent=1)
            f.write("\n")
    if args.quiet:
        print(analyze.one_line_summary(report))
    else:
        print(analyze.format_report(report))
    return 0 if report.get("n_steps", 0) > 0 else 1


if __name__ == "__main__":
    sys.exit(main())
