"""Sweep-file profiling driver (reference ``examples/profiling/``
parity: ``profile.sh`` + allocations/datasets/interfaces/models jsonl
sweeps, ``realhf/experiments/benchmark/profile_exp.py``).

Each line of the sweep jsonl is a dict of dotted config overrides
merged onto the base ``profile`` experiment (ProfileConfig -- the full
6-MFC PPO graph on synthetic data). One override-sweep format covers
everything the reference splits into four files: allocations
(``actor_gen_alloc=d8t1``), microbatching (``actor_train_n_mbs=2``),
interface knobs (``ppo.max_new_tokens=512``), model sizes
(``model_size=7b``), dataset shapes (``prompt_len_max=1024``).

Instead of relaunching per setup (the reference pauses and
reconfigures its workers), each setup builds a fresh in-process
InlineRunner; compiled-program caches persist across setups that
share shapes.

Usage::

    python scripts/profile_sweep.py \
        --sweep examples/profiling/allocations.jsonl \
        --out profile_results.jsonl \
        model_size=tiny benchmark_steps=2 n_prompts=32

Output: one JSON line per setup -- the overrides, end-to-end step
seconds, and per-MFC wall-clock totals from the runtime's
mfc:* spans -- plus a ranked table on stdout.
"""

import argparse
import json
import sys
import time


def run_setup(base_overrides, line_overrides, index):
    from realhf_tpu.base import name_resolve
    from realhf_tpu.experiments.common import apply_overrides
    from realhf_tpu.experiments.profile_exp import (
        ProfileConfig,
        mfc_timing_summary,
    )
    from realhf_tpu.system.inline import InlineRunner

    name_resolve.reconfigure("memory")
    cfg = ProfileConfig(experiment_name="profsweep",
                        trial_name=f"s{index}")
    merged = dict(base_overrides)
    merged.update({k: str(v) for k, v in line_overrides.items()})
    apply_overrides(cfg, merged)
    spec = cfg.build()

    runner = InlineRunner(spec)
    t0 = time.monotonic()
    _, mfc_secs = mfc_timing_summary(runner.run)
    wall = time.monotonic() - t0
    steps = max(spec.ctl.benchmark_steps or 1, 1)
    mfc = {k: round(v / steps, 4) for k, v in mfc_secs.items()}
    return dict(setup=line_overrides, step_secs=round(wall / steps, 4),
                mfc_secs=mfc, benchmark_steps=steps)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Run the profile experiment over a jsonl sweep of "
                    "config overrides.")
    ap.add_argument("--sweep", required=True,
                    help="jsonl file: one dict of dotted overrides per "
                         "line")
    ap.add_argument("--out", default="profile_results.jsonl")
    ap.add_argument("base", nargs="*",
                    help="base overrides applied to every setup, "
                         "key=value")
    args = ap.parse_args(argv)

    base = {}
    for kv in args.base:
        k, _, v = kv.partition("=")
        base[k] = v

    with open(args.sweep) as f:
        setups = [json.loads(line) for line in f if line.strip()]
    if not setups:
        raise SystemExit(f"empty sweep file {args.sweep}")

    results = []
    with open(args.out, "w") as out:
        for i, line_overrides in enumerate(setups):
            print(f"[{i + 1}/{len(setups)}] {line_overrides}",
                  file=sys.stderr, flush=True)
            res = run_setup(base, line_overrides, i)
            results.append(res)
            out.write(json.dumps(res) + "\n")
            out.flush()

    results.sort(key=lambda r: r["step_secs"])
    mfc_names = sorted({m for r in results for m in r["mfc_secs"]})
    hdr = f"{'step_s':>8} " + " ".join(f"{m:>14}" for m in mfc_names) \
        + "  setup"
    print(hdr)
    for r in results:
        row = f"{r['step_secs']:>8.3f} " + " ".join(
            f"{r['mfc_secs'].get(m, float('nan')):>14.4f}"
            for m in mfc_names)
        print(row + "  " + json.dumps(r["setup"]))
    print(f"\nBest: {json.dumps(results[0]['setup'])} "
          f"at {results[0]['step_secs']:.3f}s/step "
          f"-> {args.out}")
    return results


if __name__ == "__main__":
    main()
