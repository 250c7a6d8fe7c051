"""On-chip cost-model microbenchmark -> persisted calibration.

The minimal chip-window entry for ROADMAP weak #5: run
``search.engine.calibrate_cost_model`` on the current backend (one
timed train step + a two-point decode fit per distinct role
architecture) and persist the calibrated ``TPUCostModel`` as JSON at
the location ``search.engine.default_cost_model`` auto-loads from --
after one run, every allocation search (``allocation_mode=search``,
``apply_searched_allocations``, ElasticPlanner re-planning) prices
candidates with MEASURED MXU efficiency and HBM bandwidth instead of
the analytic v5e defaults.

``scripts/calibrate_tpu.py`` remains the fuller driver (same artifact
plus a searched-vs-heuristic allocation comparison); this entry is the
one a short window should run first because it exits as soon as the
artifact is on disk.

Usage::

    python scripts/calibrate.py [--out calibration_tpu.json]
    # then: searches pick it up from $REALHF_TPU_CALIBRATION or
    # ./calibration_tpu.json automatically
"""
import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from realhf_tpu.base.backend import enable_compile_cache  # noqa: E402
enable_compile_cache()


def main(argv=None):
    from realhf_tpu.search.engine import (CALIBRATION_FILE, TPUCostModel,
                                          calibrate_cost_model)

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=CALIBRATION_FILE,
                    help="artifact path (default: the location "
                         "default_cost_model() auto-loads)")
    args = ap.parse_args(argv)

    import jax

    # the bench-shaped PPO spec: same probe architectures the real
    # experiments allocate
    from calibrate_tpu import build_spec

    spec = build_spec()
    backend = jax.default_backend()
    base = TPUCostModel()
    cal = calibrate_cost_model(spec, base=base)
    artifact = dict(backend=backend,
                    base=dataclasses.asdict(base),
                    calibrated=dataclasses.asdict(cal))
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=2)
    os.replace(tmp, args.out)
    print(f"calibration ({backend}) -> {args.out}")
    print(json.dumps(artifact["calibrated"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
