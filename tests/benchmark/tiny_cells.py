"""The tests' own cells (``manifest.json`` beside this file): two
configurations, two traffic mixes, a kind and a reader that live wholly
in this directory, found by name as a later PR's would be.

``run_cell`` is called as a function; ``run.py`` the command has no
CPU path, and what these runs time is never reported as a metric."""

import os

import jax

from benchmark import run

MANIFEST = os.path.join(run.ROOT, "tests", "benchmark", "manifest.json")
#: stand-ins: a CPU has no row in arith.PEAKS, and must not get one
PEAKS = dict(flops=1e12, hbm_bw=1e11)


def go(name, trace, tmp_path, seconds=0.3):
    """One run of a tiny cell through the wrappers a chip run uses,
    with a seed as large as the driver's."""
    cell = run.load_cell(MANIFEST, name)
    return run.run_cell(cell, seed=2 ** 31 + 77, seconds=seconds,
                        trace=trace, work=str(tmp_path), peaks=PEAKS,
                        expect_kernels=False)


def check_line(out, trace):
    assert set(out) - {"breakdown"} == {"correct", "attempted", "failed",
                                        "metrics", "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert out["device"]["platform"] == jax.devices()[0].platform
    for metric in out["metrics"].values():
        assert set(metric) == {"value", "unit"}
    if not trace:
        assert set(out["metrics"]) == {"tokens_per_s", "step_max_s",
                                       "setup_s"}
        assert all(m["value"] > 0 for m in out["metrics"].values())
