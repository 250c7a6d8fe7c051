"""Smoke-run every shipped example script (each has a self-demo
``main`` designed for the virtual CPU mesh), so the documented user
surface cannot silently rot when APIs move."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(script, *args, timeout=540):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=REPO)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    return r.stdout


def test_visualize_dfg(tmp_path):
    out = _run("visualize_dfg.py", str(tmp_path / "dfg.dot"))
    assert "actor_train" in out and "(sink)" in out
    dot = (tmp_path / "dfg.dot").read_text()
    assert '"actor_gen" -> "actor_train"' in dot


@pytest.mark.slow  # full trial / multi-process, ~8-20 s (CHANGES.md, PR 22)
def test_load_and_eval_rw_demo():
    out = _run("load_and_eval_rw.py")
    assert "OK (random-init demo)" in out


@pytest.mark.slow  # multi-process trial, >15 s alone (CHANGES.md, PR 22)
def test_ppo_ref_ema():
    out = _run("ppo_ref_ema.py")
    assert "EMA (eta=0.5) actor-replica reference" in out
