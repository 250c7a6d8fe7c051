"""Profiling subsystem: per-MFC spans, trace dumps, memory stats
(reference model_worker.py:664-721 + base/monitor.py:375-427)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from realhf_tpu.base import constants, monitor


def test_mfc_is_timed_by_its_span_and_the_region_adds_none():
    """One clock an MFC: the ``compute:<name>`` span around
    ``mfc_profile_region`` times it; the region records nothing."""
    from realhf_tpu.obs import tracing
    tracing.reset_default()
    try:
        tracing.start()
        with tracing.span("compute:actor_gen", mfc="actor_gen"):
            with monitor.mfc_profile_region("actor_gen"):
                jnp.sum(jnp.ones((64, 64))).block_until_ready()
        capture = tracing.stop()
    finally:
        tracing.reset_default()
    [span] = capture.spans
    assert span["name"] == "compute:actor_gen"
    assert span["end"] > span["start"]
    # the module keeps no marks of its own beside the span
    assert not [n for n in dir(monitor) if "mark" in n.lower()]


def test_trace_dump(monkeypatch, tmp_path):
    monkeypatch.setattr(constants, "ROOT_DIR", str(tmp_path))
    constants.set_experiment_trial_names("montest", "t0")
    monkeypatch.setenv(monitor.DUMP_TRACE_ENV, "1")
    with monitor.mfc_profile_region("ref_inf"):
        jnp.dot(jnp.ones((128, 128)), jnp.ones((128, 128))) \
            .block_until_ready()
    d = monitor.trace_dir("ref_inf")
    # jax.profiler.trace wrote a tensorboard/perfetto event tree
    files = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
    assert files, d


def test_device_memory_stats():
    st = monitor.device_memory_stats()
    assert set(st) == {"bytes_in_use", "peak_bytes_in_use",
                       "bytes_limit"}


def test_flop_formulas_positive():
    f = monitor.transformer_train_flops(
        n_layers=2, hidden_dim=64, n_q_heads=4, n_kv_heads=2,
        head_dim=16, intermediate_dim=128, vocab_size=256,
        seqlens=[32, 16])
    assert f > 0


def test_kernel_classification(tmp_path):
    """Chrome-trace kernel classification (reference
    kernelStatFromTrace, monitor.py:517-699) against a synthetic
    TPU-shaped trace: device tracks aggregated by category, host
    tracks ignored."""
    import gzip
    import json

    trace = {"traceEvents": [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "python host"}},
        {"ph": "X", "pid": 1, "tid": 0, "name": "fusion.12",
         "ts": 1000, "dur": 500},
        {"ph": "X", "pid": 1, "tid": 0, "name": "dot_general.3",
         "ts": 1500, "dur": 300},
        {"ph": "X", "pid": 1, "tid": 1, "name": "all-reduce.1",
         "ts": 1600, "dur": 200},
        {"ph": "X", "pid": 1, "tid": 0, "name": "copy.7",
         "ts": 1900, "dur": 100},
        {"ph": "X", "pid": 1, "tid": 0, "name": "weird-op",
         "ts": 2000, "dur": 50},
        # host event must be ignored
        {"ph": "X", "pid": 9, "tid": 0, "name": "fusion.fake",
         "ts": 0, "dur": 99999},
    ]}
    p = tmp_path / "host.trace.json.gz"
    with gzip.open(p, "wt") as f:
        json.dump(trace, f)

    stats = monitor.kernel_stats_from_trace(str(tmp_path))
    assert stats["compute"] == pytest.approx(800e-6)
    assert stats["comm"] == pytest.approx(200e-6)
    assert stats["mem"] == pytest.approx(100e-6)
    assert stats["misc"] == pytest.approx(50e-6)
    assert stats["total_busy"] == pytest.approx(1150e-6)
    assert stats["span"] == pytest.approx((2050 - 1000) * 1e-6)
