"""Profiling subsystem: per-MFC spans, memory stats (reference
model_worker.py:664-721 + base/monitor.py:375-427)."""

import jax.numpy as jnp

from realhf_tpu.base import monitor


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
    # the module keeps no marks of its own beside the span, starts no
    # profile (``tracing.start`` is the one way) and reads no device
    # time by category (``python -m realhf_tpu.obs.parts`` does)
    assert not [n for n in dir(monitor) if "mark" in n.lower()
                or "kernel" in n.lower() or n == "DUMP_TRACE_ENV"]
    assert capture.profile_dir is None


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
