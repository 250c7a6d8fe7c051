"""FLOPs accounting, per-MFC profiler dumps, and device memory
statistics.

Parity with reference ``realhf/base/monitor.py``: the FLOP formulas
(:277-353) used by the master to log per-step TFLOP/s, and accelerator
memory stats via JAX device APIs. Timing lives in ``obs/tracing.py``
(the reference's CUDA time marks): one span an MFC.
"""

import os
import contextlib
import time
from typing import Dict, List


def attn_flops(q_len: int, kv_len: int, n_q_heads: int, head_dim: int,
               causal: bool = True) -> int:
    """FLOPs of QK^T + PV for one sequence (forward)."""
    full = 4 * q_len * kv_len * n_q_heads * head_dim
    return full // 2 if causal else full


def transformer_forward_flops(
    n_layers: int,
    hidden_dim: int,
    n_q_heads: int,
    n_kv_heads: int,
    head_dim: int,
    intermediate_dim: int,
    vocab_size: int,
    seqlens: List[int],
    gated_mlp: bool = True,
) -> int:
    """Dense-transformer forward FLOPs over packed sequences.

    Mirrors the accounting of reference ``base/monitor.py:277-353``
    (per-projection matmul FLOPs + causal attention + head).
    """
    T = sum(seqlens)
    sum_sq = sum(l * l for l in seqlens)
    qkv = 2 * T * hidden_dim * (n_q_heads + 2 * n_kv_heads) * head_dim
    attn_o = 2 * T * n_q_heads * head_dim * hidden_dim
    attn = 2 * sum_sq * n_q_heads * head_dim  # QK^T + PV with causal 1/2 factor
    n_mlp_mats = 3 if gated_mlp else 2
    mlp = 2 * T * hidden_dim * intermediate_dim * n_mlp_mats
    per_layer = qkv + attn_o + attn + mlp
    head = 2 * T * hidden_dim * vocab_size
    return n_layers * per_layer + head


def transformer_train_flops(**kw) -> int:
    """Backward is ~2x forward; total train step ~3x forward."""
    return 3 * transformer_forward_flops(**kw)


def device_memory_stats(device=None) -> Dict[str, int]:
    """Per-chip HBM stats (replaces nvml polling, reference :255)."""
    import jax
    d = device or jax.local_devices()[0]
    stats = d.memory_stats() or {}
    return {
        "bytes_in_use": stats.get("bytes_in_use", 0),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use", 0),
        "bytes_limit": stats.get("bytes_limit", 0),
    }


# ----------------------------------------------------------------------
# Profiling (reference model_worker.py:664-721 per-MFC profiler +
# REAL_DUMP_MEMORY, monitor.py:375-427). A profile of the device is
# started in ONE way, ``obs.tracing.start(profile_dir)`` (the worker
# command ``profiler`` calls it): it holds the spans and what the
# compiled programs say of themselves; device time by what an
# operation IS comes from ``python -m realhf_tpu.obs.parts``.
# ----------------------------------------------------------------------
DUMP_MEMORY_ENV = "REALHF_TPU_DUMP_MEMORY"
#: read once, at import: the region sits on every MFC of the hot path
_DUMP_MEMORY = os.environ.get(DUMP_MEMORY_ENV, "") == "1"


def trace_dir(sub: str = "") -> str:
    from realhf_tpu.base import constants
    d = os.path.join(constants.run_log_path(), "trace", sub)
    os.makedirs(d, exist_ok=True)
    return d


@contextlib.contextmanager
def mfc_profile_region(name: str):
    """Wrap one MFC execution (inside its ``compute:<name>`` span,
    which times and names it). With REALHF_TPU_DUMP_MEMORY=1 in the
    process's environment at import: a device-memory profile (pprof)
    saved under ``{log}/trace/{name}/`` after the MFC completes (the
    reference's CUDA memory snapshots)."""
    yield
    if _DUMP_MEMORY:
        import jax
        path = os.path.join(trace_dir(name.replace("/", "_")),
                            f"memory_{int(time.time())}.prof")
        try:
            jax.profiler.save_device_memory_profile(path)
        except Exception:  # noqa: BLE001 - profiling must never kill a run
            pass
