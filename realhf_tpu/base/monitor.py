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


def generation_flops(
    n_layers: int,
    hidden_dim: int,
    n_q_heads: int,
    n_kv_heads: int,
    head_dim: int,
    intermediate_dim: int,
    vocab_size: int,
    prompt_lens: List[int],
    gen_len: int,
    gated_mlp: bool = True,
) -> int:
    """Prefill + decode FLOPs for a generation MFC."""
    prefill = transformer_forward_flops(
        n_layers=n_layers, hidden_dim=hidden_dim, n_q_heads=n_q_heads,
        n_kv_heads=n_kv_heads, head_dim=head_dim,
        intermediate_dim=intermediate_dim, vocab_size=vocab_size,
        seqlens=prompt_lens, gated_mlp=gated_mlp)
    decode = 0
    for pl in prompt_lens:
        # Each decoded token attends to the whole prefix.
        dense = transformer_forward_flops(
            n_layers=n_layers, hidden_dim=hidden_dim, n_q_heads=n_q_heads,
            n_kv_heads=n_kv_heads, head_dim=head_dim,
            intermediate_dim=intermediate_dim, vocab_size=vocab_size,
            seqlens=[1] * gen_len, gated_mlp=gated_mlp)
        kv_attn = sum(2 * 2 * (pl + t) * n_q_heads * head_dim
                      for t in range(gen_len))
        decode += dense + kv_attn
    return prefill + decode


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
# Profiling / tracing (reference model_worker.py:664-721 per-MFC
# profiler + REAL_DUMP_TRACE/REAL_DUMP_MEMORY, monitor.py:375-427)
# ----------------------------------------------------------------------
DUMP_TRACE_ENV = "REALHF_TPU_DUMP_TRACE"
DUMP_MEMORY_ENV = "REALHF_TPU_DUMP_MEMORY"


def trace_dir(sub: str = "") -> str:
    from realhf_tpu.base import constants
    d = os.path.join(constants.run_log_path(), "trace", sub)
    os.makedirs(d, exist_ok=True)
    return d


@contextlib.contextmanager
def mfc_profile_region(name: str):
    """Wrap one MFC execution (inside its ``compute:<name>`` span,
    which times and names it):

    - REALHF_TPU_DUMP_TRACE=1: a full ``jax.profiler.trace`` dumped to
      ``{log}/trace/{name}/`` (TensorBoard/perfetto-readable -- the
      reference's per-MFC chrome traces);
    - REALHF_TPU_DUMP_MEMORY=1: a device-memory profile (pprof) saved
      after the MFC completes (the reference's CUDA memory snapshots).
    """
    import jax

    dump_trace = os.environ.get(DUMP_TRACE_ENV, "") == "1"
    dump_memory = os.environ.get(DUMP_MEMORY_ENV, "") == "1"
    safe = name.replace("/", "_")
    with (jax.profiler.trace(trace_dir(safe)) if dump_trace
          else contextlib.nullcontext()):
        yield
    if dump_memory:
        path = os.path.join(trace_dir(safe),
                            f"memory_{int(time.time())}.prof")
        try:
            jax.profiler.save_device_memory_profile(path)
        except Exception:  # noqa: BLE001 - profiling must never kill a run
            pass


# ----------------------------------------------------------------------
# Kernel-time classification from profiler traces (reference
# kernelStatFromTrace + CUDAKernelTimeStat, base/monitor.py:517-699)
# ----------------------------------------------------------------------
#: substring -> category, first match wins (XLA kernel naming)
KERNEL_CATEGORIES = (
    ("all-reduce", "comm"), ("all-gather", "comm"),
    ("reduce-scatter", "comm"), ("all-to-all", "comm"),
    ("collective", "comm"), ("permute", "comm"), ("send", "comm"),
    ("recv", "comm"),
    ("copy", "mem"), ("transpose", "mem"), ("bitcast", "mem"),
    ("reshape", "mem"), ("broadcast", "mem"), ("slice", "mem"),
    ("concatenate", "mem"), ("pad", "mem"),
    ("fusion", "compute"), ("dot", "compute"), ("conv", "compute"),
    ("matmul", "compute"), ("custom-call", "compute"),
    ("scatter", "compute"), ("gather", "compute"),
    ("reduce", "compute"), ("rng", "compute"), ("cholesky", "compute"),
    ("sort", "compute"), ("iota", "compute"),
)


def classify_kernel(name: str) -> str:
    n = name.lower()
    for sub, cat in KERNEL_CATEGORIES:
        if sub in n:
            return cat
    return "misc"


def kernel_stats_from_trace(trace_path: str) -> Dict[str, float]:
    """Aggregate device-kernel time by category from a profiler dump.

    ``trace_path`` is a chrome-trace ``*.trace.json(.gz)`` file or a
    directory (the newest trace under it is used -- e.g. the dir that
    ``mfc_profile_region`` wrote with REALHF_TPU_DUMP_TRACE=1).
    Returns seconds per category (compute/comm/mem/misc) plus
    ``total_busy`` and ``span`` (first-event to last-event extent of
    the device tracks), the inputs of the reference's
    compute/comm/idle breakdown.
    """
    import glob
    import gzip
    import json

    if os.path.isdir(trace_path):
        cands = sorted(glob.glob(
            os.path.join(trace_path, "**", "*.trace.json.gz"),
            recursive=True))
        if not cands:
            raise FileNotFoundError(
                f"No *.trace.json.gz under {trace_path}")
        trace_path = cands[-1]
    opener = gzip.open if trace_path.endswith(".gz") else open
    with opener(trace_path, "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])

    # pid -> process name from metadata events; device tracks only
    proc_names = {e.get("pid"): str(e.get("args", {}).get("name", ""))
                  for e in events
                  if e.get("ph") == "M" and e.get("name") == "process_name"}

    def is_device(pid) -> bool:
        n = proc_names.get(pid, "").lower()
        return any(s in n for s in ("tpu", "gpu", "/device", "xla"))

    out = {"compute": 0.0, "comm": 0.0, "mem": 0.0, "misc": 0.0}
    t_lo, t_hi = None, None
    for e in events:
        if e.get("ph") != "X" or not is_device(e.get("pid")):
            continue
        dur = float(e.get("dur", 0.0)) * 1e-6  # us -> s
        ts = float(e.get("ts", 0.0)) * 1e-6
        out[classify_kernel(str(e.get("name", "")))] += dur
        t_lo = ts if t_lo is None else min(t_lo, ts)
        t_hi = ts + dur if t_hi is None else max(t_hi, ts + dur)
    out["total_busy"] = sum(
        out[k] for k in ("compute", "comm", "mem", "misc"))
    out["span"] = (t_hi - t_lo) if t_lo is not None else 0.0
    return out
