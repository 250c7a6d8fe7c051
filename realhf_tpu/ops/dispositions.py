"""Per-kernel engaged/fallback disposition (ROADMAP weak #2).

Every handwritten Pallas kernel has an XLA fallback, which makes
"kernel silently not engaged" a first-contact risk on new hardware:
a wrong gate and the bench measures the fallback while the record
claims the kernel. ``kernel_dispositions()`` evaluates the SAME gates
the dispatch sites use and reports, per kernel, whether it would
engage and in which mode -- the table lands in every BENCH payload
(``kernel_disposition``) and drives the skip reasons of the
compiled-mode CI tier (tests/ops/test_compiled_kernels.py).

Modes:
  - "compiled":  real Mosaic lowering on a TPU backend
  - "interpret": Pallas interpret emulation (CPU CI wiring coverage)
  - "xla":       the kernel does not engage; the XLA path runs
"""

import os
from typing import Any, Dict

KERNELS = (
    "flash_attention",               # ops/flash_attention.py (packed fwd/bwd)
    "flash_decode_attention_stacked",  # ops/decode_attention.py
    "grouped_matmul",                # ops/grouped_matmul.py (the experts')
    "delta_rule_scan",               # ops/delta_rule.py (chunked scan fwd/bwd)
)


def _base_mode() -> Dict[str, Any]:
    """Gate shared by all kernels: base/backend.pallas_enabled()."""
    import jax

    if os.environ.get("REALHF_TPU_DISABLE_PALLAS") == "1":
        return dict(mode="xla", engaged=False,
                    reason="REALHF_TPU_DISABLE_PALLAS=1 forces the "
                           "GSPMD/XLA paths (A-B rig)")
    backend = jax.default_backend()
    if backend == "tpu":
        return dict(mode="compiled", engaged=True,
                    reason="TPU backend: Mosaic-compiled kernels")
    if os.environ.get("REALHF_TPU_FORCE_PALLAS") == "1":
        return dict(mode="interpret", engaged=True,
                    reason=f"backend '{backend}' with "
                           "REALHF_TPU_FORCE_PALLAS=1: interpret-mode "
                           "emulation (wiring coverage, not perf)")
    return dict(mode="xla", engaged=False,
                reason=f"backend '{backend}' cannot lower Mosaic "
                       "kernels and REALHF_TPU_FORCE_PALLAS is unset")


def kernel_dispositions() -> Dict[str, Dict[str, Any]]:
    """Evaluate each kernel's engagement gate on the CURRENT backend;
    returns {kernel: {mode, engaged, reason}} (keys sorted for a
    stable payload diff)."""
    base = _base_mode()
    return {k: dict(base) for k in sorted(KERNELS)}
