"""The flash kernels' share of the chip's matrix peak where a learned
selection masks them, in percent: the reading of ``flash.mxu_share.py``
(whose reader this calls: the three kernels' own device seconds and
their calls from the profiled steps' trace; a kernel that takes the
selection is named ``flash_fwd_sel`` / ``flash_bwd_dq_sel`` /
``flash_bwd_dkv_sel`` and matches the same names), over the FLOPs
``family.flash_flops`` counts OF THE MATHEMATICS: the products of the
SELECTED (query, key) pairs alone (75.0% of the causal pairs on a
4096-token document at ``topk`` 2048). The kernels visit every block
pair under the causal diagonal whole, multiply it, and mask what the
selection left out, so the share says what visiting unselected pairs
costs beside the diagonal blocks' masked halves, and cannot pass 100%.
Nothing where the family counts no such FLOPs, nothing was profiled, or
the trace holds no such kernel (a commit before them, the XLA path)."""

import importlib.util
import os


def _flash_mxu_share():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "flash.mxu_share.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_file_flash_mxu_share", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read(record):
    return _flash_mxu_share().read(record)
