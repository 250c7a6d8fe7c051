"""The flash kernels' share of the chip's matrix peak where the key is
wider than the value (latent attention: 192 and 128), in percent: the
reading of ``flash.mxu_share.py`` (whose reader this calls: the
kernels' own device seconds and their calls from the profiled steps'
trace), over the FLOPs ``family.flash_flops`` counts OF THE
MATHEMATICS: a score-shaped product of a visited block pair at the
key's width, a value-shaped one at the value's. The 128-wide MXU
contracts 192 in two passes, half of the second empty, and none of
that is counted: the metric says what the unaligned width costs, it
does not hide it (on the chip the share read OVER the same kernels' at
(128, 128): what a block pair costs beside its products is the same at
every width, and a wider key amortises it; PERF.md, PR 37).
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
