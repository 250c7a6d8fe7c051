"""Share of the (query block, key block) pairs under the packed rows'
causal diagonals that the flash kernels visit, in percent: the growth
of ``flash_kv_blocks_total{kind=visited}`` over that of
``{kind=causal}`` in the profiled steps. The engine counts both on the
host from each batch's segment ids by the kernels' own rule, an
attention layer at a time (``visited`` under the layer's sliding
window where it has one, ``causal`` without), so a stack of window and
full layers adds up right. 100 says that neither a window nor a
document boundary took a block off the loops. Nothing where the program
has no such counter (a commit before it) or no packed row went to the
kernels."""

from benchmark import program_capture


def _growth(capture, kind):
    return sum(v for k, v in capture.counters.items()
               if k.startswith("flash_kv_blocks_total")
               and f"kind={kind}" in k)


def read(record):
    capture = program_capture.last(program_capture.profiled)
    if capture is None:
        return None
    visited, causal = _growth(capture, "visited"), _growth(capture, "causal")
    if not causal:
        return None
    return 100.0 * visited / causal
