"""Seconds of set-up that jax spent tracing programs and lowering them
to MLIR: ``trace_s`` + ``lower_s`` summed over the set-up capture's
spans, on which ``metrics.watch_compiles`` puts every event of the
thread that compiles. Left out: the second lowering under
``engine:facts`` (that is ``setup.facts_s``) and what this harness
lowers before the program's first MFC. Moves ``setup_s``."""

from benchmark import setup_capture


def read(record):
    return setup_capture.read("trace_lower_s")
