"""Seconds of the warm-up ``step`` span from its first ``mfc:*`` child's
start to its end: tracing, lowering, load or compile, the facts, and
the device's first run. Moves ``setup_s``."""

from benchmark import setup_capture


def read(record):
    return setup_capture.read("first_step_s")
