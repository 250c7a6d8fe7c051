"""Seconds of set-up under the spans ``setup:imports`` (the experiments,
user code, the datasets and interfaces) and ``setup:spec`` (the
experiment's configuration, its spec, the allocation). Moves
``setup_s``."""

from benchmark import setup_capture


def read(record):
    return setup_capture.read("import_s")
