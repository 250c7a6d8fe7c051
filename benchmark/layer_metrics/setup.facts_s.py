"""Seconds of set-up under the ``engine:facts`` spans: a train or
generate program's text read whole after its first call (a second
trace and lowering, a cache load, the parse). Moves ``setup_s``."""

from benchmark import setup_capture


def read(record):
    return setup_capture.read("facts_s")
