"""Programs of the program's set-up that the persistent compile cache
did not hold (compiled, then written): ``cache_misses`` summed over the
set-up capture's spans. 0 says every program of this run's set-up was
loaded, so its ``setup_s`` is a warm one. Moves ``setup_s``."""

from benchmark import setup_capture


def read(record):
    return setup_capture.read("cache_misses")
