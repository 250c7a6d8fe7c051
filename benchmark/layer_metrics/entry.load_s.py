"""Seconds from the call of ``quickstart.main`` to the runner's first
``run_step``: building the experiment, loading and sharding every role,
the dataset. Moves ``setup_s``."""


def read(record):
    return record["entry_load_s"]
