"""Seconds of set-up under the ``setup:model`` spans (their union), one a
role and one a replica: the checkpoint's load or the init, the
sharding, the optimizer's state. The spans' attributes say which role
took what. Moves ``setup_s``."""

from benchmark import setup_capture


def read(record):
    return setup_capture.read("weights_s")
