"""Seconds of set-up under span ``setup:data``: tokenizer (its share is
the span's ``tokenizer_s``), dataset, loader. Moves ``setup_s``."""

from benchmark import setup_capture


def read(record):
    return setup_capture.read("data_s")
