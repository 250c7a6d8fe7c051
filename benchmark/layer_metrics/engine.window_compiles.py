"""Programs lowered after the warm-up step. Should be 0: a compile
inside the window shows in ``step_max_s``."""


def read(record):
    return record["window_compiles"]
