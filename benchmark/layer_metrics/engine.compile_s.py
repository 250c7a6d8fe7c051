"""Seconds the backend spent compiling programs, or loading them from
the persistent cache, during set-up (``CompileWatch``). Moves
``setup_s``."""


def read(record):
    return record["setup_compile_s"]
