"""Seconds of the program's own set-up capture, from the call of
``quickstart.main`` to the end of its first step, less the head of that
``step`` span before its first ``mfc:*`` child (this harness's reference
comparison runs there, inside the patched ``run_step``; nothing in a
user's run): what a user of ``quickstart`` waits for. ``setup_s`` less
this is the harness's own (its imports and runtime start, its
checkpoint and documents, its reference). Moves ``setup_s``."""

from benchmark import setup_capture


def read(record):
    return setup_capture.read("program_s")
