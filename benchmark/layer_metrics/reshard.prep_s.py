"""Seconds a step of a reshard that are not the transfer: the
vocabulary un/re-padding (``realloc:repad``), the EMA merge
(``realloc:ema``) and the ``realloc`` span's own time, which is its
duration less what ``realloc:put`` covers. From the program's own
capture of the profiled steps (those ``reshard.s`` is of); median over
them; nothing where the cell reshards nothing."""

from benchmark import program_capture


def read(record):
    capture = program_capture.last(program_capture.profiled)
    if capture is None:
        return None

    def prep_seconds(spans):
        reallocs = [s for s in spans if s["name"] == "realloc"]
        if not reallocs:
            return None
        return sum(capture.self_seconds(
            r, cover=lambda s: s["name"] == "realloc:put")
            for r in reallocs)

    return program_capture.median_over_steps(capture, prep_seconds)
