"""What the program itself recorded of the traced stretch: the capture
of ``realhf_tpu.obs.tracing`` (spans with parent ids on the host clock,
counter deltas), which the harness started and stopped through the
program's control. The readers of ``layer_metrics/`` that time the
inside of an MFC or of a reshard read it here, not from ``record``.
"""

import statistics


def last(where=lambda capture: True):
    """The newest capture that holds spans and for which ``where``
    holds: ``--trace 2`` makes two, the profiled steps (MFCs and
    reshards end blocked, as the outside clocks do) and then steps with
    every span synced. None where the program has no such control (a
    commit before it) or nothing of the kind was traced."""
    try:
        from realhf_tpu.obs import tracing
    except ImportError:
        return None
    get = getattr(tracing, "captures", None)
    for capture in reversed(get() if get is not None else []):
        if capture.spans and where(capture):
            return capture
    return None


def profiled(capture):
    """The steps the outside clocks and the device trace are of."""
    return capture.profile_dir is not None


def all_synced(capture):
    """Every engine program ended blocked: host time is host time."""
    return capture.sync is True


def median_over_steps(capture, seconds_of):
    """Median over the capture's whole ``step`` spans of
    ``seconds_of(spans beneath the step)``; None where that gives None
    for every step, or there is no step."""
    values = [seconds_of(capture.descendants(step))
              for step in capture.named("step")]
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None
