"""What the program recorded of its own set-up: the capture that
``quickstart.main`` starts as its first statement and the runner stops
when its first step has ended (``realhf_tpu.obs.tracing.start_setup``),
found among the program's captures as the one that holds ``setup:*``
spans. The ``setup.*`` readers of ``layer_metrics/`` take one number
each of the program's own arithmetic over it
(``realhf_tpu.obs.setup.split``), which leaves out what this harness
does inside the warm-up ``step`` span before the program's first MFC
(the reference comparison)."""

from benchmark import program_capture


def read(key):
    """``split``'s ``key`` of the set-up capture; None where the
    program recorded none (a commit before it did)."""
    capture = program_capture.last(lambda c: bool(c.named("setup:")))
    if capture is None:
        return None
    from realhf_tpu.obs import setup
    return setup.split(capture)[key]
