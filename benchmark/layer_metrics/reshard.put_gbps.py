"""Rate of the one ``jax.device_put`` that moves the weights between
layouts: the logical bytes of the tree (``realloc_bytes_total``, every
leaf's global shape times its item size) over the blocked seconds of
the ``realloc:put`` spans, in GB/s, over the profiled steps (those
``reshard.s`` is of). Nothing where the cell reshards nothing."""

from benchmark import program_capture


def read(record):
    capture = program_capture.last(program_capture.profiled)
    if capture is None:
        return None
    secs = sum(s["end"] - s["start"] for s in capture.named("realloc:put"))
    moved = sum(v for k, v in capture.counters.items()
                if k.startswith("realloc_bytes_total"))
    if not secs or not moved:
        return None
    return moved / secs / 1e9
