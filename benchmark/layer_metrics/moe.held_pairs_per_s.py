"""Rate at which the train program multiplies (token, expert) pairs
through the experts it HOLDS, in millions a second a chip: the growth of
``moe_held_pairs_total`` (the pairs routed to held experts, which only
the device knows: the train step returns their count beside the loss
and the engine adds it up) over the blocked seconds of the
``engine:train`` spans, as ``moe.pairs_per_s`` divides the pairs routed
over ALL experts. From the program's capture of the steps in which
every span was synced. Nothing where the program counts no held pairs
(a dense model, a model that holds every expert, a commit before the
counter) or no such steps ran."""

from benchmark import program_capture


def read(record):
    capture = program_capture.last(program_capture.all_synced)
    if capture is None:
        return None
    secs = sum(s["end"] - s["start"] for s in capture.named("engine:train"))
    pairs = sum(v for k, v in capture.counters.items()
                if k.startswith("moe_held_pairs_total"))
    if not secs or not pairs:
        return None
    return pairs / secs / record["chips"] / 1e6
