"""Rate at which the train program routes (token, expert) pairs
through the experts, in millions a second a chip: the growth of
``moe_routed_pairs_total`` (valid tokens x experts a token x layers of
every program call, counted by the program on the host) over the
blocked seconds of the ``engine:train`` spans, as ``reshard.put_gbps``
divides bytes by seconds. From the program's capture of the steps in
which every span was synced: in the profiled steps only ``compute:*``
and ``realloc*`` end blocked, and ``engine:train`` ends when the
program is enqueued. Nothing where the program counts no pairs (a
dense model, a commit before the counter) or no such steps ran."""

from benchmark import program_capture


def read(record):
    capture = program_capture.last(program_capture.all_synced)
    if capture is None:
        return None
    secs = sum(s["end"] - s["start"] for s in capture.named("engine:train"))
    pairs = sum(v for k, v in capture.counters.items()
                if k.startswith("moe_routed_pairs_total"))
    if not secs or not pairs:
        return None
    return pairs / secs / record["chips"] / 1e6
