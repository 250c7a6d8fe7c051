"""How uneven the HELD experts' loads were: the (token, k) pairs the
busiest held expert received over the mean of ALL experts (tokens x k /
experts), worst layer and worst microbatch of a train step, as the
device counted it and the train step returned it beside the loss
(attribute ``moe_held_load_max_over_mean`` of the ``engine:train``
spans; the program also keeps it as a gauge of the same name). Median
over the profiled steps. 1 is perfectly even. Nothing where the program
reports no such statistic (a model that holds every expert, a commit
before it)."""

import statistics

from benchmark import program_capture

NAME = "moe_held_load_max_over_mean"


def read(record):
    capture = program_capture.last(program_capture.profiled)
    if capture is None:
        return None
    values = [s["attributes"][NAME] for s in capture.named("engine:train")
              if NAME in s["attributes"]]
    return statistics.median(values) if values else None
