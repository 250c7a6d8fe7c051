"""The pass at which a looped model's exit gate expects an answer token
to leave, in passes: the mean over a train step's answer tokens of
``sum_t t p_t`` (t from 1), p the exit distribution the gate's logits
give (``realhf_tpu/ops/functional.py:exit_log_distribution``), as the
device computed it and the train step returned it beside the loss
(attribute ``expected_exit_pass`` of the ``engine:train`` spans; the
program also keeps it as gauge ``loop_expected_exit_pass``). Median
over the profiled steps.

A WITNESS, not a score: it says which passes carry weight in the
objective that was timed, as ``flash.visited_share`` says which blocks
the kernels visited. It has NO better direction (the manifest has to
name one): 1 says every token leaves after the first pass and the
other passes' losses weigh nothing, T that only the last pass's does.
At the harness's seeded weights with T = 4 every lambda is near 0.5
and the FIRST step reads near 1.875 (p near 1/2, 1/4, 1/8, 1/8); the
traced steps come after the window, some thirty optimizer steps on
thirty-two random documents into the run, by when the first pass has
begun to memorise them faster than the later ones and the gate has
followed the lower loss: 1.0 to 1.3 there (PERF.md, PR 53). Nothing
where the program reports no such statistic (a model without an exit
gate, a commit before it)."""

import statistics

from benchmark import program_capture

NAME = "expected_exit_pass"


def read(record):
    capture = program_capture.last(program_capture.profiled)
    if capture is None:
        return None
    values = [s["attributes"][NAME] for s in capture.named("engine:train")
              if NAME in s["attributes"]]
    return statistics.median(values) if values else None
