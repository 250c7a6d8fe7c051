"""Own device seconds a step and chip, in the train program
(``jit_train_step`` / ``jit_train_seq``) of the profiled steps, of the
ssm layers' chunked scan alone, sub-part ``ssm/scan``
(``realhf_tpu/obs/parts.py:SCAN``; ``realhf_tpu/ops/ssm_scan.py``): the
step's softplus, the running decays, ``C B^T`` and the decays' mask
inside a chunk, the chunks' end states, the scan that carries the state
and the outputs with ``D x``. Forward, rematerialised forward and
backward together; a part of ``train.ssm_s``, which holds the whole of
``ssm``. Which operation belongs to the sub-part the PROGRAM says
(``benchmark/program_parts.py`` joins the engine's table to the trace
file). Nothing where the capture has no ``programs``, nothing was
profiled, or the cell trains nothing; 0 where the program has no such
sub-part (a commit before it, a model without ssm layers)."""

from benchmark import program_parts


def read(record):
    return program_parts.seconds_a_step(
        record, "jit_train_",
        lambda part, pass_, opcode, phase: part == "ssm/scan")
