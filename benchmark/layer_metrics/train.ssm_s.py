"""Own device seconds a step and chip, in the train program
(``jit_train_step`` / ``jit_train_seq``) of the profiled steps, of the
ssm layers' mixers, part ``ssm``: the norm before them, ``w_in``, the
short convolution with its bias, the chunked scan (sub-part
``ssm/scan``, which ``ssm.scan_s`` reads alone), the gate and the
grouped norm, ``w_out``.
Forward, rematerialised forward and backward together. Which operation
belongs to the part the PROGRAM says: the engine reads each compiled
program's ``op_name``s once (``Engine.program_facts``) and the capture
carries the table (``benchmark/program_parts.py`` joins it to the trace
file). Nothing where the capture has no ``programs`` (a commit before
them), nothing was profiled, or the cell trains nothing; 0 where the
program has no such part (a commit before it, a model without ssm
layers)."""

from benchmark import program_parts


def read(record):
    return program_parts.train(record, *("ssm",))
