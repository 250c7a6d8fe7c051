"""Own device seconds a step and chip, in the train program
(``jit_train_step`` / ``jit_train_seq``) of the profiled steps, of
the dense feed-forwards, parts ``mlp`` (the norm before it, the SwiGLU or
GELU, a patterned model's dense lead) and ``shared_expert``.
Forward, rematerialised forward and backward together. Which operation
belongs to the part the PROGRAM says: the engine reads each compiled
program's ``op_name``s once (``Engine.program_facts``) and the capture
carries the table (``benchmark/program_parts.py`` joins it to the trace
file). Nothing where the capture has no ``programs`` (a commit before
them), nothing was profiled, or the cell trains nothing."""

from benchmark import program_parts


def read(record):
    return program_parts.train(record, *("mlp", "shared_expert"))
