"""Own device seconds a step and chip, in the train program
(``jit_train_step`` / ``jit_train_seq``) of the profiled steps, of
the attention operator, part ``attn``: the flash kernels (or the XLA
path) AND what XLA puts directly around them (transposes into the
kernels' layout, the log-sum-exp's slice and broadcast, the kept
residuals' copies). ``flash.mxu_share`` beside it times the kernels
alone.
Forward, rematerialised forward and backward together. Which operation
belongs to the part the PROGRAM says: the engine reads each compiled
program's ``op_name``s once (``Engine.program_facts``) and the capture
carries the table (``benchmark/program_parts.py`` joins it to the trace
file). Nothing where the capture has no ``programs`` (a commit before
them), nothing was profiled, or the cell trains nothing."""

from benchmark import program_parts


def read(record):
    return program_parts.train(record, *("attn",))
