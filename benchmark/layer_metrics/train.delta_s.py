"""Own device seconds a step and chip, in the train program
(``jit_train_step`` / ``jit_train_seq``) of the profiled steps, of the
delta layers' mixers, part ``delta``: the norm before them, their
projections, short convolutions and gates, the chunked recurrence
(sub-part ``delta/scan``, which ``delta.scan_s`` reads alone), the
output's norm and gate, ``wo``.
Forward, rematerialised forward and backward together. Which operation
belongs to the part the PROGRAM says: the engine reads each compiled
program's ``op_name``s once (``Engine.program_facts``) and the capture
carries the table (``benchmark/program_parts.py`` joins it to the trace
file). Nothing where the capture has no ``programs`` (a commit before
them), nothing was profiled, or the cell trains nothing; 0 where the
program has no such part (a commit before it, a model without delta
layers)."""

from benchmark import program_parts


def read(record):
    return program_parts.train(record, *("delta",))
