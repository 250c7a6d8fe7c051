"""Own device seconds a step and chip, in the train program
(``jit_train_step`` / ``jit_train_seq``) of the profiled steps, of
the head, parts ``vocab_head`` (the final norm and the head's chunk
bodies) and ``loss`` (what the interface's objective does after the
picked log-probabilities).
Forward, rematerialised forward and backward together. Which operation
belongs to the part the PROGRAM says: the engine reads each compiled
program's ``op_name``s once (``Engine.program_facts``) and the capture
carries the table (``benchmark/program_parts.py`` joins it to the trace
file). Nothing where the capture has no ``programs`` (a commit before
them), nothing was profiled, or the cell trains nothing."""

from benchmark import program_parts


def read(record):
    return program_parts.train(record, *("vocab_head", "loss"))
