"""Own device seconds a step and chip, in the train program
(``jit_train_step`` / ``jit_train_seq``) of the profiled steps, of a
looped model's exit gate, part ``exit``
(``realhf_tpu/obs/parts.py:EXIT``): the gate's projection of every
pass's final hidden state (``models/transformer.py:exit_logit``), the
exit distribution over the passes (``ops/functional.py:
exit_log_distribution``), the passes' losses weighed by it and its
entropy (``interfaces/sft.py``). The heads the gate weighs are NOT in
it: they stay ``vocab_head`` and ``loss`` (``train.head_s``).
Forward, rematerialised forward and backward together. Which operation
belongs to the part the PROGRAM says: the engine reads each compiled
program's ``op_name``s once (``Engine.program_facts``) and the capture
carries the table (``benchmark/program_parts.py`` joins it to the trace
file). Nothing where the capture has no ``programs`` (a commit before
them), nothing was profiled, or the cell trains nothing; 0 where the
program has no such part (a commit before it, a model without an exit
gate)."""

from benchmark import program_parts


def read(record):
    return program_parts.train(record, *("exit",))
