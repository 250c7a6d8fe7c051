"""Own device seconds a step and chip, in the train program
(``jit_train_step`` / ``jit_train_seq``) of the profiled steps, of the
loops' OWN work, part ``layers`` with its sub-part ``layers/loop``
(``realhf_tpu/obs/parts.py:LAYERS``, ``LOOP``): what a looped model's
loop of passes does around the layer scans (every pass's final hidden
state and gate logit stacked for the objective, a pass's carry:
``layers/loop``) together with what a layer scan does inside a pass
(a layer's weights out of their stack, what the backward keeps a layer
and reads back, the shared weights' gradients added into their
accumulator's rows: ``layers``). What the blocks compute is NOT in it:
attention, projections and feed-forward have parts of their own. A
model that is
not looped has ``layers`` alone, which has a name and no metric of its
own in its cells (``train.unscoped_s``'s docstring).
Forward, rematerialised forward and backward together. Which operation
belongs to the part the PROGRAM says (``benchmark/program_parts.py``
joins the engine's table to the trace file). Nothing where the capture
has no ``programs``, nothing was profiled, or the cell trains
nothing."""

from benchmark import program_parts


def read(record):
    return program_parts.train(record, *("layers",))
