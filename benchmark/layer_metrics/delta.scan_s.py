"""Own device seconds a step and chip, in the train program
(``jit_train_step`` / ``jit_train_seq``) of the profiled steps, of the
delta layers' chunked recurrence alone, sub-part ``delta/scan``
(``realhf_tpu/obs/parts.py:SCAN``; ``realhf_tpu/ops/delta_rule.py``):
the running decays, the pairs inside a chunk, the triangular inverse,
the chunks' coefficients, the scan that carries the state and the
outputs, with the l2 norm of q and k and the decay's softplus, which
the recurrence applies a segment at a time. Forward, rematerialised
forward and backward together; a part of ``train.delta_s``, which
holds the whole of ``delta``. Which operation belongs to the sub-part
the PROGRAM says (``benchmark/program_parts.py`` joins the engine's
table to the trace file). Nothing where the capture has no
``programs``, nothing was profiled, or the cell trains nothing; 0 where
the program has no such sub-part (a commit before it, a model without
delta layers)."""

from benchmark import program_parts


def read(record):
    return program_parts.seconds_a_step(
        record, "jit_train_",
        lambda part, pass_, opcode, phase: part == "delta/scan")
