"""Own device seconds a step and chip, in the train program
(``jit_train_step`` / ``jit_train_seq``) of the profiled steps, of what
makes a latent layer's keys and values from the token's compressed row,
sub-part ``attn_proj/latent``: the compression ``u W_kva``, the latent's
norm, the expansion a head ``c W_kvb``, the shared rotary key's rotation
and its broadcast to every head (``realhf_tpu/obs/parts.py:LATENT``).
Forward, rematerialised forward and backward together; a part of
``train.attn_proj_s``, which holds the whole of ``attn_proj``. Which
operation belongs to the sub-part the PROGRAM says
(``benchmark/program_parts.py`` joins the engine's table to the trace
file). Nothing where the capture has no ``programs``, nothing was
profiled, or the cell trains nothing; 0 where the program has no such
sub-part (a commit before it, a model without latent layers)."""

from benchmark import program_parts


def read(record):
    return program_parts.seconds_a_step(
        record, "jit_train_",
        lambda part, pass_, opcode, phase: part == "attn_proj/latent")
