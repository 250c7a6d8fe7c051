"""Own device seconds a step and chip, in the ``jit_generate`` program
of the profiled steps, of the decode steps' dense work, parts
``attn_proj``, ``mlp`` and ``layers`` under scope ``decode``: norms,
projections, rotary, feed-forward, one token a stream, and the layer
loop's slices of the weights out of their stack (a copy a layer and
token where XLA does not fuse the slice into the product).
Which operation belongs where the PROGRAM says: the engine reads the
compiled program's ``op_name``s once (``Engine.program_facts``) and the
capture carries the table (``benchmark/program_parts.py`` joins it to
the trace file). Nothing where the capture has no ``programs`` (a
commit before them), nothing was profiled, or the cell generates
nothing."""

from benchmark import program_parts


def read(record):
    return program_parts.generate(
        record, lambda part, pass_, opcode, phase:
        phase == "decode" and part in ("attn_proj", "mlp", "layers"))
