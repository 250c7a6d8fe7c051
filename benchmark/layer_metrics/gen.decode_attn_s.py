"""Own device seconds a step and chip, in the ``jit_generate`` program
of the profiled steps, of the decode steps' attention, part
``attn`` under scope ``decode``: the decode kernel (or the XLA path), the
token's write into the stacked cache and what XLA puts around them.
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
        phase == "decode" and part == "attn")
