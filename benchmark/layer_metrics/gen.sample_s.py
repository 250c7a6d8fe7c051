"""Own device seconds a step and chip, in the ``jit_generate`` program
of the profiled steps, of choosing the next token: everything
under scope ``sample`` (warping, the draw, the log-probability, the
output buffers) and the vocabulary head (``vocab_head`` under
``decode``: final norm and logits).
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
        phase == "sample" or (
            phase == "decode" and part == "vocab_head"))
