"""Own device seconds a step and chip of the operations of the train
program whose opcode is a collective (``all-reduce``,
``reduce-scatter``, ``all-gather``, ``all-to-all``,
``collective-permute``, their ``-start`` / ``-done``), whatever their
part: the time the device's operation line is held by communication.
Communication that overlaps compute on another line is not in it. The
opcode is the compiled program's own (``Engine.program_facts``).
Nothing where the capture has no ``programs``, nothing was profiled,
or the cell trains nothing."""

from benchmark import program_parts


def read(record):
    return program_parts.seconds_a_step(
        record, "jit_train_",
        lambda part, pass_, opcode, phase:
        program_parts.is_collective(opcode))
