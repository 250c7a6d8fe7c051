"""Own device seconds a step and chip of the operations of the train
program that NO part claims: not attention, projections, feed-forward,
experts, convolution, head, loss or accumulation, and not ``optimizer``,
``embed`` or ``layers`` (the layer loop's own slices and stacks) either:
those three have a name and no metric. The honesty metric of the
``train.*_s`` family: above 5% of the program a ``jax.named_scope`` is
missing in the model (or the program came out of a persistent cache
written before the scopes). The parts' metrics, ``optimizer``, ``embed``,
``layers`` and this add up to the program's own device seconds. Nothing
where the capture has no ``programs``, nothing was profiled, or the cell
trains nothing."""

from benchmark import program_parts


def read(record):
    return program_parts.seconds_a_step(
        record, "jit_train_",
        lambda part, pass_, opcode, phase: part is None)
