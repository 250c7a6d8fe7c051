"""Own device seconds a step and chip, in the train program of the
profiled steps, of sub-part ``index/select``: the visibility mask of a
block of queries, the bisection over the scores' bits that finds each
query's ``topk``-th largest score, the ties' order, and the int8 rows
of the selection (``realhf_tpu/ops/sparse_index.py:select_topk``). A
part of ``sparse.index_s``, which holds all of ``index``; what is left
of that beside this is the scores' products and the projections.
Nothing where the capture has no ``programs``, nothing was profiled, or
the cell trains nothing; 0 where the program has no such sub-part (a
commit before it)."""

from benchmark import program_parts


def read(record):
    return program_parts.seconds_a_step(
        record, "jit_train_",
        lambda part, pass_, opcode, phase: part == "index/select")
