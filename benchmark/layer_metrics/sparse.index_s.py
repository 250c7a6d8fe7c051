"""Own device seconds a step and chip, in the train program
(``jit_train_step`` / ``jit_train_seq``) of the profiled steps, of the
sparse layers' indexers, part ``index``: the indexer's three
projections, its key's LayerNorm and the rotary embedding of its
queries and key (``index/project``), its scores of every (query, key)
pair of a row, a block of 512 queries at a time (``index/scores``), and
the choice of the ``topk`` best visible keys a query, written as the
int8 selection the flash kernels take (``index/select``, which
``sparse.select_s`` reads alone). Forward only: no gradient reaches an
indexer, and a rematerialised block KEEPS the selection, so neither the
rematerialised pass nor the backward holds anything of the part (a
``remat`` share here says the selection is made twice). Which operation
belongs to the part the PROGRAM says (``benchmark/program_parts.py``
joins the engine's table to the trace file). Nothing where the capture
has no ``programs``, nothing was profiled, or the cell trains nothing;
0 where the program has no such part (a commit before it, a model
without sparse layers)."""

from benchmark import program_parts


def read(record):
    return program_parts.train(record, *("index",))
