"""Share of the (query, key) pairs under the packed rows' causal masks
that the sparse layers attend, in percent: the growth of
``sparse_pairs_total{kind=selected}`` over that of ``{kind=causal}`` in
the profiled steps. The engine counts both on the host from each
batch's segment ids (``realhf_tpu/ops/sparse_index.py:pair_counts``: a
token at position p of its document sees p + 1 keys and selects
``min(p + 1, topk)``), times the sparse layers. 75.0 on documents of
4,096 tokens at ``topk`` 2,048; 100 says that no row reached ``topk``
keys, so the selection is every visible key and the layer is plain
attention. (That the kernels TAKE the selection is the ``engine:train``
span's ``flash_mask_calls``.) Nothing where the program has no such
counter (a commit before it, a model without sparse layers)."""

from benchmark import program_capture


def _growth(capture, kind):
    return sum(v for k, v in capture.counters.items()
               if k.startswith("sparse_pairs_total")
               and f"kind={kind}" in k)


def read(record):
    capture = program_capture.last(program_capture.profiled)
    if capture is None:
        return None
    selected = _growth(capture, "selected")
    causal = _growth(capture, "causal")
    if not causal:
        return None
    return 100.0 * selected / causal
