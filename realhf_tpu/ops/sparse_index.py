"""The learned indexer of a sparse attention layer: which keys a token
attends over (``models/config.py:IndexerConfig`` has the equations).

Three steps, each a sub-scope of part ``index`` (``obs/parts.py``) where
``models/transformer.py`` calls them: the projections (there), the
scores ``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])``
(:func:`index_scores`) and the selection of the ``topk`` visible keys of
largest score a query (:func:`select_topk`). :func:`selection_mask`
runs the last two a BLOCK OF QUERIES at a time (``[heads, 512, L]``
scores, not ``[heads, L, L]``: 1.07 GB in float32 at 16 heads and a row
of 4096), and ONLY the blocks in which some query of the batch sees
more than ``topk`` keys (:func:`scoring_blocks`): a query that sees no
more selects all it sees, so every other block's rows are the
visibility mask, which no score changes. On documents of 4,096 at a
``topk`` of 2,048 that is half the blocks; on rows of documents no
longer than ``topk`` no block scores at all. It returns the selection
as the int8 ``[B, L, L]`` mask the attention functions take as one
more operand (``ops/flash_attention.py``, ``ops/attention.py``).

The selection is EXACT. ``jax.lax.top_k`` at k = 2048 of 4096 is a full
sort on the TPU; here the k-th largest score of a row is found by
bisection over the scores' BITS (32 steps, each one comparison and one
count over the row: a float32's bits, sign folded, order as the values
do), then every key above it is taken and, of the keys equal to it, the
lowest-numbered ones until there are ``topk`` (ties go to the lower s:
what a stable descending sort gives). Which of the two is faster on the
chip PERF.md says (PR 45); an approximate top-k would be a different
model, not a faster one.
"""

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from realhf_tpu.obs import parts as P

#: rows of queries whose index scores are held at once
QUERY_BLOCK = 512


def index_scores(q_index: jnp.ndarray, k_index: jnp.ndarray,
                 weights: jnp.ndarray) -> jnp.ndarray:
    """``I[b, t, s] = sum_j weights[b, t, j] ReLU(q_index[b, t, j] .
    k_index[b, s])`` in float32: q_index [B, Tq, n, d], k_index
    [B, S, d], weights [B, Tq, n] (already scaled) -> [B, Tq, S]."""
    s = jnp.einsum("bqnd,bkd->bnqk", q_index, k_index,
                   preferred_element_type=jnp.float32)
    w = weights.astype(jnp.float32).transpose(0, 2, 1)[..., None]
    return (jax.nn.relu(s) * w).sum(axis=1)


def _ordered_bits(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 whose unsigned order is the floats' (-0.0 and
    0.0 one value; no NaN is expected): the sign bit set on what is not
    negative, every bit flipped on what is."""
    x = jnp.where(x == 0, 0.0, x.astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    negative = (bits >> 31).astype(bool)
    return jnp.where(negative, ~bits, bits | jnp.uint32(1 << 31))


def select_topk(scores: jnp.ndarray, visible: jnp.ndarray,
                topk: int) -> jnp.ndarray:
    """bool [..., S]: the ``topk`` VISIBLE entries of largest score of
    each row of ``scores`` [..., S] (float32), ties to the lower index;
    every visible entry of a row that has no more than ``topk``."""
    # an entry that is not visible sorts below every float
    key = jnp.where(visible, _ordered_bits(scores), jnp.uint32(0))
    kth = jnp.zeros(key.shape[:-1], jnp.uint32)
    for bit in range(31, -1, -1):
        # the largest value that ``topk`` entries reach: bit by bit
        trial = kth | jnp.uint32(1 << bit)
        reached = (key >= trial[..., None]).sum(-1, dtype=jnp.int32)
        kth = jnp.where(reached >= topk, trial, kth)
    above = key > kth[..., None]
    tie = key == kth[..., None]
    room = topk - above.sum(-1, dtype=jnp.int32)
    first_ties = jnp.cumsum(tie, axis=-1, dtype=jnp.int32) \
        <= room[..., None]
    return (above | (tie & first_ties)) & visible


def _block_of(l: int, block: int) -> int:
    """The rows of queries a block holds on rows of ``l``: ``block``
    where it divides the row, else the whole row as one block."""
    return block if l % block == 0 else l


def visible_counts(seg_ids, xp=jnp):
    """int32 ``[..., L]``: the keys each token of packed rows sees: its
    position in its document plus one (a document is ONE contiguous run
    of its id; run starts as ``ops/flash_attention.py:block_ranges``
    finds them), 0 for padding. No score changes it. ``xp`` is ``jnp``
    inside a program and ``np`` on the host: one rule for both."""
    idx = xp.arange(seg_ids.shape[-1], dtype=xp.int32)
    new = xp.concatenate([xp.ones_like(seg_ids[..., :1], dtype=bool),
                          seg_ids[..., 1:] != seg_ids[..., :-1]], axis=-1)
    # (not xp.maximum.accumulate inside a program: jnp's is a
    # sequential scan, L steps of a while loop on the device)
    cummax = np.maximum.accumulate if xp is np else jax.lax.cummax
    start = cummax(xp.where(new, idx, 0), axis=seg_ids.ndim - 1)
    return xp.where(seg_ids != 0, idx - start + 1, 0)


def scoring_blocks(seg_ids, topk: int, block: int = QUERY_BLOCK, xp=jnp):
    """bool ``[..., n]``: the blocks of ``block`` queries of packed rows
    ``seg_ids [..., B, L]`` that have to SCORE: those in which some
    token of some of the ``B`` rows sees more than ``topk`` keys. Every
    other block's selection is its visibility mask, whatever the
    indexer's weights (:func:`select_topk`'s last clause). The rule
    :func:`selection_mask` loops by (``xp=jnp``) and the engine's
    counter ``index_blocks_total`` counts by (``xp=np``; leading axes:
    one call of the program each)."""
    *lead, b, l = seg_ids.shape
    block = _block_of(l, block)
    over = visible_counts(seg_ids, xp) > topk
    return over.reshape(*lead, b, l // block, block).any(axis=(-3, -1))


def selection_mask(q_index: jnp.ndarray, k_index: jnp.ndarray,
                   weights: jnp.ndarray, seg_ids: jnp.ndarray,
                   topk: int, block: int = QUERY_BLOCK) -> jnp.ndarray:
    """int8 [B, L, L], 1 where query t of a packed row attends key s:
    s among the ``topk`` best-scored keys of t's document at or before
    t. ``q_index`` [B, L, n, d], ``k_index`` [B, L, d], ``weights``
    [B, L, n], ``seg_ids`` [B, L] (0 = padding).

    A query that sees no more than ``topk`` keys selects every one of
    them (:func:`select_topk`: "every visible entry of a row that has
    no more than ``topk``"), so its row of the selection IS its row of
    the visibility mask (same document, not padding, at or before t)
    and no score can change one entry of it. The visibility mask is
    therefore written for the whole ``[B, L, L]`` first, and only the
    blocks of ``block`` queries in which SOME row of the batch sees
    more than ``topk`` keys (:func:`scoring_blocks`: one scalar a block
    over all rows) are scored and selected, one after another in a
    loop over as many blocks as score (a dynamic bound, no
    conditional), each overwriting its rows: what is held is one
    block's scores, and a batch of short documents runs no indexer at
    all. Bit-equal to scoring every block."""
    l = seg_ids.shape[1]
    block = _block_of(l, block)
    cols = jnp.arange(l, dtype=jnp.int32)

    def visible(seg_q, rows):  # [B, T], [T] -> bool [B, T, L]
        return (seg_q[:, :, None] == seg_ids[:, None, :]) \
            & (seg_q[:, :, None] != 0) \
            & (rows[None, :, None] >= cols[None, None, :])

    with jax.named_scope(P.SELECT):
        select = visible(seg_ids, cols).astype(jnp.int8)
        scoring = scoring_blocks(seg_ids, topk, block)
        # the blocks that score first, in their order
        order = jnp.argsort(~scoring, stable=True).astype(jnp.int32)

    def one(i, select):
        start = order[i] * block

        def rows_of(x):  # [B, L, ...] -> [B, block, ...]
            return jax.lax.dynamic_slice_in_dim(x, start, block, axis=1)

        with jax.named_scope(P.SCORES):
            scores = index_scores(rows_of(q_index), k_index,
                                  rows_of(weights))
        with jax.named_scope(P.SELECT):
            picked = select_topk(
                scores, visible(rows_of(seg_ids), start + cols[:block]),
                topk).astype(jnp.int8)
            return jax.lax.dynamic_update_slice_in_dim(
                select, picked, start, axis=1)

    return jax.lax.fori_loop(0, scoring.sum(dtype=jnp.int32), one, select)


def pair_counts(seg_ids: np.ndarray, topk: int):
    """``(selected, causal)``: the (query, key) pairs one sparse layer
    attends over packed rows ``seg_ids [..., L]`` and the pairs under
    its documents' causal masks, one head's: a token at position p of
    its document sees p + 1 keys (:func:`visible_counts`) and selects
    ``min(p + 1, topk)`` of them, whatever the indexer's weights. On
    the host, in numpy; the engine's counter ``sparse_pairs_total``
    adds these up."""
    seen = visible_counts(np.asarray(seg_ids), xp=np)
    return int(np.minimum(seen, topk).sum()), int(seen.sum())


def unselected_blocks(select: np.ndarray, seg_ids: np.ndarray,
                      bq: Optional[int] = None, bk: Optional[int] = None):
    """``(empty, visited)``: of the (query block, key block) pairs the
    forward flash kernel visits over packed rows (by its own rule,
    ``ops/flash_attention.py:block_ranges``), those that hold NO
    selected pair. ``select`` [B, L, L], ``seg_ids`` [B, L]; on the
    host. Block skipping by the selection would save exactly these."""
    from realhf_tpu.ops import flash_attention as F
    select, seg = np.asarray(select), np.asarray(seg_ids)
    b, l = seg.shape
    bq, bk = F._blocks(l, bq or F.DEFAULT_BQ, bk or F.DEFAULT_BK)
    (lo, hi), *_ = F.block_ranges(seg, bq, bk, xp=np)
    any_pair = select.reshape(b, l // bq, bq, l // bk, bk).any((2, 4))
    j = np.arange(l // bk)
    visit = (j >= lo[..., None]) & (j < hi[..., None])
    return int((visit & ~any_pair).sum()), int(visit.sum())
