"""Pallas flash attention over packed segments (TPU).

TPU-native replacement for the reference's flash-attn varlen kernels
(``realhf/impl/model/modules/attn.py:20-23``): tiled online-softmax
attention (flash-attention-2 schedule) with

- causal masking, and a sliding window (``sliding_window=W``: a
  query sees the last W tokens of its document, itself among them),
- segment-id masking for packed variable-length sequences (the
  cu_seqlens equivalent), and loops that visit only the blocks a
  block's own segments reach,
- GQA (query-head groups share KV heads),
- a custom VJP with Pallas backward kernels (dq and dkv passes),
  recomputing probabilities from the saved log-sum-exp.

Layout contract: q [B, L, nq, hd], k [B, L, nkv, hd], v [B, L, nkv, hv],
seg_ids [B, L] (0 = padding). ``hd`` is the query/key's width, which
the scores contract over, ``hv`` the value's, which the output and its
cotangent share; most models have ``hv == hd``, latent attention has
(192, 128), and the kernels read each from its own operand. One
segment id is ONE contiguous run of a row, as
``engine/packing.py:segment_ids`` lays sequences out (ids in no
order: the packer places the longest first) and as
``models/transformer.py:positions_from_segments`` and the window test
of ``ops/attention.py:_segment_mask`` already assume. L must be a
multiple of the Q block; hd and hv should be multiples of 128 for MXU
tiling (128 for llama-family models). A key's width of 192 compiles
and runs: its blocks take the whole last axis, VMEM holds them over
256 lanes, and a score-shaped product contracts over two passes of the
128-wide MXU, half of the second empty. On the chip that is not what
limits the kernels: at (192, 128), 16 heads and rows of 4096 they run
at 55.5% of the matrix peak by the mathematics' count, over the 48.5%
of window and full layers at (128, 128) (PERF.md, PR 37).

Which blocks are visited. A token attends inside its own segment
only, so a query block needs the key blocks from the lowest start to
the highest end of the segments its non-padding tokens belong to, cut
at its causal diagonal, and a key block the query blocks likewise
(``block_ranges``). Both ranges are computed from ``seg_ids`` inside
the jitted program, handed to the kernels by scalar prefetch and made
the bounds of their loops: forward and dq over ``[kv_lo, kv_hi)`` of
their query block, dkv over ``[q_lo, q_hi)`` of its key block. A
block of another segment is never computed, so the numbers are those
of visiting every block up to the diagonal: a block left out
contributed ``p = 0``, or garbage that the next rescaling by ``alpha
= 0`` wiped. A row of one segment visits the whole causal triangle; a
block of padding alone visits nothing. The range runs from the first
to the last block that holds an unmasked pair; only padding that fills
whole blocks between two segments of one block leaves a masked block
inside it.

Which visited pairs build a mask. Only a pair that an edge crosses: a
document's, padding, the causal diagonal, a window's. ``block_ranges``
gives, inside each range, the sub-range ``[full_lo, full_hi)`` of
pairs that attend EVERY (row, column) (the query block in one
document, the key block whole inside it, before the diagonal and
inside the window), prefetched as two more scalars, and each kernel
runs three loops: ``[lo, full_lo)`` and ``[full_hi, hi)`` with the
mask of segments, causality and window built as ever, ``[full_lo,
full_hi)`` without iotas, compares, ``and``s or ``where`` (``s`` goes
to the running maximum as it is; ``p = exp(s - lse)``). A ``where``
under an all-true mask is the identity, so the results are, bit for
bit, those of masking every pair. Under a selection an interior pair's
mask IS the selection's block. With one document of 4096 a row 56 of a
full layer's 72 pairs are interior; with documents of 512 (two query
blocks on one key block, both on the diagonal) none, and under a window
narrower than a pair's two blocks (512 < 256 + 512) none whatever the
segments: such a layer keeps one loop. So that three loops cost no
more than one at their boundaries, the forward's accumulator is a VMEM
scratch and the dkv pass accumulates in its float32 output blocks (a
carried ``[BQ, hv]`` is copied between one loop's spill slots and the
next's); PERF.md, PR 47, has the static schedule's counts.

A window bounds both ranges a second time, from the row indices alone
(inside a document the distance in the row IS the distance in the
document): a query block starts no earlier than the block of its
first row's oldest visible key, ``first row - (W - 1)``, and a key
block ends no later than the block of the last query that sees its
last column, ``last column + (W - 1)``; the kernels' masks add ``row
- col < W``. With ``sliding_window=None`` ranges, masks and programs
are what they are without this paragraph. In a row of
``FLASH_MAX_LEN`` or less the window takes nothing off VMEM (K and V
are whole a head there); it takes blocks off the loops (a row of 4096
at W = 512 visits 30 of its 72 causal block pairs). In a longer row
the same ranges say which blocks are FETCHED (below).

A learned selection (``select=`` int8 ``[B, L, L]``, 0 = not attended:
a sparse layer's, ``ops/sparse_index.py``) is ONE MORE blocked operand
of the three kernels beside the segment views: the rows of the step's
query block and every column in the forward and the dq pass (256 x L
bytes), every row and the columns of the step's key block in the dkv
pass (L x 512), widened to int32 in the kernel and ``and``ed into the
mask a block pair at a time (the whole mask of a pair that no edge
crosses). Every head of a query shares it. No pair
it leaves out can be computed inside the kernel from row metadata, as
segments, causality and the window are; the block ranges stay theirs
(a visited block with no selected pair is multiplied and masked away:
skipping it is ROADMAP R4c (b)). A kernel that takes one is named
``flash_fwd_sel`` / ``flash_bwd_dq_sel`` / ``flash_bwd_dkv_sel`` and
has a ``custom_vjp`` of its own; a call without one is, to the byte of
its jaxpr, the call it was.

What is resident in VMEM. In a row of ``FLASH_MAX_LEN`` tokens or
less, K and V (forward, dq) and Q, dO, lse, delta (dkv) are whole per
(batch, head) whatever the ranges say: one DMA a head, and the loops
above slice them. That bounds L: ``FLASH_MAX_LEN`` is what the v5e
compiler accepts for forward AND backward at the head sizes of the
supported families. A LONGER row goes to three kernels of their own,
``flash_fwd_stream`` / ``flash_bwd_dq_stream`` /
``flash_bwd_dkv_stream``, whose VMEM does not grow with L: the loop
over a block's visited pairs is the LAST GRID AXIS there, and Mosaic's
pipeline fetches one block of the streamed side a grid step, double
buffered, while the step before it computes. Forward and dq hold one
block of Q (dq: and its dO, lse, delta, and the float32 dq it
accumulates) of ``STREAM_HEADS`` query heads that share ONE key/value
head, and step over the key blocks ``[kv_lo, kv_hi)`` of the query
block, the block index taken from the prefetched range in the index
map; the dkv pass holds one block of K and V and its float32 dK and
dV and steps over the query blocks ``[q_lo, q_hi)`` of those heads.
The axis is as long as the longest range any block of a row of ONE
document has (every packed row's ranges lie inside those: 32 key
blocks of 512 in a full layer at 16,384, 9 under a window of 4096);
a step past a block's own ``hi`` repeats the last block's index, so
nothing is fetched, and computes nothing. A fetched block of K and V
serves all the query heads of the step (7 of SmallThinker's 28 over 4:
one fetch of 262 KB against 7 x 67 MFLOP in the forward, seven times
the chip's ridge), and the pair's mask is built once for them; the
running maximum, sum and accumulator of the heads live in VMEM scratch
between steps, maximum and sum lane-broadcast there and through the
forward's arithmetic (``_fwd_stream_kernel``). The dkv pass computes
TRANSPOSED scores, ``K Q^T`` [BK, BQ]: lse and delta are then rows,
read as they are kept, one float32 a (row, head), and no product
contracts over its operands' first axes. What bounds L there is
``FLASH_STREAM_MAX_LEN``, the longest row the compile test asks the
v5e compiler for; a selection
(``select=``) or a key wider than its value past ``FLASH_MAX_LEN``
raises by name, as does a row past either bound: a row the compiler
would refuse never drops to the O(L^2) XLA path in silence. Longer
rows still need more microbatches or a context-parallel mesh (ring
attention).

Mosaic requires the last two dims of every block to be (8, 128)-tile
aligned, so 1D row metadata rides wider layouts: q-side segment ids
and lse/delta are broadcast over a 128-lane axis, k-side segment ids
over an 8-sublane axis (same scheme as jax's bundled flash kernel).
The SAVED lse is one float32 a (row, head), ``[B, nq, L]``; the
backward broadcasts it again (``RESIDUAL_NAMES``).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from realhf_tpu.obs import parts as P
from realhf_tpu.ops.hlo_text import device_instructions

DEFAULT_BQ = 256
DEFAULT_BK = 512
#: Longest packed row whose K and V (and, in the dkv pass, Q, dO, lse
#: and delta) the kernels hold WHOLE a head in VMEM; a longer row takes
#: the ``*_stream`` kernels, which hold blocks. Asked of the v5e
#: compiler (libtpu 0.0.34, bf16, tests/ops/test_chip_compile.py): the
#: whole-row backward compiles to L = 5120 at (14 q, 2 kv, hd 64) and
#: to 6144 at (32, 8, 128) and runs out of VMEM one kilotoken above
#: either; the whole-row forward alone compiles to 8192 and is refused
#: at 16384, with or without a window; the windowed backward at
#: (64, 8, 128) x 4096 compiles too, as does the backward at a key's
#: width of 192 and a value's of 128 (16, 16 heads) x 4096.
FLASH_MAX_LEN = 4096
#: Longest packed row the ``*_stream`` kernels take: their VMEM does
#: not grow with L (the compile test asks the v5e compiler for (28 q, 4
#: kv, 128) x 16,384 and x 32,768, forward and backward, with and
#: without a window of 4096); what grows is the grid and the
#: prefetched ranges in SMEM, four int32 a block
FLASH_STREAM_MAX_LEN = 32768
#: what the name of a kernel that streams its blocks ends in
STREAM_SUFFIX = "_stream"
#: most query heads (of ONE key/value head) a grid step of a stream
#: kernel serves from one fetched block of K and V: the largest divisor
#: of the group up to this (7 of 28 over 4, 8 of 64 over 8, 1 where
#: every head has keys of its own). Their blocks and scratch are what
#: the kernels hold: 8 MB at 7 heads of 128 in bf16; and the forward's
#: pair body is these heads written out in one straight line (about
#: 550 bundles a head: its size and its compile grow with this)
STREAM_HEADS = 8
NEG_INF = -2.0 ** 30
LANES = 128
SUBLANES = 8
#: The two residuals of the backward that only the forward kernel can
#: make, by the names ``_flash_attention_fwd`` gives them
#: (``checkpoint_name``): the output, head-major ``[B, nq, L, hv]`` as
#: the kernel writes it (the VALUE's width), and the log-sum-exp, one
#: float32 a (row, head), ``[B, nq, L]``. A ``jax.checkpoint`` whose
#: policy keeps both (``models/transformer.py:_remat``) recomputes q, k
#: and v in the backward but not the kernel: ``2 hv + 4`` bytes a
#: (token, head) against a second run of ``flash_fwd``.
RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _blocks(l: int, bq: int, bk: int):
    bq = min(bq, l)
    bk = min(bk, l)
    while l % bq:
        bq //= 2
    while l % bk:
        bk //= 2
    return max(bq, 8), max(bk, 8)


# ----------------------------------------------------------------------
# Which blocks a block's segments reach
# ----------------------------------------------------------------------
def block_ranges(seg_ids, bq: int, bk: int, causal: bool = True, xp=jnp,
                 sliding_window: Optional[int] = None):
    """The key blocks each query block has to visit and the query
    blocks each key block has to visit, from the segment ids alone:
    ``(kv_lo, kv_hi) [B, L // bq]`` and ``(q_lo, q_hi) [B, L // bk]``,
    int32, ``hi`` one past the last block; and third, inside each, the
    sub-range of pairs that no edge crosses, ``((kv_full_lo,
    kv_full_hi), (q_full_lo, q_full_hi))``.

    A token attends only inside its own segment, and a segment is ONE
    contiguous run of its id, so the tokens of a block reach no
    further than from the lowest start to the highest end of the runs
    that its non-padding tokens belong to (run starts as
    ``models/transformer.py:positions_from_segments`` finds them).
    Causality cuts that span at the block's own diagonal. A block of
    padding alone gets an empty range (``lo >= hi``); a row of one
    segment gets the whole causal triangle. A ``sliding_window`` of W
    (with ``causal``) cuts the span again: no key before the query
    block's first row less W - 1, no query after the key block's last
    column plus W - 1.

    A pair of the sub-range attends EVERY (row, column): the query
    block lies in one non-padding segment, the key block whole inside
    that segment, its last column at or before the query block's first
    row (``causal``), and the query block's last row less the key
    block's first column is under W. Segments are contiguous runs, so
    these pairs are contiguous: ``lo <= full_lo <= full_hi <= hi``, and
    ``full_lo == full_hi`` where a block holds two documents or
    padding. The kernels build no mask there. ``xp`` is ``jnp`` inside
    a program and ``np`` for :func:`block_counts`: one rule for
    both."""
    b, l = seg_ids.shape
    idx = xp.arange(l, dtype=xp.int32)[None, :]
    edge = seg_ids[:, 1:] != seg_ids[:, :-1]
    true = xp.ones((b, 1), bool)
    # (not xp.maximum.accumulate inside a program: jnp's is a
    # sequential scan, L steps of a while loop on the device)
    if xp is np:
        cummax = functools.partial(np.maximum.accumulate, axis=1)

        def cummin_reverse(x):
            return np.minimum.accumulate(x[:, ::-1], axis=1)[:, ::-1]
    else:
        cummax = functools.partial(jax.lax.cummax, axis=1)
        cummin_reverse = functools.partial(jax.lax.cummin, axis=1,
                                           reverse=True)
    start = cummax(xp.where(xp.concatenate([true, edge], axis=1), idx, 0))
    end = cummin_reverse(
        xp.where(xp.concatenate([edge, true], axis=1), idx + 1, l))
    valid = seg_ids != 0
    start = xp.where(valid, start, l)
    end = xp.where(valid, end, 0)

    def span(block):
        """Lowest start and highest end of a block's runs, its first
        index, and whether it lies in ONE run and holds no padding:
        every token's run starts at or before the block (padding's
        "starts" at ``l``)."""
        starts = start.reshape(b, l // block, block)
        first = xp.arange(l // block, dtype=xp.int32) * block
        return (starts.min(-1), end.reshape(b, l // block, block).max(-1),
                first, starts.max(-1) <= first)

    q_start, q_end, q_first, q_one = span(bq)
    k_start, k_end, k_first, k_one = span(bk)
    # the pairs no edge crosses, by the blocks' own run [start, end):
    # the other block whole inside it
    kv_full = (-(-q_start // bk), q_end // bk)
    q_full = (-(-k_start // bq), k_end // bq)
    if causal:
        # keys at or before the query block's last row; queries at or
        # after the key block's first column
        q_end = xp.minimum(q_end, q_first + bq)
        k_start = xp.maximum(k_start, k_first)
        # no edge: the key block's last column at or before the query
        # block's first row
        kv_full = (kv_full[0], (q_first + 1) // bk)
        q_full = (-(-(k_first + bk - 1) // bq), q_full[1])
    if sliding_window is not None:
        assert causal, "a sliding window is a causal window"
        # keys no older than W - 1 before the query block's first row;
        # queries no later than W - 1 after the key block's last column
        q_start = xp.maximum(q_start, q_first - (sliding_window - 1))
        k_end = xp.minimum(k_end, k_first + bk + (sliding_window - 1))
        # no edge: the query block's LAST row sees the key block's
        # FIRST column
        kv_full = (xp.maximum(
            kv_full[0], -(-(q_first + bq - sliding_window) // bk)),
            kv_full[1])
        q_full = (q_full[0], xp.minimum(
            q_full[1], (k_first + sliding_window) // bq))
    kv_range = (q_start // bk, -(-q_end // bk))
    q_range = (k_start // bq, -(-k_end // bq))

    def inside(full, one, visited):
        """``full`` cut to ``lo <= full_lo <= full_hi <= hi``; empty
        (at ``hi``) where the block is not of one run."""
        lo, hi = visited
        hi = xp.maximum(hi, lo)
        full_lo = xp.where(one, xp.clip(full[0], lo, hi), hi)
        return full_lo, xp.where(one, xp.clip(full[1], full_lo, hi), hi)

    return kv_range, q_range, (inside(kv_full, q_one, kv_range),
                               inside(q_full, k_one, q_range))


def block_counts(seg_ids: np.ndarray, bq: int = DEFAULT_BQ,
                 bk: int = DEFAULT_BK,
                 sliding_window: Optional[int] = None):
    """``(visited, causal, unmasked)``: the (query block, key block)
    pairs the causal forward kernel visits over packed rows ``seg_ids
    [..., L]`` (one head, one layer; under the layer's
    ``sliding_window``), the pairs under the row's causal diagonal
    that it would visit if each row were one segment and there were no
    window, and those of the visited that no edge crosses, for which
    the kernels build no mask. On the host, in numpy, by the kernels'
    own rule (:func:`block_ranges`); the engine's counter
    ``flash_kv_blocks_total`` adds these up."""
    seg_ids = np.asarray(seg_ids)
    seg_ids = seg_ids.reshape(-1, seg_ids.shape[-1])
    bq, bk = _blocks(seg_ids.shape[1], bq, bk)
    (lo, hi), _, ((full_lo, full_hi), _) = block_ranges(
        seg_ids, bq, bk, xp=np, sliding_window=sliding_window)
    (_, diag), _, _ = block_ranges(np.ones_like(seg_ids[:1]), bq, bk, xp=np)
    return (int(np.maximum(hi - lo, 0).sum()),
            int(diag.sum()) * seg_ids.shape[0],
            int((full_hi - full_lo).sum()))


# ----------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------
def _loop_pairs(bounds, body, carry, bq, bk, window):
    """``body(j, carry, edges)`` over this grid step's visited pairs
    ``[lo, hi)``, ``bounds`` the four prefetched scalar arrays (``lo,
    hi, full_lo, full_hi``, each ``[B * blocks]``, flat: a 2-D array in
    SMEM pads its last axis to 128 words): ``edges`` False over
    ``[full_lo, full_hi)``, the pairs that attend every (row, column)
    and need no mask (``block_ranges``), True before and after them.
    Three loops, not a branch a pair: by the compiler's static schedule
    a ``cond`` merges its carries over 170 bundles a pair. A window
    narrower than a pair's two blocks leaves no such pair whatever the
    segments, and the loop is the one it was."""
    i = pl.program_id(0) * pl.num_programs(2) + pl.program_id(2)
    lo, hi, full_lo, full_hi = (ref[i] for ref in bounds)
    masked = functools.partial(body, edges=True)
    if window is not None and window < bq + bk:
        return jax.lax.fori_loop(lo, hi, masked, carry)
    carry = jax.lax.fori_loop(lo, full_lo, masked, carry)
    carry = jax.lax.fori_loop(full_lo, full_hi,
                              functools.partial(body, edges=False), carry)
    return jax.lax.fori_loop(full_hi, hi, masked, carry)


def _edge_mask(seg_q, seg_k, q_idx, k_idx, causal, window):
    """The ``[BQ, BK]`` mask of a block pair that an edge may cross:
    segments, padding, causality, the window."""
    mask = (seg_q[:, None] == seg_k[None, :]) & (seg_q[:, None] != 0)
    if causal:
        mask &= q_idx >= k_idx
    if window is not None:
        mask &= q_idx - k_idx < window
    return mask


def _and_selected(mask, block):
    """``mask`` (None: every pair attended) and the selection's int8
    ``[BQ, BK]`` block of the pair, widened."""
    selected = block.astype(jnp.int32) != 0
    return selected if mask is None else mask & selected


def _fwd_kernel(kv_lo_ref, kv_hi_ref, full_lo_ref, full_hi_ref,  # prefetch
                q_ref, k_ref, v_ref, segq_ref, segk_ref,  # inputs
                *rest,  # [the selection,] the outputs o, lse, a scratch
                scale: float, bk: int, causal: bool,
                window: Optional[int] = None):
    *sel_ref, o_ref, lse_ref, acc_ref = rest
    qi = pl.program_id(2)
    bq, hv = q_ref.shape[-2], v_ref.shape[-1]

    q = q_ref[0, 0].astype(jnp.float32) * scale  # [BQ, hd]
    seg_q = segq_ref[0, :, 0]  # [BQ]
    q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    # (the accumulator in VMEM, not a third carry: between two loops a
    # carry of [BQ, hv] is copied from one loop's registers and spill
    # slots to the next's)
    acc_ref[...] = jnp.zeros((bq, hv), jnp.float32)

    def body(j, carry, edges):
        m, l_sum = carry
        k = k_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)  # [BK, hd]
        v = v_ref[0, 0, pl.ds(j * bk, bk), :]  # [BK, hv]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [BQ, BK]
        mask = None
        if edges:
            seg_k = segk_ref[0, 0, pl.ds(j * bk, bk)]  # [BK]
            k_idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = _edge_mask(seg_q, seg_k, q_idx, k_idx, causal, window)
        if sel_ref:  # [BQ, BK] of the selection's rows of this block
            mask = _and_selected(mask, sel_ref[0][0, :, pl.ds(j * bk, bk)])
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l_sum * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new

    # only the key blocks this query block's segments reach
    # (block_ranges): a block left out held no unmasked pair
    m, l_sum = _loop_pairs(
        (kv_lo_ref, kv_hi_ref, full_lo_ref, full_hi_ref), body, (m0, l0),
        bq, bk, window)
    acc = acc_ref[...]
    # Rows that never saw a valid key (all-padding rows) keep
    # m == NEG_INF: their p = exp(NEG_INF - NEG_INF) = 1 garbage must be
    # zeroed here. (Fully-masked *blocks* of otherwise-valid rows
    # self-correct via the alpha rescaling once a valid block arrives.)
    row_valid = m > NEG_INF / 2
    safe_l = jnp.where(l_sum > 0, l_sum, 1.0)
    out = jnp.where(row_valid[:, None], acc / safe_l[:, None], 0.0)
    o_ref[0, 0] = out.astype(o_ref.dtype)
    lse = jnp.where(row_valid, m + jnp.log(safe_l), NEG_INF)
    lse_ref[0, 0] = jnp.broadcast_to(lse[:, None], (bq, LANES))


def _expand_segments(seg_ids):
    """seg [B, L] -> lane-broadcast q view [B, L, LANES] and
    sublane-broadcast kv view [B, SUBLANES, L]."""
    b, l = seg_ids.shape
    segq = jnp.broadcast_to(seg_ids[:, :, None], (b, l, LANES))
    segk = jnp.broadcast_to(seg_ids[:, None, :], (b, SUBLANES, l))
    return segq, segk


def _index_maps(group: int):
    """Index maps of a (batch, q head, block) grid step, each taking
    the prefetched scalars after the grid indices: the step's block
    (``row``) or the whole length (``whole``) of a [B, nq, L, .] array,
    the same of a [B, nkv, L, .] array (``kv_row``, ``kv_whole``), and
    of the segment views: ``seg_row`` of [B, L, LANES], ``seg_whole``
    of either view."""
    return dict(
        row=lambda bi, h, i, *_: (bi, h, i, 0),
        whole=lambda bi, h, i, *_: (bi, h, 0, 0),
        kv_row=lambda bi, h, i, *_: (bi, h // group, i, 0),
        kv_whole=lambda bi, h, i, *_: (bi, h // group, 0, 0),
        seg_row=lambda bi, h, i, *_: (bi, i, 0),
        seg_whole=lambda bi, h, i, *_: (bi, 0, 0))


#: what a kernel may keep in VMEM before the compiler is asked for
#: more: the scoped limit every Mosaic kernel gets by default on a v5e
DEFAULT_SCOPED_VMEM = 16 * 2 ** 20


def _vmem_limit(in_specs, out_specs, out_shape, args):
    """``vmem_limit_bytes`` for a call whose blocks, each held twice
    (Mosaic's pipeline double-buffers every operand), pass the default
    scoped limit: those bytes and a quarter more for the kernel's own
    values. None where they fit the default, which is every shape the
    kernels had before a row of 4096 at heads of 128: such a call's
    program is untouched."""
    held = _held_twice(in_specs, out_specs, out_shape, args)
    return None if held <= DEFAULT_SCOPED_VMEM else int(held * 1.25)


def _held_twice(in_specs, out_specs, out_shape, args) -> int:
    """Bytes of a call's blocks, each held twice (Mosaic's pipeline
    double-buffers every operand)."""
    outs = jax.tree.leaves(out_shape)
    specs = list(in_specs) + list(jax.tree.leaves(
        out_specs, is_leaf=lambda x: isinstance(x, pl.BlockSpec)))
    dtypes = [a.dtype for a in args] + [o.dtype for o in outs]
    return 2 * sum(int(np.prod(spec.block_shape)) * np.dtype(dt).itemsize
                   for spec, dt in zip(specs, dtypes))


def _ranged_call(kernel, name, grid, bounds, in_specs, out_specs,
                 out_shape, *args, scratch=()):
    """``pallas_call`` with a grid step's loop bounds ``(lo, hi,
    full_lo, full_hi)``, each ``[B, grid[2]]``, prefetched as scalars;
    index maps get them after the grid indices. ``scratch``: the
    kernel's VMEM scratch shapes, its last arguments."""
    limit = _vmem_limit(in_specs, out_specs, out_shape, args)
    params = {} if limit is None else dict(
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit))
    return pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(bounds), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=list(scratch)),
        name=name, **params,
    )(*(x.reshape(-1) for x in bounds), *args)


def _flash_fwd(q, k, v, seg_ids, scale, causal, bq, bk, window=None,
               select=None):
    """The forward kernel's two outputs as it writes them: the output
    head-major ``[B, nq, L, hv]`` (the value's width) and the
    lane-broadcast log-sum-exp ``[B, nq, L, LANES]``. ``select``: the
    selection ``[B, L, L]`` int8, one more blocked operand (the rows of
    the step's query block, every column)."""
    b, l, nq, hd = q.shape
    if row_streams(l):
        return _flash_fwd_stream(q, k, v, seg_ids, scale, causal, bq, bk,
                                 window)
    nkv, hv = k.shape[2], v.shape[3]
    group = nq // nkv
    bq, bk = _blocks(l, bq, bk)

    qt = q.transpose(0, 2, 1, 3)  # [B, nq, L, hd]
    kt = k.transpose(0, 2, 1, 3)  # [B, nkv, L, hd]
    vt = v.transpose(0, 2, 1, 3)
    segq, segk = _expand_segments(seg_ids)
    kv_range, _, (kv_full, _) = block_ranges(seg_ids, bq, bk, causal,
                                             sliding_window=window)

    at = _index_maps(group)

    specs, operands, suffix = _selected(
        select, pl.BlockSpec((1, bq, l), at["seg_row"]))
    out, lse = _ranged_call(
        functools.partial(_fwd_kernel, scale=scale, bk=bk, causal=causal,
                          window=window),
        "flash_fwd" + suffix, (b, nq, l // bq), kv_range + kv_full,
        [
            pl.BlockSpec((1, 1, bq, hd), at["row"]),
            pl.BlockSpec((1, 1, l, hd), at["kv_whole"]),
            pl.BlockSpec((1, 1, l, hv), at["kv_whole"]),
            pl.BlockSpec((1, bq, LANES), at["seg_row"]),
            pl.BlockSpec((1, SUBLANES, l), at["seg_whole"]),
        ] + specs,
        (pl.BlockSpec((1, 1, bq, hv), at["row"]),
         pl.BlockSpec((1, 1, bq, LANES), at["row"])),
        (jax.ShapeDtypeStruct((b, nq, l, hv), q.dtype),
         jax.ShapeDtypeStruct((b, nq, l, LANES), jnp.float32)),
        qt, kt, vt, segq, segk, *operands,
        scratch=[pltpu.VMEM((bq, hv), jnp.float32)])
    return out, lse


#: what the name of a kernel that takes a selection ends in
#: (``flash_fwd_sel``, ``flash_bwd_dq_sel``, ``flash_bwd_dkv_sel``):
#: :func:`flash_mask_calls` counts them in a compiled program's text
SELECTED_SUFFIX = "_sel"


def _selected(select, spec):
    """``(specs, args, suffix)``: what a call adds for a selection: its
    block's spec and the operand after the kernel's other inputs, the
    suffix of the kernel's name; nothing where there is none, so that
    such a call is the call it was."""
    if select is None:
        return [], [], ""
    return [spec], [select], SELECTED_SUFFIX


# ----------------------------------------------------------------------
# Backward
# ----------------------------------------------------------------------
def _bwd_dq_kernel(kv_lo_ref, kv_hi_ref, full_lo_ref, full_hi_ref,
                   q_ref, k_ref, v_ref, segq_ref, segk_ref, do_ref,
                   lse_ref, delta_ref, *rest,  # [the selection,] dq
                   scale: float, bk: int, causal: bool,
                   window: Optional[int] = None):
    *sel_ref, dq_ref = rest
    qi = pl.program_id(2)
    bq, hd = q_ref.shape[-2], q_ref.shape[-1]

    q = q_ref[0, 0].astype(jnp.float32) * scale
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, :, 0]
    delta = delta_ref[0, 0, :, 0]
    seg_q = segq_ref[0, :, 0]
    q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    def body(j, dq, edges):
        k = k_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = None
        if edges:
            seg_k = segk_ref[0, 0, pl.ds(j * bk, bk)]
            k_idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = _edge_mask(seg_q, seg_k, q_idx, k_idx, causal, window)
        if sel_ref:
            mask = _and_selected(mask, sel_ref[0][0, :, pl.ds(j * bk, bk)])
        p = jnp.exp(s - lse[:, None])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = _loop_pairs(
        (kv_lo_ref, kv_hi_ref, full_lo_ref, full_hi_ref), body,
        jnp.zeros((bq, hd), jnp.float32), bq, bk, window)
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_lo_ref, q_hi_ref, full_lo_ref, full_hi_ref,
                    q_ref, k_ref, v_ref, segq_ref, segk_ref, do_ref,
                    lse_ref, delta_ref, *rest,  # [the selection,] dk, dv
                    scale: float, bq: int, causal: bool,
                    window: Optional[int] = None):
    *sel_ref, dk_ref, dv_ref = rest
    ki = pl.program_id(2)
    bk, hd, hv = k_ref.shape[-2], k_ref.shape[-1], v_ref.shape[-1]

    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    seg_k = segk_ref[0, 0, pl.ds(ki * bk, bk)]
    k_idx = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    def body(j, carry, edges):
        q = q_ref[0, 0, pl.ds(j * bq, bq), :].astype(jnp.float32) * scale
        do = do_ref[0, 0, pl.ds(j * bq, bq), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(j * bq, bq), 0]
        delta = delta_ref[0, 0, pl.ds(j * bq, bq), 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = None
        if edges:
            seg_q = segq_ref[0, pl.ds(j * bq, bq), 0]
            q_idx = j * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask = _edge_mask(seg_q, seg_k, q_idx, k_idx, causal, window)
        if sel_ref:  # the selection's columns of this block, all rows
            mask = _and_selected(mask, sel_ref[0][0, pl.ds(j * bq, bq), :])
        p = jnp.exp(s - lse[:, None])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dv_ref[0, 0] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dk_ref[0, 0] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return carry

    # Per-q-head partials; summed over each KV group outside
    # (race-free). Accumulated in the float32 output blocks, not in
    # carries: two of [BK, hd] are copied between one loop and the next
    dk_ref[0, 0] = jnp.zeros((bk, hd), jnp.float32)
    dv_ref[0, 0] = jnp.zeros((bk, hv), jnp.float32)
    _loop_pairs((q_lo_ref, q_hi_ref, full_lo_ref, full_hi_ref), body, None,
                bq, bk, window)


def _flash_bwd(res, g, scale, causal, bq, bk, window=None, select=None):
    q, k, v, seg_ids, ot, lse = res
    if row_streams(q.shape[1]):
        return _flash_bwd_stream(res, g, scale, causal, bq, bk, window)
    do = g
    b, l, nq, hd = q.shape
    hv = v.shape[3]
    # the kept log-sum-exp is one number a (row, head); the kernels
    # read it over 128 lanes, as they do delta
    lse = jnp.broadcast_to(lse[..., None], (b, nq, l, LANES))
    nkv = k.shape[2]
    group = nq // nkv
    bq_, bk_ = _blocks(l, bq, bk)

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    segq, segk = _expand_segments(seg_ids)

    delta = (ot.astype(jnp.float32) * dot.astype(jnp.float32)).sum(-1)
    delta = jnp.broadcast_to(delta[..., None], (b, nq, l, LANES))

    kv_range, q_range, (kv_full, q_full) = block_ranges(
        seg_ids, bq_, bk_, causal, sliding_window=window)

    at = _index_maps(group)

    row_specs, row_operands, suffix = _selected(
        select, pl.BlockSpec((1, bq_, l), at["seg_row"]))
    col_specs, col_operands, _ = _selected(select, pl.BlockSpec(
        (1, l, bk_), lambda bi, h, i, *_: (bi, 0, i)))
    dq = _ranged_call(
        functools.partial(_bwd_dq_kernel, scale=scale, bk=bk_,
                          causal=causal, window=window),
        "flash_bwd_dq" + suffix, (b, nq, l // bq_), kv_range + kv_full,
        [
            pl.BlockSpec((1, 1, bq_, hd), at["row"]),
            pl.BlockSpec((1, 1, l, hd), at["kv_whole"]),
            pl.BlockSpec((1, 1, l, hv), at["kv_whole"]),
            pl.BlockSpec((1, bq_, LANES), at["seg_row"]),
            pl.BlockSpec((1, SUBLANES, l), at["seg_whole"]),
            pl.BlockSpec((1, 1, bq_, hv), at["row"]),
            pl.BlockSpec((1, 1, bq_, LANES), at["row"]),
            pl.BlockSpec((1, 1, bq_, LANES), at["row"]),
        ] + row_specs,
        pl.BlockSpec((1, 1, bq_, hd), at["row"]),
        jax.ShapeDtypeStruct(qt.shape, jnp.float32),
        qt, kt, vt, segq, segk, dot, lse, delta, *row_operands)

    dk_partial, dv_partial = _ranged_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, bq=bq_,
                          causal=causal, window=window),
        "flash_bwd_dkv" + suffix, (b, nq, l // bk_), q_range + q_full,
        [
            pl.BlockSpec((1, 1, l, hd), at["whole"]),
            pl.BlockSpec((1, 1, bk_, hd), at["kv_row"]),
            pl.BlockSpec((1, 1, bk_, hv), at["kv_row"]),
            pl.BlockSpec((1, l, LANES), at["seg_whole"]),
            pl.BlockSpec((1, SUBLANES, l), at["seg_whole"]),
            pl.BlockSpec((1, 1, l, hv), at["whole"]),
            pl.BlockSpec((1, 1, l, LANES), at["whole"]),
            pl.BlockSpec((1, 1, l, LANES), at["whole"]),
        ] + col_specs,
        (pl.BlockSpec((1, 1, bk_, hd), at["row"]),
         pl.BlockSpec((1, 1, bk_, hv), at["row"])),
        (jax.ShapeDtypeStruct((b, nq, l, hd), jnp.float32),
         jax.ShapeDtypeStruct((b, nq, l, hv), jnp.float32)),
        qt, kt, vt, segq, segk, dot, lse, delta, *col_operands)

    # Sum q-head partials within each KV group.
    dk = dk_partial.reshape(b, nkv, group, l, hd).sum(2).transpose(0, 2, 1, 3)
    dv = dv_partial.reshape(b, nkv, group, l, hv).sum(2).transpose(0, 2, 1, 3)
    dq_ = dq.transpose(0, 2, 1, 3).astype(q.dtype)
    return (dq_, dk.astype(k.dtype), dv.astype(v.dtype), None)


def flash_fwd_per_bwd(hlo_text: str) -> Optional[float]:
    """The ``flash_fwd`` custom calls of a compiled program over its
    ``flash_bwd_dq`` ones: how often a layer's forward kernel runs for
    each backward of it. 2.0 where a rematerialised block runs the
    kernel again in the backward, 1.0 where the block keeps the
    kernel's residuals (``RESIDUAL_NAMES``), in a scanned stack (one
    pair in the loop bodies) and an unrolled one alike. None for a
    program with no backward kernel. A pure function of the optimized
    HLO text (``Engine.compiled_text``,
    ``hlo_text.device_instructions``)."""
    calls = [name for name, _, opcode in device_instructions(hlo_text)
             if opcode == "custom-call"]
    bwd = sum("flash_bwd_dq" in name for name in calls)
    if not bwd:
        return None
    return sum("flash_fwd" in name for name in calls) / bwd


def flash_mask_calls(hlo_text: str) -> int:
    """The flash custom calls of a compiled program that take a
    SELECTION (a sparse layer's: ``SELECTED_SUFFIX`` in the kernel's
    name): three a sparse layer of a train program whose rematerialised
    blocks keep the forward's residuals, one of a forward-only program,
    0 on the XLA path. The same pure function of the text as
    :func:`flash_fwd_per_bwd`."""
    return sum(
        "flash_" in name and SELECTED_SUFFIX in name
        for name, _, opcode in device_instructions(hlo_text)
        if opcode == "custom-call")


# ----------------------------------------------------------------------
# Rows past FLASH_MAX_LEN: the streamed side fetched a block a grid step
# ----------------------------------------------------------------------
def row_streams(row_len: int) -> bool:
    """Whether a packed row of ``row_len`` tokens takes the kernels
    that stream their blocks (it is past what the whole-row kernels
    hold): the one rule of the kernels' dispatch and of the engine's
    ``flash_stream_rows``."""
    return row_len > FLASH_MAX_LEN


def stream_heads(group: int) -> int:
    """Query heads a grid step of a stream kernel serves: the largest
    divisor of the key/value head's ``group`` up to ``STREAM_HEADS``."""
    return max(n for n in range(1, min(group, STREAM_HEADS) + 1)
               if group % n == 0)


def _stream_steps(l, bq, bk, causal, window):
    """How long the stream kernels' last grid axis is: the most key
    blocks a query block visits and the most query blocks a key block
    visits, in a row of ONE document (every packed row's ranges lie
    inside that row's: a segment starts no earlier and ends no later
    than the row)."""
    (lo, hi), (q_lo, q_hi), _ = block_ranges(
        np.ones((1, l), np.int32), bq, bk, causal, xp=np,
        sliding_window=window)
    return int((hi - lo).max()), int((q_hi - q_lo).max())


def _stream_pair(bounds, pair, window, bq, bk):
    """This grid step's block of the streamed side and its body:
    ``pair(block, edges)`` runs where the step lies inside the resident
    block's range ``[lo, hi)`` (``bounds``: the four prefetched arrays
    of :func:`_loop_pairs`), without a mask over ``[full_lo, full_hi)``
    and with one outside it (two bodies under ``pl.when``: nothing is
    carried in registers between steps, so a branch merges nothing). A
    window narrower than a pair's two blocks leaves no pair without an
    edge, and one body."""
    n = pl.program_id(0) * pl.num_programs(2) + pl.program_id(2)
    lo, hi, full_lo, full_hi = (ref[n] for ref in bounds)
    block = lo + pl.program_id(3)
    active = block < hi
    if window is not None and window < bq + bk:
        pl.when(active)(lambda: pair(block, True))
        return
    interior = (block >= full_lo) & (block < full_hi)
    pl.when(active & interior)(lambda: pair(block, False))
    pl.when(active & jnp.logical_not(interior))(lambda: pair(block, True))


def _pair_mask(seg_q, seg_k, q0, k0, bq, bk, causal, window,
               keys_down=False):
    """The mask of the block pair whose first query row is ``q0`` and
    first key column ``k0``, built once for all the heads of a step:
    ``[BQ, BK]``, or ``[BK, BQ]`` (``keys_down``: the dkv pass's
    transposed scores). ``seg_q`` / ``seg_k``: the blocks' segment
    ids, ``[BQ]`` and ``[BK]``."""
    shape, q_axis = ((bk, bq), 1) if keys_down else ((bq, bk), 0)
    q_idx = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_idx = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    down, across = (seg_k, seg_q) if keys_down else (seg_q, seg_k)
    return _edge_mask(down, across, q_idx, k_idx, causal, window)


def _first_step():
    return pl.program_id(3) == 0


def _last_step():
    return pl.program_id(3) == pl.num_programs(3) - 1


def _across(x, width: int):
    """A lane-broadcast ``[BQ, LANES]`` (one number a row, the same in
    every lane) over ``width`` lanes: tiled where ``width`` is past
    ``LANES`` (key blocks of 512, a value of 128 or 256), its first
    lanes where it is under (a test's blocks of 64, a value of 64).
    Decided by the shapes the kernel is given; no lane of ``x`` is
    moved either way, which is why the stream forward keeps its
    softmax's state in this form."""
    reps = -(-width // LANES)
    wide = jnp.tile(x, (1, reps)) if reps > 1 else x
    return wide if wide.shape[1] == width else wide[:, :width]


def _fwd_stream_kernel(kv_lo_ref, kv_hi_ref, full_lo_ref, full_hi_ref,
                       q_ref, k_ref, v_ref, segq_ref, segk_ref,
                       o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                       scale: float, causal: bool,
                       window: Optional[int] = None):
    """One (query block of ``heads`` heads, key block) pair a grid
    step: the online softmax of :func:`_fwd_kernel`, its running
    maximum, sum and accumulator in VMEM scratch ``[heads, BQ, .]``
    between the steps of a query block.

    The maximum and the sum are one number a row kept over ``LANES``
    lanes, and the body never takes them out of that form: the row
    maximum and the row sum of a pair come out of their reductions with
    the lanes they had (``keepdims``), ``alpha`` is ``[BQ, LANES]``,
    and what scales ``[BQ, BK]`` scores or a ``[BQ, hv]`` accumulator
    is that array tiled or cut to their width (:func:`_across`), which
    moves no lane. As ``[BQ]`` vectors (``m_ref[g, :, 0]``, as the
    whole-row kernel carries them) every (pair, head) paid two
    extractions and four broadcasts through the cross-lane units: 1,088
    bundles of the compiler's schedule against 646 at (7 heads, 128)
    (PERF.md, PR 63). The heads of a step are written out one after
    another, not looped over: a head's chain ``Q K^T -> max -> exp ->
    sum -> P V`` is serial, and in ONE straight line the scheduler
    overlaps a head's products with its neighbours' softmax (547
    bundles a head). ``STREAM_HEADS`` bounds how many that is."""
    qi = pl.program_id(2)
    _, heads, bq, _ = q_ref.shape
    bk, hv = k_ref.shape[2], v_ref.shape[3]

    @pl.when(_first_step())
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def pair(kb, edges):
        mask = _pair_mask(segq_ref[0, :, 0], segk_ref[0, 0, :], qi * bq,
                          kb * bk, bq, bk, causal, window) if edges else None
        k = k_ref[0, 0].astype(jnp.float32)  # [BK, hd]
        v = v_ref[0, 0]  # [BK, hv]

        for g in range(heads):  # written out, not looped over: above
            q = q_ref[0, g].astype(jnp.float32) * scale  # [BQ, hd]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [BQ, BK]
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            m_prev, l_prev = m_ref[g], l_ref[g]  # [BQ, LANES]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - _across(m_new, bk))
            alpha = jnp.exp(m_prev - m_new)
            m_ref[g] = m_new
            l_ref[g] = alpha * l_prev + p.sum(axis=1, keepdims=True)
            acc_ref[g] = (
                acc_ref[g] * _across(alpha, hv) + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))

    _stream_pair((kv_lo_ref, kv_hi_ref, full_lo_ref, full_hi_ref), pair,
                 window, bq, bk)

    @pl.when(_last_step())
    def _():
        def head(g, carry):
            # (rows that saw no key: :func:`_fwd_kernel`'s comment)
            m, l_sum = m_ref[g], l_ref[g]
            row_valid = m > NEG_INF / 2
            safe_l = jnp.where(l_sum > 0, l_sum, 1.0)
            o_ref[0, g] = jnp.where(
                _across(m, hv) > NEG_INF / 2,
                acc_ref[g] / _across(safe_l, hv), 0.0).astype(o_ref.dtype)
            lse_ref[0, g] = jnp.where(row_valid, m + jnp.log(safe_l), NEG_INF)
            return carry

        jax.lax.fori_loop(0, heads, head, 0)


def _bwd_dq_stream_kernel(kv_lo_ref, kv_hi_ref, full_lo_ref, full_hi_ref,
                          q_ref, k_ref, v_ref, segq_ref, segk_ref, do_ref,
                          lse_ref, delta_ref, dq_ref, *,
                          scale: float, causal: bool,
                          window: Optional[int] = None):
    """:func:`_bwd_dq_kernel` a pair a grid step: dQ of the step's
    heads accumulates in its float32 output block, which stays in VMEM
    over the steps of a query block."""
    qi = pl.program_id(2)
    _, heads, bq, _ = q_ref.shape
    bk = k_ref.shape[2]

    @pl.when(_first_step())
    def _():
        dq_ref[...] = jnp.zeros(dq_ref.shape, jnp.float32)

    def pair(kb, edges):
        mask = _pair_mask(segq_ref[0, :, 0], segk_ref[0, 0, :], qi * bq,
                          kb * bk, bq, bk, causal, window) if edges else None
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)

        def head(g, carry):
            q = q_ref[0, g].astype(jnp.float32) * scale
            do = do_ref[0, g].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            p = jnp.exp(s - lse_ref[0, g, :, 0][:, None])
            if mask is not None:
                p = jnp.where(mask, p, 0.0)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0, g, :, 0][:, None])
            dq_ref[0, g] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, heads, head, 0)

    _stream_pair((kv_lo_ref, kv_hi_ref, full_lo_ref, full_hi_ref), pair,
                 window, bq, bk)

    @pl.when(_last_step())
    def _():
        dq_ref[...] = dq_ref[...] * scale


def _bwd_dkv_stream_kernel(q_lo_ref, q_hi_ref, full_lo_ref, full_hi_ref,
                           q_ref, k_ref, v_ref, segq_ref, segk_ref, do_ref,
                           lse_ref, delta_ref, dk_ref, dv_ref, *,
                           scale: float, causal: bool,
                           window: Optional[int] = None):
    """:func:`_bwd_dkv_kernel` a pair a grid step, TRANSPOSED: scores
    ``K Q^T`` [BK, BQ], so that lse and delta are rows ``[1, BQ]`` (one
    float32 a (row, head), as they are kept: a lane-broadcast copy
    would be 128 times the bytes of a block fetched EVERY step) and
    dV = P^T dO, dK = dS^T Q contract over the second axis of their
    first operand. ``segk_ref`` is the LANE view of the key block's
    segments (a column), ``segq_ref`` the sublane view of the query
    block's (a row). dK and dV of the step's heads add up in the
    float32 output blocks."""
    ki = pl.program_id(2)
    _, heads, bq, _ = q_ref.shape
    bk = k_ref.shape[2]

    @pl.when(_first_step())
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)
        dv_ref[...] = jnp.zeros(dv_ref.shape, jnp.float32)

    def pair(qb, edges):
        mask = _pair_mask(segq_ref[0, 0, :], segk_ref[0, :, 0], qb * bq,
                          ki * bk, bq, bk, causal, window,
                          keys_down=True) if edges else None
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)

        def head(g, carry):
            q = q_ref[0, g].astype(jnp.float32) * scale  # [BQ, hd]
            do = do_ref[0, g].astype(jnp.float32)  # [BQ, hv]
            s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            p = jnp.exp(s - lse_ref[0, g])  # [BK, BQ] - [1, BQ]
            if mask is not None:
                p = jnp.where(mask, p, 0.0)
            dv_ref[0, 0] += jax.lax.dot_general(
                p, do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0, g])
            dk_ref[0, 0] += jax.lax.dot_general(
                ds, q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, heads, head, 0)

    _stream_pair((q_lo_ref, q_hi_ref, full_lo_ref, full_hi_ref), pair,
                 window, bq, bk)


def _stream_call(kernel, name, grid, bounds, in_specs, out_specs, out_shape,
                 *args, scratch=()):
    """:func:`_ranged_call` over a grid of four axes, the last one the
    steps of a block's range. What the call holds does not grow with
    the row: its blocks twice (the pipeline's two buffers), its
    scratch, and room for a pair's float32 scores, probabilities and
    mask; the compiler is asked for that much."""
    held = _held_twice(in_specs, out_specs, out_shape, args) + sum(
        int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize for s in scratch)
    return pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(bounds), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=list(scratch)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(DEFAULT_SCOPED_VMEM,
                                 int(held * 1.25) + 8 * 2 ** 20)),
        name=name,
    )(*(x.reshape(-1) for x in bounds), *args)


def _stream_maps(nb: int, n_streamed: int, heads: int, group: int):
    """Index maps of a (batch, head group, resident block, step) grid
    step, each taking the four prefetched ranges after the grid
    indices. ``res``: the resident block of a ``[B, nq, L, .]`` array
    (``heads`` heads from ``h x heads``); ``kv_res``: of a ``[B, nkv,
    L, .]`` one; ``seg_res`` / ``seg_res_row``: of the lane / sublane
    view of the segments. ``step`` and its kin: the same of the
    STREAMED block, ``lo + step`` held inside ``[lo, hi)`` and the
    array: past a block's own range the index repeats, and the pipeline
    fetches nothing."""
    def streamed(bi, i, j, lo, hi):
        n = bi * nb + i
        return jnp.clip(jnp.minimum(lo[n] + j, hi[n] - 1), 0,
                        n_streamed - 1)

    def kv_head(h):
        return (h * heads) // group

    return dict(
        res=lambda bi, h, i, j, *_: (bi, h, i, 0),
        kv_res=lambda bi, h, i, j, *_: (bi, kv_head(h), i, 0),
        seg_res=lambda bi, h, i, j, *_: (bi, i, 0),
        seg_res_row=lambda bi, h, i, j, *_: (bi, 0, i),
        step=lambda bi, h, i, j, lo, hi, *_: (
            bi, h, streamed(bi, i, j, lo, hi), 0),
        step_row=lambda bi, h, i, j, lo, hi, *_: (
            bi, h, 0, streamed(bi, i, j, lo, hi)),
        kv_step=lambda bi, h, i, j, lo, hi, *_: (
            bi, kv_head(h), streamed(bi, i, j, lo, hi), 0),
        seg_step=lambda bi, h, i, j, lo, hi, *_: (
            bi, streamed(bi, i, j, lo, hi), 0),
        seg_step_row=lambda bi, h, i, j, lo, hi, *_: (
            bi, 0, streamed(bi, i, j, lo, hi)))


@jax.named_scope(P.STREAM)
def _flash_fwd_stream(q, k, v, seg_ids, scale, causal, bq, bk, window):
    """:func:`_flash_fwd` for a row past ``FLASH_MAX_LEN``: the same
    two outputs, K and V a block a grid step. (Sub-part ``attn/stream``
    of a device trace: ``obs/parts.py``.)"""
    b, l, nq, hd = q.shape
    nkv, hv = k.shape[2], v.shape[3]
    group = nq // nkv
    heads = stream_heads(group)
    bq, bk = _blocks(l, bq, bk)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    segq, segk = _expand_segments(seg_ids)
    kv_range, _, (kv_full, _) = block_ranges(seg_ids, bq, bk, causal,
                                             sliding_window=window)
    steps, _ = _stream_steps(l, bq, bk, causal, window)
    at = _stream_maps(l // bq, l // bk, heads, group)
    return _stream_call(
        functools.partial(_fwd_stream_kernel, scale=scale, causal=causal,
                          window=window),
        "flash_fwd" + STREAM_SUFFIX, (b, nq // heads, l // bq, steps),
        kv_range + kv_full,
        [
            pl.BlockSpec((1, heads, bq, hd), at["res"]),
            pl.BlockSpec((1, 1, bk, hd), at["kv_step"]),
            pl.BlockSpec((1, 1, bk, hv), at["kv_step"]),
            pl.BlockSpec((1, bq, LANES), at["seg_res"]),
            pl.BlockSpec((1, SUBLANES, bk), at["seg_step_row"]),
        ],
        (pl.BlockSpec((1, heads, bq, hv), at["res"]),
         pl.BlockSpec((1, heads, bq, LANES), at["res"])),
        (jax.ShapeDtypeStruct((b, nq, l, hv), q.dtype),
         jax.ShapeDtypeStruct((b, nq, l, LANES), jnp.float32)),
        qt, kt, vt, segq, segk,
        scratch=[pltpu.VMEM((heads, bq, LANES), jnp.float32),
                 pltpu.VMEM((heads, bq, LANES), jnp.float32),
                 pltpu.VMEM((heads, bq, hv), jnp.float32)])


@jax.named_scope(P.STREAM)
def _flash_bwd_stream(res, g, scale, causal, bq, bk, window):
    """:func:`_flash_bwd` for a row past ``FLASH_MAX_LEN``."""
    q, k, v, seg_ids, ot, lse = res
    b, l, nq, hd = q.shape
    nkv, hv = k.shape[2], v.shape[3]
    group = nq // nkv
    heads = stream_heads(group)
    bq, bk = _blocks(l, bq, bk)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = g.transpose(0, 2, 1, 3)
    segq, segk = _expand_segments(seg_ids)
    delta = (ot.astype(jnp.float32) * dot.astype(jnp.float32)).sum(-1)
    kv_range, q_range, (kv_full, q_full) = block_ranges(
        seg_ids, bq, bk, causal, sliding_window=window)
    kv_steps, q_steps = _stream_steps(l, bq, bk, causal, window)
    kernel = dict(scale=scale, causal=causal, window=window)

    at = _stream_maps(l // bq, l // bk, heads, group)
    dq = _stream_call(
        functools.partial(_bwd_dq_stream_kernel, **kernel),
        "flash_bwd_dq" + STREAM_SUFFIX,
        (b, nq // heads, l // bq, kv_steps), kv_range + kv_full,
        [
            pl.BlockSpec((1, heads, bq, hd), at["res"]),
            pl.BlockSpec((1, 1, bk, hd), at["kv_step"]),
            pl.BlockSpec((1, 1, bk, hv), at["kv_step"]),
            pl.BlockSpec((1, bq, LANES), at["seg_res"]),
            pl.BlockSpec((1, SUBLANES, bk), at["seg_step_row"]),
            pl.BlockSpec((1, heads, bq, hv), at["res"]),
            pl.BlockSpec((1, heads, bq, LANES), at["res"]),
            pl.BlockSpec((1, heads, bq, LANES), at["res"]),
        ],
        pl.BlockSpec((1, heads, bq, hd), at["res"]),
        jax.ShapeDtypeStruct(qt.shape, jnp.float32),
        qt, kt, vt, segq, segk, dot,
        jnp.broadcast_to(lse[..., None], (b, nq, l, LANES)),
        jnp.broadcast_to(delta[..., None], (b, nq, l, LANES)))

    at = _stream_maps(l // bk, l // bq, heads, group)
    dk_partial, dv_partial = _stream_call(
        functools.partial(_bwd_dkv_stream_kernel, **kernel),
        "flash_bwd_dkv" + STREAM_SUFFIX,
        (b, nq // heads, l // bk, q_steps), q_range + q_full,
        [
            pl.BlockSpec((1, heads, bq, hd), at["step"]),
            pl.BlockSpec((1, 1, bk, hd), at["kv_res"]),
            pl.BlockSpec((1, 1, bk, hv), at["kv_res"]),
            pl.BlockSpec((1, SUBLANES, bq), at["seg_step_row"]),
            pl.BlockSpec((1, bk, LANES), at["seg_res"]),
            pl.BlockSpec((1, heads, bq, hv), at["step"]),
            pl.BlockSpec((1, heads, 1, bq), at["step_row"]),
            pl.BlockSpec((1, heads, 1, bq), at["step_row"]),
        ],
        (pl.BlockSpec((1, 1, bk, hd), at["res"]),
         pl.BlockSpec((1, 1, bk, hv), at["res"])),
        (jax.ShapeDtypeStruct((b, nq // heads, l, hd), jnp.float32),
         jax.ShapeDtypeStruct((b, nq // heads, l, hv), jnp.float32)),
        qt, kt, vt, segk, segq, dot, lse[:, :, None, :],
        delta[:, :, None, :])

    # one partial a step's group of heads: summed where a key/value
    # head's query heads took several steps
    per_kv = group // heads
    dk = dk_partial.reshape(b, nkv, per_kv, l, hd).sum(2).transpose(0, 2, 1, 3)
    dv = dv_partial.reshape(b, nkv, per_kv, l, hv).sum(2).transpose(0, 2, 1, 3)
    return (dq.transpose(0, 2, 1, 3).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype), None)


# ----------------------------------------------------------------------
# Public API
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_attention(q, k, v, seg_ids, scale, causal, bq, bk, window):
    out, _ = _flash_fwd(q, k, v, seg_ids, scale, causal, bq, bk, window)
    return out.transpose(0, 2, 1, 3)


def _flash_attention_fwd(q, k, v, seg_ids, scale, causal, bq, bk, window):
    out, lse = _flash_fwd(q, k, v, seg_ids, scale, causal, bq, bk, window)
    # the output as the kernel wrote it, head-major (the backward reads
    # it that way, and keeping it costs no transposed copy), and ONE
    # lane of the [B, nq, L, LANES] log-sum-exp: all hold the same number
    out = checkpoint_name(out, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse[..., 0], RESIDUAL_NAMES[1])
    return out.transpose(0, 2, 1, 3), (q, k, v, seg_ids, out, lse)


_flash_attention.defvjp(
    _flash_attention_fwd,
    lambda scale, causal, bq, bk, window, res, g: _flash_bwd(
        res, g, scale, causal, bq, bk, window))


#: the selection a sparse layer's forward hands its backward, by the
#: name ``models/transformer.py`` gives it: int8 ``[B, L, L]``. Kept by
#: a rematerialised block (``_remat``), the indexer does not run again.
SELECT_RESIDUAL = "flash_select"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_attention_selected(q, k, v, seg_ids, select, scale, causal, bq,
                              bk, window):
    out, _ = _flash_fwd(q, k, v, seg_ids, scale, causal, bq, bk, window,
                        select)
    return out.transpose(0, 2, 1, 3)


def _flash_attention_selected_fwd(q, k, v, seg_ids, select, scale, causal,
                                  bq, bk, window):
    out, lse = _flash_fwd(q, k, v, seg_ids, scale, causal, bq, bk, window,
                          select)
    out = checkpoint_name(out, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse[..., 0], RESIDUAL_NAMES[1])
    return out.transpose(0, 2, 1, 3), (q, k, v, seg_ids, select, out, lse)


def _flash_attention_selected_bwd(scale, causal, bq, bk, window, res, g):
    q, k, v, seg_ids, select, out, lse = res
    return _flash_bwd((q, k, v, seg_ids, out, lse), g, scale, causal, bq,
                      bk, window, select) + (None,)


_flash_attention_selected.defvjp(_flash_attention_selected_fwd,
                                 _flash_attention_selected_bwd)


def flash_attention(q, k, v, seg_ids, *, causal: bool = True,
                    scale: Optional[float] = None,
                    logits_soft_cap: Optional[float] = None,
                    sliding_window: Optional[int] = None,
                    select: Optional[jnp.ndarray] = None,
                    block_q: int = DEFAULT_BQ,
                    block_k: int = DEFAULT_BK) -> jnp.ndarray:
    """Packed-segment flash attention; drop-in for
    `ops.attention.packed_attention_xla` on TPU. ``sliding_window=W``
    (causal only): a query sees the last W tokens of its document.
    ``select`` [B, L, L] (int8, 0 = not attended): a learned selection
    of keys a query, one more operand of the three kernels' masks;
    every head of a query shares it. The block ranges stay those of
    segments, causality and window."""
    if logits_soft_cap is not None:
        raise NotImplementedError(
            "soft cap not yet supported by the flash kernel; use the XLA "
            "path (packed_attention(..., use_flash=False)).")
    l = q.shape[1]
    if l > FLASH_STREAM_MAX_LEN:
        raise ValueError(
            f"flash_attention: packed row of {l} tokens exceeds "
            f"FLASH_STREAM_MAX_LEN={FLASH_STREAM_MAX_LEN}, the longest "
            "row the kernels that stream K and V by block are compiled "
            f"for (rows up to FLASH_MAX_LEN={FLASH_MAX_LEN} hold K and V "
            "whole a head in the chip's VMEM). Split the batch into "
            "more microbatches (the MFC's n_mbs) so packed rows get "
            "shorter, or shard the sequence over a context-parallel "
            "mesh (ring attention).")
    if row_streams(l) and (select is not None
                           or q.shape[-1] != v.shape[-1]):
        raise NotImplementedError(
            f"flash_attention: packed row of {l} tokens exceeds "
            f"FLASH_MAX_LEN={FLASH_MAX_LEN}, and the kernels that "
            "stream K and V by block (flash_*_stream) take no learned "
            "selection (select=, a sparse layer's) and no key wider "
            f"than its value (key {q.shape[-1]}, value {v.shape[-1]}: "
            "latent attention). Split the batch into more microbatches "
            "(the MFC's n_mbs) so packed rows get shorter.")
    if sliding_window is not None and not (causal and sliding_window >= 1):
        raise ValueError(
            f"sliding_window={sliding_window} needs causal attention "
            "and at least one token")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if select is not None:
        return _flash_attention_selected(
            q, k, v, seg_ids.astype(jnp.int32), select.astype(jnp.int8),
            float(scale), causal, block_q, block_k, sliding_window)
    return _flash_attention(q, k, v, seg_ids.astype(jnp.int32),
                            float(scale), causal, block_q, block_k,
                            sliding_window)
