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
are what they are without this paragraph. The window takes nothing off
VMEM: K and V are still whole a head, so ``FLASH_MAX_LEN`` stands; it
takes blocks off the loops (a row of 4096 at W = 512 visits 30 of its
72 causal block pairs).

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

What is still whole in VMEM. K and V (forward, dq) and Q, dO, lse,
delta (dkv) are kept whole per (batch, head) whatever the ranges say,
which bounds L: ``FLASH_MAX_LEN`` below is what the v5e compiler
accepts for forward AND backward at the head sizes of the supported
families; ``flash_attention`` raises above it. Longer rows need more
microbatches (shorter packed rows) or a context-parallel mesh (ring
attention); streaming KV by DMA is future work.

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

from realhf_tpu.ops.hlo_text import device_instructions

DEFAULT_BQ = 256
DEFAULT_BK = 512
#: Longest packed row the kernel takes. Asked of the v5e compiler
#: (libtpu 0.0.34, bf16, tests/ops/test_chip_compile.py): the backward
#: compiles to L = 5120 at (14 q, 2 kv, hd 64) and to 6144 at
#: (32, 8, 128) and runs out of VMEM one kilotoken above either; the
#: forward alone compiles to 8192 and is refused at 16384. A sliding
#: window changes none of this (K and V stay whole a head in VMEM);
#: the windowed backward at (64, 8, 128) x 4096 compiles too, as does
#: the backward at a key's width of 192 and a value's of 128 (16, 16
#: heads) x 4096.
FLASH_MAX_LEN = 4096
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
    outs = jax.tree.leaves(out_shape)
    specs = list(in_specs) + list(jax.tree.leaves(
        out_specs, is_leaf=lambda x: isinstance(x, pl.BlockSpec)))
    dtypes = [a.dtype for a in args] + [o.dtype for o in outs]
    held = 2 * sum(int(np.prod(spec.block_shape)) * np.dtype(dt).itemsize
                   for spec, dt in zip(specs, dtypes))
    return None if held <= DEFAULT_SCOPED_VMEM else int(held * 1.25)


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
    if q.shape[1] > FLASH_MAX_LEN:
        raise ValueError(
            f"flash_attention: packed row of {q.shape[1]} tokens exceeds "
            f"FLASH_MAX_LEN={FLASH_MAX_LEN}, the longest row whose "
            "forward and backward kernels hold whole in the chip's "
            f"VMEM at a key's width of {q.shape[-1]}. Split "
            "the batch into more microbatches (the MFC's n_mbs) so "
            "packed rows get shorter, or shard the sequence over a "
            "context-parallel mesh (ring attention).")
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
