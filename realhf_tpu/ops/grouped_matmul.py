"""The experts' grouped products as Pallas kernels of the repo's own.

``grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G]) -> [M,
N]`` is the call ``jax.lax.ragged_dot`` has: the rows of ``lhs`` lie
sorted by group, group ``g`` takes the next ``group_sizes[g]`` of them
and multiplies them by ``rhs[g]``. What differs is **the contract for
the rows no group covers** (``ops/moe.py:_ragged_share`` gathers a
static number of rows of which the held experts' pairs are the first):

- a row at or past ``group_sizes.sum()`` comes back EXACTLY ZERO in the
  result and in the gradient of ``lhs``, and adds nothing to the
  gradient of ``rhs``, whatever lies in those rows of ``lhs`` or of the
  cotangent (NaN included);
- and costs no product: the grid covers only the row tiles a group
  covers. XLA:TPU's own grouped matmul skips those tiles too but
  leaves them UNWRITTEN, in the backward's products as well (PERF.md,
  PR 31), so with it every row had to lie in a group and a share
  multiplied its zeroed half at full price.

Three kernels under one ``jax.custom_vjp`` (the design of
``jax.experimental.pallas.ops.tpu.megablox``: a group's metadata by
scalar prefetch, a grid over the visits of (row tile, group) pairs):

- ``gmm``: ``out[rows of g] = lhs[rows of g] @ rhs[g]``. The whole
  contraction lies in one block (no k loop, no accumulator); the grid
  is (column tiles, visits) with the visits inside, so a group's
  ``[K, tn]`` block of weights is fetched once while consecutive row
  tiles stay in the group. A row tile that two groups share is visited
  by each, and each stores its own rows alone. Inside a visit the
  product goes over the block's columns a chunk at a time
  (``_column_chunk``).
- ``gmm_t``: the same with ``rhs`` read transposed, ``d lhs = d out @
  rhs[g]^T``, no transposed copy of the weights.
- ``tgmm``: ``d rhs[g] = lhs[rows of g]^T @ d out[rows of g]``,
  contracted over a group's row tiles inside the kernel (float32
  accumulator in VMEM, no transposed copy of ``lhs``), zero for a
  group without rows.

The rows past the last group are never visited; one ``jnp.where`` on
the result, which XLA fuses into whatever reads it, makes them zero
(the tiles' garbage may be NaN: a select, not a product). Operands as
they come (bf16 in the benchmark's cells, float32 in the tests),
float32 accumulation, results in the operands' dtype, as
``lax.ragged_dot`` gives them. Tiles are chosen here from what the
call can see (``_tiles``): there is no option.
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from realhf_tpu.ops.flash_attention import DEFAULT_SCOPED_VMEM

#: names of the three kernels in a compiled program's text and in a
#: device trace (``obs/parts.py``, the engine's ``moe_gmm_calls``)
GMM, GMM_T, TGMM = "gmm", "gmm_t", "tgmm"
#: rows of a row tile (where the call has more; a smaller call is one
#: tile): 256 rows of 2048 in bf16 are 1 MB, fetched while the tile
#: before is multiplied
ROW_TILE = 256
#: columns of the weight block a product inside a kernel takes at most
#: (``_column_chunk``)
COLUMN_CHUNK = 512
#: what the blocks of one call may take of VMEM, each counted twice
#: (Mosaic double-buffers every operand), with the float32 product of
#: a visit: a group's weights WHOLE (2048 x 1536 in bf16: 6.3 MB,
#: twice) and the float32 accumulator of ``tgmm`` beside them fit (42
#: MB); wider experts get column tiles
VMEM_BUDGET = 56 * 2 ** 20


def _visits(group_sizes: jnp.ndarray, m: int, tm: int, empty: bool):
    """The grid's second axis: one VISIT for every (group, row tile)
    pair in which the group has rows, in the rows' order. Returns
    ``(group_ids [V], tile_ids [V], offsets [G + 1], n)``: the group
    and row tile of visit ``v``, the first row of each group (and the
    end of the last), and how many of the ``V = tiles + G - 1`` slots
    are visits. ``empty``: a group without rows gets one visit all the
    same (``tgmm`` has its block of the result to zero)."""
    g = group_sizes.shape[0]
    tiles = m // tm
    ends = jnp.minimum(jnp.cumsum(group_sizes.astype(jnp.int32)), m)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    has = ends > starts
    first = jnp.minimum(starts // tm, tiles - 1)
    spans = jnp.where(has, (ends - 1) // tm - first + 1, 1 if empty else 0)
    upto = jnp.cumsum(spans)
    slots = tiles + g - 1
    at = jnp.arange(slots, dtype=jnp.int32)
    group_ids = jnp.minimum(
        (upto[None, :] <= at[:, None]).sum(axis=1).astype(jnp.int32), g - 1)
    tile_ids = jnp.clip(first[group_ids] + at - (upto - spans)[group_ids],
                        0, tiles - 1)
    return group_ids, tile_ids, offsets, upto[-1]


def _row_mask(offsets, group, tile, tm):
    """``[tm, 1]``: which rows of the row tile lie in the group."""
    rows = tile * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return jnp.logical_and(rows >= offsets[group],
                           rows < offsets[group + 1])


def _column_chunk(tn: int) -> int:
    """Columns of a weight block one product inside a kernel takes:
    the widest multiple of 128 lanes up to ``COLUMN_CHUNK`` that
    divides ``tn`` (1408 = 11 x 128 goes by 128s), the whole block
    where ``tn`` is no multiple of 128. The block lies whole in VMEM
    either way; a kernel that loops over chunks of it is a fraction of
    the code of one product over all of it, and compiles in a
    fraction of the time (``setup_s``: PERF.md, PR 38)."""
    if tn % 128:
        return tn
    return max(c for c in range(128, COLUMN_CHUNK + 1, 128) if tn % c == 0)


def _over_columns(tn: int, chunk_fn):
    """``chunk_fn(columns)`` for every chunk of ``tn`` columns, as a
    loop inside the kernel."""
    cn = _column_chunk(tn)
    if cn == tn:
        chunk_fn(slice(None))
        return

    def turn(j, carry):
        chunk_fn(pl.ds(pl.multiple_of(j * cn, cn), cn))
        return carry

    jax.lax.fori_loop(0, tn // cn, turn, 0)


def _params(held_bytes: int, semantics: Tuple[str, ...], itemsize: int):
    """Compiler parameters of a call whose blocks and values take
    ``held_bytes`` of VMEM (``_tiles``): where those and a quarter more
    pass the default scoped limit the call asks for them, as
    ``ops/flash_attention.py:_vmem_limit`` does. (The quarter is no
    luxury: inside Moonlight's whole train step ``gmm_t`` at blocks of
    15.1 MB and a product of 1.4 was refused for 96 KB over the
    default 16 MiB, where the microbatch's program alone compiled:
    PERF.md, PR 38.) Float32 operands (no benchmark cell has them: the
    tests and ``chip_check.py``'s float32 rows, whose products run at
    the highest precision) get half again: with the delta scan's
    kernels in the same program Kimi-Linear's float32 forward was
    refused for 1.83 MiB over the quarter at a held expert's
    ``[2304, 1024]`` (what XLA stacks around a call differs by
    program: PERF.md, PR 42)."""
    limit = held_bytes * 3 // 2 if itemsize == 4 else held_bytes * 5 // 4
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=limit if limit > DEFAULT_SCOPED_VMEM else None)


def _tiles(m: int, k: int, n: int, itemsize: int, acc: bool
           ) -> Tuple[int, int, int, int]:
    """``(tm, tk, tn, bytes held)`` of a call over ``m`` rows of
    ``itemsize`` bytes an element whose weight blocks are ``[tk, tn]``
    of ``[k, n]``: the row tile, and the largest weight block the
    budget holds: whole where it fits, else columns then rows of it
    halved (in multiples of 128 lanes). ``acc``: a
    float32 accumulator of the block's shape stands beside it and the
    block is the RESULT (``tgmm``); otherwise ``tk`` is the whole
    contraction and never cut."""
    tm = m if m <= ROW_TILE else ROW_TILE
    tk, tn = k, n

    def held():
        # blocks twice (Mosaic double-buffers them), the float32
        # product of a visit once
        if acc:  # lhs [tm, tk], d out [tm, tn] -> out [tk, tn]
            return 2 * itemsize * tm * (tk + tn) \
                + (2 * itemsize + 4 + 4) * tk * tn
        # lhs [tm, tk], weights [tk, tn] -> out [tm, tn]
        return 2 * itemsize * (tm * tk + tk * tn) \
            + (2 * itemsize + 4) * tm * tn

    def halved(x):
        return max(128, pl.cdiv(x, 256) * 128)

    while held() > VMEM_BUDGET and tn > 128:
        tn = halved(tn)
    while acc and held() > VMEM_BUDGET and tk > 128:
        tk = halved(tk)
    return tm, tk, tn, held()


def _padded_rows(m: int) -> int:
    """Rows of the arrays the kernels see: a call of more than one row
    tile whose rows do not divide into tiles is padded up (the pad lies
    past every group)."""
    return m if m <= ROW_TILE else pl.cdiv(m, ROW_TILE) * ROW_TILE


def _pad_rows(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    return x if x.shape[0] == rows else jnp.pad(
        x, ((0, rows - x.shape[0]), (0, 0)))


def _gmm(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray,
         transpose_rhs: bool) -> jnp.ndarray:
    """``lhs [M, K] x rhs [G, K, N] -> [M, N]`` by group
    (``transpose_rhs``: ``rhs [G, N, K]``, read as it lies), the rows
    past the last group zero."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    rows = _padded_rows(m)
    size = lhs.dtype.itemsize
    tm, _, tn, held = _tiles(rows, k, n, size, acc=False)
    group_ids, tile_ids, offsets, visits = _visits(group_sizes, rows, tm,
                                                   empty=False)
    dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def kernel(group_ids, tile_ids, offsets, lhs_ref, rhs_ref, out_ref):
        v = pl.program_id(1)
        mask = _row_mask(offsets, group_ids[v], tile_ids[v], tm)

        def columns(cols):
            out = jax.lax.dot_general(
                lhs_ref[...],
                rhs_ref[cols, :] if transpose_rhs else rhs_ref[:, cols],
                dims, preferred_element_type=jnp.float32)
            # the tile's other rows are another visit's, or no one's
            out_ref[:, cols] = jnp.where(mask, out.astype(out_ref.dtype),
                                         out_ref[:, cols])

        _over_columns(tn, columns)

    def weights(j, v, group_ids, tile_ids, offsets):
        return (group_ids[v], j, 0) if transpose_rhs \
            else (group_ids[v], 0, j)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), visits),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, g, t, o: (t[v], 0)),
                pl.BlockSpec((None, tn, k) if transpose_rhs
                             else (None, k, tn), weights)],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, g, t, o: (t[v], j))),
        compiler_params=_params(held, ("parallel", "arbitrary"), size),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=size * (m * k + rhs.size + m * n)),
        name=GMM_T if transpose_rhs else GMM,
    )(group_ids, tile_ids, offsets, _pad_rows(lhs, rows), rhs)
    covered = jnp.arange(m)[:, None] < offsets[-1]
    return jnp.where(covered, out[:m], jnp.zeros((), out.dtype))


def _tgmm(lhs: jnp.ndarray, dout: jnp.ndarray, group_sizes: jnp.ndarray
          ) -> jnp.ndarray:
    """``out[g] = lhs[rows of g]^T @ dout[rows of g]``: ``[M, K]`` and
    ``[M, N]`` -> ``[G, K, N]``, zero for a group without rows; the
    rows past the last group add nothing."""
    m, k = lhs.shape
    n = dout.shape[1]
    groups = group_sizes.shape[0]
    rows = _padded_rows(m)
    size = lhs.dtype.itemsize
    tm, tk, tn, held = _tiles(rows, k, n, size, acc=True)
    group_ids, tile_ids, offsets, visits = _visits(group_sizes, rows, tm,
                                                   empty=True)
    dims = (((0,), (0,)), ((), ()))

    def kernel(group_ids, tile_ids, offsets, lhs_ref, dout_ref, out_ref,
               acc_ref):
        v = pl.program_id(2)
        group = group_ids[v]
        before = group_ids[jnp.maximum(v - 1, 0)]
        last = pl.num_programs(2) - 1
        after = group_ids[jnp.minimum(v + 1, last)]
        mask = _row_mask(offsets, group, tile_ids[v], tm)

        @pl.when(jnp.logical_or(v == 0, before != group))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(offsets[group + 1] > offsets[group])
        def _():  # BOTH sides masked: a NaN times zero is a NaN
            lhs = jnp.where(mask, lhs_ref[...], 0)

            def columns(cols):
                acc_ref[:, cols] += jax.lax.dot_general(
                    lhs, jnp.where(mask, dout_ref[:, cols], 0), dims,
                    preferred_element_type=jnp.float32)

            _over_columns(tn, columns)

        @pl.when(jnp.logical_or(v == last, after != group))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), pl.cdiv(k, tk), visits),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, i, v, g, t, o: (t[v], i)),
                pl.BlockSpec((tm, tn),
                             lambda j, i, v, g, t, o: (t[v], j))],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda j, i, v, g, t, o: (g[v], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=_params(
            held, ("parallel", "arbitrary", "arbitrary"), size),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=size * (m * k + m * n + groups * k * n)),
        name=TGMM,
    )(group_ids, tile_ids, offsets, _pad_rows(lhs, rows),
      _pad_rows(dout, rows))


@functools.lru_cache(maxsize=None)
def _jitted(fn, static, *tiles):
    """``fn`` under a ``jax.jit`` of its own (``static``: its static
    argument names): the calls of one shape in a program (a layer's
    gate and up projections, every sparse layer, both branches of a
    share) are traced and lowered ONCE and called, not 96 times over
    (5 s of a Moonlight step's lowering). ``tiles``: the constants the
    trace reads, so that a trace made under other tiles is never taken
    for this one."""
    return jax.jit(fn, static_argnames=static)


def _call(fn, *args, **static):
    return _jitted(fn, tuple(static), ROW_TILE, COLUMN_CHUNK,
                   VMEM_BUDGET)(*args, **static)


@jax.custom_vjp
def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray) -> jnp.ndarray:
    """``lhs [M, K]``, sorted by group, times ``rhs [G, K, N]`` by
    group: ``[M, N]`` in ``lhs``'s dtype. ``group_sizes [G]`` (int32)
    may add up to LESS than ``M``: the rows past their sum come back
    zero, here and in ``lhs``'s gradient, add nothing to ``rhs``'s and
    are not multiplied (the module's docstring)."""
    return _call(_gmm, lhs, rhs, group_sizes, transpose_rhs=False)


def _fwd(lhs, rhs, group_sizes):
    return grouped_matmul(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _bwd(residuals, dout):
    lhs, rhs, group_sizes = residuals
    dout = dout.astype(lhs.dtype)
    return (_call(_gmm, dout, rhs, group_sizes, transpose_rhs=True),
            _call(_tgmm, lhs, dout, group_sizes).astype(rhs.dtype),
            np.zeros(group_sizes.shape, jax.dtypes.float0))


grouped_matmul.defvjp(_fwd, _bwd)
