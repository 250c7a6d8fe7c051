"""Pallas flash-decode attention against a padded KV cache (TPU).

Replaces the plain-XLA ``ops.attention.decode_attention`` on the hot
decode path (reference ``flash_attn_with_kvcache``, attn.py:238): one
query token per stream attends over the whole cache with a tiled
online softmax, never materializing the ``[B, nq, S]`` score tensor.
Decode is HBM-bandwidth bound -- the kernel makes a single pass over
K/V per step, with all query heads of a KV group (GQA) sharing each
loaded block.

Layout contract (HEAD-MAJOR, so no transpose sits on the hot path):
q [B, nq, hd], the FULL stacked caches [nl, B, nkv, S, hd], keep-mask
[B, S] (validity AND the sliding window -- precomputed in XLA, it is
O(B*S) elementwise).

One kernel, ``flash_decode_attention_stacked``: it takes the whole
stack plus a layer index (a Python int of an unrolled layer loop or
the traced index of a `lax.scan` over layers), delivered through
scalar prefetch so the index map picks layer ``l``'s rows and only
they are streamed from HBM; every layer shares one Mosaic body. The
kernel, not a slice, is the cache's consumer inside the decode loop:
its operand constraint keeps the loop's carry row-major, so the
token's write is ``B * nkv`` row writes in place. Slicing a layer out
first (``k_all[l]``) is NOT a free view on the chip: it was a slice
and a transposing copy of the layer's cache for every layer and
token, and it left XLA free to lay the stack out slot-minor, which
made the one-token write a strided write into every tile (PERF.md,
PR 30). ``decode_layer_copies`` counts such copies in a compiled
program.

The query-group axis is padded up to the fp32 sublane count (8); hd
should be a multiple of 128 on real TPUs. S is a multiple of the K
block (caches are allocated so: ``transformer.round_cache_len``).
"""

import functools
import re
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from realhf_tpu.base import logging
from realhf_tpu.ops.hlo_text import device_instructions

logger = logging.getLogger("decode_attention")

NEG_INF = -2.0 ** 30
SUBLANES = 8


#: the kernel's name: a device trace's operation (``decode_attn_stacked.N``)
#: and the mark of it in a compiled program's text
KERNEL_NAME = "decode_attn_stacked"

#: K-block rows per kernel step (a multiple of 128: lane tiling).
DEFAULT_BK = 512


def _decode_body(q, k_at, v_at, keep_at, o_ref, *, scale, bk, s,
                 m_ref=None, l_ref=None):
    """Shared online-softmax body over one (stream, kv-head) cell.
    ``q``: loaded [gp, hd]; ``k_at(j)/v_at(j)``: [bk, hd] block loads;
    ``keep_at(j)``: [bk] int32; ``o_ref``: the output ref.
    ``m_ref``/``l_ref`` (optional): per-row softmax max / normalizer
    outputs -- the partial stats a KV-sequence-split caller combines
    across shards (sharded_decode_attention_seqsplit)."""
    gp, hd = q.shape
    q = q.astype(jnp.float32) * scale

    def body(j, carry):
        m, l_sum, acc = carry
        k = k_at(j).astype(jnp.float32)
        v = v_at(j)
        keep = keep_at(j)  # [bk] int32

        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [gp, bk]
        sc = jnp.where((keep > 0)[None, :], sc, NEG_INF)

        m_new = jnp.maximum(m, sc.max(axis=1))
        p = jnp.exp(sc - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l_sum * alpha + p.sum(axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((gp,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((gp,), jnp.float32)
    acc0 = jnp.zeros((gp, hd), jnp.float32)
    m, l_sum, acc = jax.lax.fori_loop(0, s // bk, body, (m0, l0, acc0))

    row_valid = m > NEG_INF / 2  # streams whose cache is still empty
    safe_l = jnp.where(l_sum > 0, l_sum, 1.0)
    out = jnp.where(row_valid[:, None], acc / safe_l[:, None], 0.0)
    o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)
    if m_ref is not None:
        m_ref[...] = m.reshape(m_ref.shape)
        l_ref[...] = l_sum.reshape(l_ref.shape)


def _stacked_kernel(lidx_ref, q_ref, k_ref, v_ref, keep_ref, o_ref,
                    *stat_refs, scale, bk):
    # lidx_ref is the scalar-prefetch operand; the index_map already
    # consumed it to select the layer block. stat_refs: the (m, l)
    # outputs of a return_stats call, else empty.
    m_ref, l_ref = stat_refs or (None, None)
    s = k_ref.shape[-2]
    _decode_body(
        q_ref[0, 0],
        lambda j: k_ref[0, 0, 0, pl.ds(j * bk, bk), :],
        lambda j: v_ref[0, 0, 0, pl.ds(j * bk, bk), :],
        lambda j: keep_ref[0, 0, pl.ds(j * bk, bk)],
        o_ref, scale=scale, bk=bk, s=s, m_ref=m_ref, l_ref=l_ref)


def _trim_stats(res, return_stats, b, nq, group):
    """Strip the padded query-group rows from a decode pallas_call's
    result(s) and flatten heads back to [B, nq, ...]."""
    if return_stats:
        out, m, l = res
        hd = out.shape[-1]
        return (out[:, :, :group, :].reshape(b, nq, hd),
                m[:, :, :group].reshape(b, nq),
                l[:, :, :group].reshape(b, nq))
    hd = res.shape[-1]
    return res[:, :, :group, :].reshape(b, nq, hd)


#: candidate K-blocks, descending (multiples of 128 for lane tiling)
_BK_LADDER = (4096, 2048, 1024, 512, 384, 256, 128)


def _pick_bk(s: int, block_k: int = DEFAULT_BK) -> int:
    """Largest K-block <= block_k that divides s (cache lengths are
    allocated as multiples of 128, so this succeeds on the generation
    paths; the kernel refuses a length nothing divides). The ladder
    spans past 512 so a raised DEFAULT_BK actually takes effect."""
    if s <= block_k:
        return s
    for bk in _BK_LADDER:
        if bk <= block_k and s % bk == 0:
            return bk
    return block_k


def _window_keep(valid_mask, sliding_window, slot):
    keep = valid_mask
    if sliding_window is not None:
        assert slot is not None, "sliding_window decode needs slot indices"
        s = valid_mask.shape[1]
        idx = jnp.arange(s, dtype=jnp.int32)[None, :]
        keep = keep & ((slot[:, None] - idx) < sliding_window)
    return keep.astype(jnp.int32)


# public alias: seqsplit callers precompute the keep mask GLOBALLY
# (window positions are global; shards see local indices)
window_keep = _window_keep


def _pad_group(q, nkv, group, gp):
    b, _, hd = q.shape
    qg = q.reshape(b, nkv, group, hd)
    if gp != group:
        qg = jnp.concatenate(
            [qg, jnp.zeros((b, nkv, gp - group, hd), q.dtype)], axis=2)
    return qg


def sharded_decode_attention(fn, mesh, q, caches, valid_mask, slot,
                             layer_index):
    """Partition the decode-attention kernel over a dp x tp mesh with
    `shard_map` (manual over the data/model axes): a bare pallas_call
    under GSPMD has no partitioning rule, so without this wrapper XLA
    would gather the full KV cache onto every device -- fatal for the
    tp16 70B decode story (docs/distributed.md).
    ``fn(q, k, v, valid, slot, lidx)`` runs on LOCAL shards of the
    stacked caches: B over "data", heads over "model" (GQA grouping
    survives because nq and nkv shard together); the layer axis and
    ``layer_index`` (a scalar array) are replicated.

    Callers must check `decode_shardable` (B % dp, nq % tp, nkv % tp)
    and fall back to the XLA path otherwise."""
    from functools import partial as _partial

    from jax.sharding import PartitionSpec as P

    from realhf_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    kv_spec = P(None, DATA_AXIS, MODEL_AXIS, None, None)
    slot_spec = P(DATA_AXIS) if slot is not None else P()
    has_slot = slot is not None
    # decode requires pipe=ctx=1, so go FULLY manual (partial-auto
    # meshes cannot host the interpret-mode kernel's callbacks)
    axis_names = {a for a in mesh.axis_names}

    @_partial(jax.shard_map, mesh=mesh,
              axis_names=axis_names,
              in_specs=(P(DATA_AXIS, MODEL_AXIS, None), kv_spec,
                        kv_spec, P(DATA_AXIS, None), slot_spec, P()),
              out_specs=P(DATA_AXIS, MODEL_AXIS, None),
              # pallas_call outputs carry no varying-axes metadata
              check_vma=False)
    def run(q_l, k_l, v_l, valid_l, slot_l, lidx):
        return fn(q_l, k_l, v_l, valid_l,
                  slot_l if has_slot else None, lidx)

    k_all, v_all = caches
    return run(q, k_all, v_all, valid_mask,
               slot if has_slot else jnp.zeros((), jnp.int32),
               layer_index)


def mesh_nontrivial(mesh) -> bool:
    """True when the mesh actually shards over data/model (the pallas
    kernels then need the shard_map wrappers)."""
    if mesh is None:
        return False
    from realhf_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
    return (mesh.shape.get(DATA_AXIS, 1)
            * mesh.shape.get(MODEL_AXIS, 1)) > 1


_warned_unshardable = set()


def decode_shardable(mesh, b: int, nq: int, nkv: int) -> bool:
    """Whether the pallas decode kernels can partition HEAD-wise on
    this mesh (B over "data", q/kv heads over "model")."""
    if mesh is None:
        return True
    from realhf_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
    dp = mesh.shape.get(DATA_AXIS, 1)
    tp = mesh.shape.get(MODEL_AXIS, 1)
    if dp == 1 and tp == 1:
        return True
    return b % dp == 0 and nq % tp == 0 and nkv % tp == 0


def choose_decode_partitioning(mesh, b: int, nq: int, nkv: int,
                               s: int) -> Optional[str]:
    """How the pallas decode kernel partitions on this mesh:
    ``"heads"`` (B over "data", heads over "model" -- the fast path),
    ``"seq"`` (KV sequence over "model" with a cross-shard flash
    combine -- GQA at tp > n_kv_heads, e.g. LLaMA-70B's 8 kv-heads at
    tp16), or ``None`` (nothing divides: GSPMD einsum fallback, with a
    one-time warning because the throughput loss is real)."""
    if mesh is None:
        return "heads"
    from realhf_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
    dp = mesh.shape.get(DATA_AXIS, 1)
    tp = mesh.shape.get(MODEL_AXIS, 1)
    if dp == 1 and tp == 1:
        return "heads"
    if decode_shardable(mesh, b, nq, nkv):
        return "heads"
    s_local = s // tp
    # the LOCAL shard length must satisfy the kernels' K-block
    # constraint (stacked kernel asserts s % bk == 0; _pick_bk finds a
    # divisor when s_local <= block_k or s_local % 128 == 0)
    if (b % dp == 0 and s % tp == 0
            and (s_local <= DEFAULT_BK or s_local % 128 == 0)):
        return "seq"
    key = (dp, tp, b, nq, nkv, s)
    if key not in _warned_unshardable:
        _warned_unshardable.add(key)
        logger.warning(
            "Pallas decode kernel cannot partition on this mesh "
            "(dp=%d tp=%d, batch=%d, nq=%d, nkv=%d, cache_len=%d: "
            "neither heads nor KV sequence divide evenly); decoding "
            "via the GSPMD einsum path instead -- expect lower decode "
            "throughput.", dp, tp, b, nq, nkv, s)
    return None


def run_decode_kernels(mesh, q, caches, valid_mask, slot, layer_index,
                       *, scale=None, sliding_window=None):
    """Single dispatcher for one decode-attention call onto the Pallas
    kernel, against the FULL stacked caches at ``layer_index`` (a
    Python int or a traced scalar): bare kernel on trivial meshes,
    head-sharded or KV-sequence-split shard_map per
    ``choose_decode_partitioning``. Returns ``None`` when no kernel
    partitioning applies -- the caller then takes its GSPMD/XLA
    fallback. Traced scales (deep scale_attn_by_inverse_layer_idx
    models) fold into q here, since the kernel needs a python-static
    scale."""
    if not (scale is None or isinstance(scale, (int, float))):
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
        scale = 1.0
    b, nq = q.shape[0], q.shape[1]
    nkv, s = caches[0].shape[2], caches[0].shape[3]
    layer_index = jnp.asarray(layer_index, jnp.int32)

    def plain(q_, k_, v_, valid_, slot_, lidx):
        return flash_decode_attention_stacked(
            q_, k_, v_, valid_, lidx, scale=scale,
            sliding_window=sliding_window, slot=slot_)

    def stats(q_, k_, v_, keep_, lidx):
        return flash_decode_attention_stacked(
            q_, k_, v_, keep_.astype(bool), lidx, scale=scale,
            return_stats=True)

    if not mesh_nontrivial(mesh):
        return plain(q, caches[0], caches[1], valid_mask, slot,
                     layer_index)
    part = choose_decode_partitioning(mesh, b, nq, nkv, s)
    if part == "heads":
        return sharded_decode_attention(
            plain, mesh, q, caches, valid_mask, slot, layer_index)
    if part == "seq":
        keep = window_keep(valid_mask, sliding_window, slot)
        return sharded_decode_attention_seqsplit(
            stats, mesh, q, caches, keep, layer_index)
    return None


def sharded_decode_attention_seqsplit(fn_stats, mesh, q, caches, keep,
                                      layer_index):
    """KV-SEQUENCE-split decode for GQA at tp > n_kv_heads (the
    LLaMA-70B tp16 case, docs/distributed.md): heads cannot shard
    16-ways, so each "model" shard instead holds a SLICE OF THE CACHE
    SEQUENCE, runs the flash kernel over its slice with partial
    softmax stats, and the shards combine with the standard
    flash-attention merge (``out = sum_i w_i out_i``,
    ``w_i = l_i exp(m_i - m)``) via psum over "model". Attention
    FLOPs and KV bytes split tp-ways evenly regardless of head
    counts; q (tiny at decode, [B, nq, hd]) is replicated over
    "model".

    ``fn_stats(q, k, v, keep, lidx) -> (out, m, l)`` runs on LOCAL
    shards and must apply any sliding window itself -- ``keep`` here
    is the PRE-COMPUTED global keep mask ([B, S] int32), since window
    positions are global while each shard sees local indices."""
    from functools import partial as _partial

    from jax.sharding import PartitionSpec as P

    from realhf_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    kv_spec = P(None, DATA_AXIS, None, MODEL_AXIS, None)
    axis_names = {a for a in mesh.axis_names}

    @_partial(jax.shard_map, mesh=mesh,
              axis_names=axis_names,
              in_specs=(P(DATA_AXIS, None, None), kv_spec, kv_spec,
                        P(DATA_AXIS, MODEL_AXIS), P()),
              out_specs=P(DATA_AXIS, None, None),
              check_vma=False)
    def run(q_l, k_l, v_l, keep_l, lidx):
        out, m, l = fn_stats(q_l, k_l, v_l, keep_l, lidx)
        out = out.astype(jnp.float32)
        # flash merge across sequence shards; empty shards carry
        # m=NEG_INF / l=0 and must contribute weight 0, not NaN
        m_all = jax.lax.pmax(m, MODEL_AXIS)
        m_safe = jnp.where(m_all > NEG_INF / 2, m_all, 0.0)
        w = jnp.where(m > NEG_INF / 2, l * jnp.exp(m - m_safe), 0.0)
        # one fused psum for numerator and normalizer (this runs per
        # layer per decode token: collective count is latency)
        num, denom = jax.lax.psum((out * w[..., None], w), MODEL_AXIS)
        safe = jnp.where(denom > 0, denom, 1.0)
        out = jnp.where(denom[..., None] > 0,
                        num / safe[..., None], 0.0)
        return out.astype(q_l.dtype)

    k_all, v_all = caches
    return run(q, k_all, v_all, keep, layer_index)


def flash_decode_attention_stacked(
    q: jnp.ndarray,        # [B, nq, hd]
    k_all: jnp.ndarray,    # [nl, B, nkv, S, hd] -- the FULL stacked cache
    v_all: jnp.ndarray,
    valid_mask: jnp.ndarray,  # [B, S] bool
    layer_index,           # scalar int32: a Python int or traced
    *,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    slot: Optional[jnp.ndarray] = None,
    block_k: int = DEFAULT_BK,
    interpret: bool = False,
    return_stats: bool = False,  # also return (m, l) softmax partials
) -> jnp.ndarray:
    """One query token per stream against layer ``layer_index`` of the
    stacked cache, read in place through a scalar-prefetch index map:
    HBM traffic is exactly one layer's K/V rows, with no per-layer
    slice copy, and one Mosaic body serves every layer. S must be a
    multiple of the K block (the generation path allocates caches
    pre-padded; see `transformer.init_kv_cache`)."""
    b, nq, hd = q.shape
    nl, _, nkv, s = k_all.shape[:4]
    group = nq // nkv
    scale = float(scale) if scale is not None else hd ** -0.5

    bk = _pick_bk(s, block_k)
    if s % bk:
        raise ValueError(
            f"stacked decode cache length {s} must be a multiple of "
            f"the K block {bk}; pad the cache at allocation time")

    keep = _window_keep(valid_mask, sliding_window, slot)
    gp = max(SUBLANES, group)
    qg = _pad_group(q, nkv, group, gp)
    keep_b = jnp.broadcast_to(keep[:, None, :], (b, SUBLANES, s))
    lidx = jnp.asarray(layer_index, jnp.int32).reshape(1)

    in_specs = [
        pl.BlockSpec((1, 1, gp, hd), lambda bi, h, lr: (bi, h, 0, 0)),
        pl.BlockSpec((1, 1, 1, s, hd),
                     lambda bi, h, lr: (lr[0], bi, h, 0, 0)),
        pl.BlockSpec((1, 1, 1, s, hd),
                     lambda bi, h, lr: (lr[0], bi, h, 0, 0)),
        pl.BlockSpec((1, SUBLANES, s), lambda bi, h, lr: (bi, 0, 0)),
    ]
    o_spec = pl.BlockSpec((1, 1, gp, hd), lambda bi, h, lr: (bi, h, 0, 0))
    out_shape = jax.ShapeDtypeStruct((b, nkv, gp, hd), q.dtype)
    out_specs = o_spec
    if return_stats:
        stat = jax.ShapeDtypeStruct((b, nkv, gp), jnp.float32)
        stat_spec = pl.BlockSpec((1, 1, gp), lambda bi, h, lr: (bi, h, 0))
        out_shape = (out_shape, stat, stat)
        out_specs = (o_spec, stat_spec, stat_spec)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b, nkv),
        in_specs=in_specs, out_specs=out_specs)
    res = pl.pallas_call(
        functools.partial(_stacked_kernel, scale=scale, bk=bk),
        out_shape=out_shape, grid_spec=grid_spec,
        interpret=interpret, name=KERNEL_NAME,
    )(lidx, qg, k_all, v_all, keep_b)
    return _trim_stats(res, return_stats, b, nq, group)


#: instructions that move nothing on the device
_HLO_FREE = frozenset(
    ("parameter", "get-tuple-element", "bitcast", "constant"))


def decode_layer_copies(hlo_text: str, layer_shape) -> int:
    """How many device operations of a compiled program produce an
    array of one layer's cache shape ``[B, nkv, S, hd]`` (as one
    device holds it): the slices and relayout copies of a layer that
    a decode loop makes when it takes ``k_all[l]`` out of the stack.
    0 where attention reads the stack in place. A pure function of
    the optimized HLO text (``Engine.compiled_text``): instructions
    inside fusion bodies are no operations of their own and are not
    counted (``hlo_text.device_instructions``), nor are those that
    move nothing (parameters, tuple elements, bitcasts)."""
    dims = ",".join(str(int(d)) for d in layer_shape)
    layer = re.compile(r"\w+\[" + dims + r"\]")  # an array, not a tuple
    return sum(opcode not in _HLO_FREE and bool(layer.match(result))
               for _, result, opcode in device_instructions(hlo_text))


def local_layer_shape(mesh, b: int, nq: int, nkv: int, s: int, hd: int):
    """One layer's cache ``[B, nkv, S, hd]`` as ONE device of ``mesh``
    holds it under `choose_decode_partitioning`: what
    `decode_layer_copies` looks for in an SPMD program."""
    if not mesh_nontrivial(mesh):
        return (b, nkv, s, hd)
    from realhf_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
    dp = mesh.shape.get(DATA_AXIS, 1)
    tp = mesh.shape.get(MODEL_AXIS, 1)
    part = choose_decode_partitioning(mesh, b, nq, nkv, s)
    if part == "seq":
        return (b // dp, nkv, s // tp, hd)
    # heads, or GSPMD's own choice: batch and heads where they divide
    return (b // dp if b % dp == 0 else b,
            nkv // tp if nkv % tp == 0 else nkv, s, hd)
