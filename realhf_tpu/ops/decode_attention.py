"""Pallas flash-decode attention against a padded KV cache (TPU).

Replaces the plain-XLA ``ops.attention.decode_attention`` on the hot
decode path (reference ``flash_attn_with_kvcache``, attn.py:238): one
query token per stream attends over the whole cache with a tiled
online softmax, never materializing the ``[B, nq, S]`` score tensor.
Decode is HBM-bandwidth bound -- the kernel makes a single pass over
K/V per step, with all query heads of a KV group (GQA) sharing each
loaded block.

Layout contract (HEAD-MAJOR, so no transpose sits on the hot path):
q [B, nq, hd], per-layer caches [B, nkv, S, hd], keep-mask [B, S]
(validity AND the sliding window -- precomputed in XLA, it is O(B*S)
elementwise). Two entry points:

- ``flash_decode_attention``: per-layer caches (unrolled decode loop;
  a static layer index into the stacked cache is a free view).
- ``flash_decode_attention_stacked``: the FULL stacked caches
  [nl, B, nkv, S, hd] plus a (traced) layer index, delivered to the
  kernel through scalar prefetch so only layer ``l``'s rows are ever
  streamed from HBM. This keeps the `lax.scan`-over-layers decode
  path at O(1) compile time without copying a layer's cache out per
  token (the round-3 decode bottleneck).

The query-group axis is padded up to the fp32 sublane count (8); hd
should be a multiple of 128 on real TPUs. S is padded to the K block.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from realhf_tpu.base import logging

logger = logging.getLogger("decode_attention")

NEG_INF = -2.0 ** 30
SUBLANES = 8


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


def _layer_kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, *, scale, bk):
    s = k_ref.shape[-2]
    _decode_body(
        q_ref[0, 0],
        lambda j: k_ref[0, 0, pl.ds(j * bk, bk), :],
        lambda j: v_ref[0, 0, pl.ds(j * bk, bk), :],
        lambda j: keep_ref[0, 0, pl.ds(j * bk, bk)],
        o_ref, scale=scale, bk=bk, s=s)


def _layer_kernel_stats(q_ref, k_ref, v_ref, keep_ref, o_ref, m_ref,
                        l_ref, *, scale, bk):
    s = k_ref.shape[-2]
    _decode_body(
        q_ref[0, 0],
        lambda j: k_ref[0, 0, pl.ds(j * bk, bk), :],
        lambda j: v_ref[0, 0, pl.ds(j * bk, bk), :],
        lambda j: keep_ref[0, 0, pl.ds(j * bk, bk)],
        o_ref, scale=scale, bk=bk, s=s, m_ref=m_ref, l_ref=l_ref)


def _stacked_kernel(lidx_ref, q_ref, k_ref, v_ref, keep_ref, o_ref, *,
                    scale, bk):
    # lidx_ref is the scalar-prefetch operand; the index_map already
    # consumed it to select the layer block, so the body is identical.
    s = k_ref.shape[-2]
    _decode_body(
        q_ref[0, 0],
        lambda j: k_ref[0, 0, 0, pl.ds(j * bk, bk), :],
        lambda j: v_ref[0, 0, 0, pl.ds(j * bk, bk), :],
        lambda j: keep_ref[0, 0, pl.ds(j * bk, bk)],
        o_ref, scale=scale, bk=bk, s=s)


def _stacked_kernel_stats(lidx_ref, q_ref, k_ref, v_ref, keep_ref,
                          o_ref, m_ref, l_ref, *, scale, bk):
    s = k_ref.shape[-2]
    _decode_body(
        q_ref[0, 0],
        lambda j: k_ref[0, 0, 0, pl.ds(j * bk, bk), :],
        lambda j: v_ref[0, 0, 0, pl.ds(j * bk, bk), :],
        lambda j: keep_ref[0, 0, pl.ds(j * bk, bk)],
        o_ref, scale=scale, bk=bk, s=s, m_ref=m_ref, l_ref=l_ref)


def _with_stats(kernel, kernel_stats, return_stats, o_shape, o_dtype,
                o_spec, stat_spec, **kw):
    """Pick the (kernel, out_shape, out_specs) triple for a decode
    pallas_call with or without the (m, l) stats outputs -- shared by
    the flat and stacked wrappers so their call setup cannot drift."""
    b, nkv, gp = o_shape[0], o_shape[1], o_shape[2]
    if return_stats:
        stat = jax.ShapeDtypeStruct((b, nkv, gp), jnp.float32)
        return (functools.partial(kernel_stats, **kw),
                (jax.ShapeDtypeStruct(o_shape, o_dtype), stat, stat),
                (o_spec, stat_spec, stat_spec))
    return (functools.partial(kernel, **kw),
            jax.ShapeDtypeStruct(o_shape, o_dtype), o_spec)


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
    allocated as multiples of 128, so this normally succeeds and the
    concat-pad fallback never runs on the hot path). The ladder spans
    past 512 so a raised DEFAULT_BK actually takes effect."""
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


def flash_decode_attention(
    q: jnp.ndarray,        # [B, nq, hd]
    k_cache: jnp.ndarray,  # [B, nkv, S, hd]
    v_cache: jnp.ndarray,
    valid_mask: jnp.ndarray,  # [B, S] bool
    *,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    slot: Optional[jnp.ndarray] = None,  # [B] int32, with sliding_window
    block_k: int = DEFAULT_BK,
    interpret: bool = False,
    return_stats: bool = False,  # also return (m, l) softmax partials
) -> jnp.ndarray:
    b, nq, hd = q.shape
    nkv, s = k_cache.shape[1], k_cache.shape[2]
    group = nq // nkv
    scale = float(scale) if scale is not None else hd ** -0.5

    keep = _window_keep(valid_mask, sliding_window, slot)

    bk = _pick_bk(s, block_k)
    pad_s = (-s) % bk
    if pad_s:
        zpad = jnp.zeros((b, nkv, pad_s, hd), k_cache.dtype)
        k_cache = jnp.concatenate([k_cache, zpad], axis=2)
        v_cache = jnp.concatenate([v_cache, zpad], axis=2)
        keep = jnp.concatenate(
            [keep, jnp.zeros((b, pad_s), jnp.int32)], axis=1)
    s += pad_s

    gp = max(SUBLANES, group)  # pad query group to the sublane tile
    qg = _pad_group(q, nkv, group, gp)
    keep_b = jnp.broadcast_to(keep[:, None, :], (b, SUBLANES, s))

    in_specs = [
        pl.BlockSpec((1, 1, gp, hd), lambda bi, h: (bi, h, 0, 0)),
        pl.BlockSpec((1, 1, s, hd), lambda bi, h: (bi, h, 0, 0)),
        pl.BlockSpec((1, 1, s, hd), lambda bi, h: (bi, h, 0, 0)),
        pl.BlockSpec((1, SUBLANES, s), lambda bi, h: (bi, 0, 0)),
    ]
    o_spec = pl.BlockSpec((1, 1, gp, hd), lambda bi, h: (bi, h, 0, 0))
    kernel, out_shape, out_specs = _with_stats(
        _layer_kernel, _layer_kernel_stats, return_stats,
        (b, nkv, gp, hd), q.dtype, o_spec,
        pl.BlockSpec((1, 1, gp), lambda bi, h: (bi, h, 0)),
        scale=scale, bk=bk)
    res = pl.pallas_call(
        kernel, out_shape=out_shape, grid=(b, nkv),
        in_specs=in_specs, out_specs=out_specs, interpret=interpret,
        name="decode_attn",
    )(qg, k_cache, v_cache, keep_b)
    return _trim_stats(res, return_stats, b, nq, group)


def sharded_decode_attention(
    fn, mesh, q, caches, valid_mask, slot, layer_index=None, *,
    stacked: bool,
):
    """Partition a decode-attention kernel over a dp x tp mesh with
    `shard_map` (manual over the data/model axes): a bare pallas_call
    under GSPMD has no partitioning rule, so without this wrapper XLA
    would gather the full KV cache onto every device -- fatal for the
    tp16 70B decode story (docs/distributed.md).
    ``fn(q, k, v, valid, slot, lidx)`` runs on LOCAL shards: B over
    "data", heads over "model" (GQA grouping survives because nq and
    nkv shard together).

    Callers must check `decode_shardable` (B % dp, nq % tp, nkv % tp)
    and fall back to the XLA path otherwise."""
    from functools import partial as _partial

    from jax.sharding import PartitionSpec as P

    from realhf_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    layer_lead = (None,) if stacked else ()
    kv_spec = P(*layer_lead, DATA_AXIS, MODEL_AXIS, None, None)
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
               (layer_index if layer_index is not None
                else jnp.zeros((), jnp.int32)))


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
                       *, stacked: bool, scale=None,
                       sliding_window=None):
    """Single dispatcher for one decode-attention call onto the Pallas
    kernels: bare kernel on trivial meshes, head-sharded or
    KV-sequence-split shard_map per ``choose_decode_partitioning``.
    Returns ``None`` when no kernel partitioning applies -- the caller
    then takes its GSPMD/XLA fallback. Shared by the flat
    (``ops/attention.decode_attention``) and stacked
    (``models/transformer._stacked_decode_attention``) paths so the
    routing cannot drift between them. Traced scales (deep
    scale_attn_by_inverse_layer_idx models) fold into q here, since
    the kernels need a python-static scale."""
    if not (scale is None or isinstance(scale, (int, float))):
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
        scale = 1.0
    b, nq = q.shape[0], q.shape[1]
    if stacked:
        nkv, s = caches[0].shape[2], caches[0].shape[3]

        def plain(q_, k_, v_, valid_, slot_, lidx):
            return flash_decode_attention_stacked(
                q_, k_, v_, valid_, lidx, scale=scale,
                sliding_window=sliding_window, slot=slot_)

        def stats(q_, k_, v_, keep_, lidx):
            return flash_decode_attention_stacked(
                q_, k_, v_, keep_.astype(bool), lidx, scale=scale,
                return_stats=True)
    else:
        nkv, s = caches[0].shape[1], caches[0].shape[2]

        def plain(q_, k_, v_, valid_, slot_, lidx):
            return flash_decode_attention(
                q_, k_, v_, valid_, scale=scale,
                sliding_window=sliding_window, slot=slot_)

        def stats(q_, k_, v_, keep_, lidx):
            return flash_decode_attention(
                q_, k_, v_, keep_.astype(bool), scale=scale,
                return_stats=True)

    if not mesh_nontrivial(mesh):
        return plain(q, caches[0], caches[1], valid_mask, slot,
                     (layer_index if layer_index is not None
                      else jnp.zeros((), jnp.int32)))
    part = choose_decode_partitioning(mesh, b, nq, nkv, s)
    if part == "heads":
        return sharded_decode_attention(
            plain, mesh, q, caches, valid_mask, slot, layer_index,
            stacked=stacked)
    if part == "seq":
        keep = window_keep(valid_mask, sliding_window, slot)
        return sharded_decode_attention_seqsplit(
            stats, mesh, q, caches, keep, layer_index, stacked=stacked)
    return None


def sharded_decode_attention_seqsplit(
    fn_stats, mesh, q, caches, keep, layer_index=None, *,
    stacked: bool,
):
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

    layer_lead = (None,) if stacked else ()
    kv_spec = P(*layer_lead, DATA_AXIS, None, MODEL_AXIS, None)
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
    return run(q, k_all, v_all, keep,
               (layer_index if layer_index is not None
                else jnp.zeros((), jnp.int32)))


def flash_decode_attention_stacked(
    q: jnp.ndarray,        # [B, nq, hd]
    k_all: jnp.ndarray,    # [nl, B, nkv, S, hd] -- the FULL stacked cache
    v_all: jnp.ndarray,
    valid_mask: jnp.ndarray,  # [B, S] bool
    layer_index: jnp.ndarray,  # scalar int32 (traced OK)
    *,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    slot: Optional[jnp.ndarray] = None,
    block_k: int = DEFAULT_BK,
    interpret: bool = False,
    return_stats: bool = False,  # also return (m, l) softmax partials
) -> jnp.ndarray:
    """Same math as `flash_decode_attention` but reads layer
    ``layer_index`` of the stacked cache directly via a scalar-prefetch
    index map -- HBM traffic is exactly one layer's K/V rows, with no
    per-layer slice copy. S must be a multiple of ``block_k`` (the
    generation path allocates caches pre-padded; see
    `transformer.init_kv_cache`)."""
    b, nq, hd = q.shape
    nl, _, nkv, s = k_all.shape[:4]
    group = nq // nkv
    scale = float(scale) if scale is not None else hd ** -0.5

    bk = _pick_bk(s, block_k)
    assert s % bk == 0, (
        f"stacked decode cache length {s} must be a multiple of the "
        f"K block {bk}; pad the cache at allocation time")

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
    kernel, out_shape, out_specs = _with_stats(
        _stacked_kernel, _stacked_kernel_stats, return_stats,
        (b, nkv, gp, hd), q.dtype, o_spec,
        pl.BlockSpec((1, 1, gp), lambda bi, h, lr: (bi, h, 0)),
        scale=scale, bk=bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b, nkv),
        in_specs=in_specs, out_specs=out_specs)
    res = pl.pallas_call(
        kernel, out_shape=out_shape, grid_spec=grid_spec,
        interpret=interpret, name="decode_attn_stacked",
    )(lidx, qg, k_all, v_all, keep_b)
    return _trim_stats(res, return_stats, b, nq, group)
