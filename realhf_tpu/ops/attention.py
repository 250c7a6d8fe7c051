"""Attention over packed variable-length sequences.

TPU-native replacement for the reference's flash-attn usage
(``realhf/impl/model/modules/attn.py:20-23``): packed batches carry
segment ids instead of cu_seqlens -- tokens attend only within their
own segment, causally. Two paths:

- ``packed_attention``: training/prefill attention on ``[B, L]``
  packed streams. Default implementation is pure XLA (einsum + fp32
  softmax with segment masking); a Pallas flash kernel
  (``realhf_tpu.ops.flash_attention``) is used on TPU for long L.
- ``decode_attention``: single-token decode against a padded KV cache
  (replaces ``flash_attn_with_kvcache``).

Segment id 0 marks padding; valid segments are >= 1.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from realhf_tpu.base.backend import pallas_enabled

NEG_INF = -2.0 ** 30  # large finite value; -inf breaks softmax for all-masked rows


def _segment_mask(seg_q: jnp.ndarray, seg_k: jnp.ndarray,
                  causal: bool,
                  sliding_window: Optional[int] = None) -> jnp.ndarray:
    """[B, Lq, Lk] bool mask: same non-zero segment (+ causality,
    + optional sliding window).

    Within a packed stream, positions inside a segment are contiguous,
    so the stream-index difference equals the in-segment position
    difference and the (q_idx - k_idx) < window test is exact.
    """
    mask = (seg_q[:, :, None] == seg_k[:, None, :]) & (seg_q[:, :, None] != 0)
    lq, lk = seg_q.shape[1], seg_k.shape[1]
    idx_q = jnp.arange(lq)[:, None]
    idx_k = jnp.arange(lk)[None, :]
    if causal:
        mask = mask & (idx_q >= idx_k)[None]
    if sliding_window is not None:
        mask = mask & ((idx_q - idx_k) < sliding_window)[None]
    return mask


def packed_attention_xla(
    q: jnp.ndarray,  # [B, L, nq, hd]
    k: jnp.ndarray,  # [B, L, nkv, hd]
    v: jnp.ndarray,  # [B, L, nkv, hv]: hv the value's width, hd or not
    seg_ids: jnp.ndarray,  # [B, L] int32, 0 = padding
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    logits_soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
    select: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Reference XLA implementation; O(L^2) scores in fp32.

    GQA is expressed by grouping query heads over each KV head so the
    einsum keeps a single contraction (MXU-friendly). ``select``
    [B, L, L] (0 = not attended): a learned selection of keys a query
    that every head shares, one more term of the mask.
    """
    b, l, nq, hd = q.shape
    nkv = k.shape[2]
    group = nq // nkv
    scale = scale if scale is not None else hd ** -0.5

    qg = q.reshape(b, l, nkv, group, hd)
    # [B, nkv, g, Lq, Lk]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if logits_soft_cap is not None:
        scores = logits_soft_cap * jnp.tanh(scores / logits_soft_cap)
    mask = _segment_mask(seg_ids, seg_ids, causal,
                         sliding_window)[:, None, None]
    if select is not None:
        mask = mask & (select != 0)[:, None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, l, nq, v.shape[-1]).astype(q.dtype)


def flash_takes(row_len: int, key_dim: int, *, scale=None,
                logits_soft_cap=None) -> bool:
    """Whether a packed row meets the flash kernel's gate: its tiling
    (``key_dim``: the query/key's width, which the scores contract
    over; the value's may differ), a static python scale, no soft cap
    (a sliding window is the kernel's own)."""
    return (row_len % 128 == 0 and key_dim >= 64
            and logits_soft_cap is None
            and (scale is None or isinstance(scale, (int, float))))


def packed_attention(q, k, v, seg_ids, *, causal=True, scale=None,
                     logits_soft_cap=None, sliding_window=None,
                     use_flash: Optional[bool] = None, select=None):
    """Dispatch between the Pallas flash kernel (TPU) and the XLA path.

    ``use_flash=None`` auto-selects: flash on TPU backends when shapes
    meet the kernel's tiling constraints, XLA otherwise (CPU tests).
    ``select``: a sparse layer's selection [B, L, L], to either path.
    """
    if use_flash is None:
        use_flash = pallas_enabled() and flash_takes(
            q.shape[1], q.shape[3], scale=scale,
            logits_soft_cap=logits_soft_cap)
    if use_flash:
        from realhf_tpu.ops.flash_attention import flash_attention

        # raises above FLASH_MAX_LEN: a row the chip's compiler would
        # refuse never drops to the O(L^2) XLA path in silence
        return flash_attention(q, k, v, seg_ids, causal=causal,
                               scale=scale,
                               logits_soft_cap=logits_soft_cap,
                               sliding_window=sliding_window,
                               select=select)
    return packed_attention_xla(q, k, v, seg_ids, causal=causal, scale=scale,
                                logits_soft_cap=logits_soft_cap,
                                sliding_window=sliding_window,
                                select=select)


def make_sharded_attention(mesh, inner=None):
    """Factory for a packed-attention fn that partitions the Pallas
    flash kernel over a dp x tp mesh with `shard_map` (B over "data",
    heads over "model"; L stays whole -- sequence sharding is ring
    attention's job). A bare pallas_call under GSPMD has no
    partitioning rule, so without this the sharded forward would
    gather full Q/K/V onto every device. Engines install this as
    ``attention_fn`` on non-trivial TPU meshes.

    Falls back to the XLA path (which GSPMD partitions natively) when
    shapes do not divide the mesh or the scale is traced. ``inner``
    overrides the per-shard implementation (tests inject the
    interpret-mode kernel). A sparse layer's selection [B, L, L] goes
    with the rows over "data" and whole to every shard of "model":
    the heads of a token share it."""
    from functools import partial as _partial

    from jax.sharding import PartitionSpec as P

    from realhf_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    dp = mesh.shape.get(DATA_AXIS, 1)
    tp = mesh.shape.get(MODEL_AXIS, 1)
    local = inner or packed_attention

    def attn(q, k, v, seg_ids, causal=True, scale=None,
             sliding_window=None, select=None):
        b, _, nq, _ = q.shape
        nkv = k.shape[2]
        more = {} if select is None else dict(select=select)
        if dp * tp == 1:
            return local(q, k, v, seg_ids, causal=causal, scale=scale,
                         sliding_window=sliding_window, **more)
        if (b % dp or nq % tp or nkv % tp
                or not (scale is None
                        or isinstance(scale, (int, float)))):
            return packed_attention_xla(
                q, k, v, seg_ids, causal=causal, scale=scale,
                sliding_window=sliding_window, **more)

        extra = [a for a in mesh.axis_names
                 if a not in (DATA_AXIS, MODEL_AXIS)]
        axis_names = (set(mesh.axis_names)
                      if all(mesh.shape[a] == 1 for a in extra)
                      else {DATA_AXIS, MODEL_AXIS})

        @_partial(jax.shard_map, mesh=mesh,
                  axis_names=axis_names,
                  in_specs=(P(DATA_AXIS, None, MODEL_AXIS, None),
                            P(DATA_AXIS, None, MODEL_AXIS, None),
                            P(DATA_AXIS, None, MODEL_AXIS, None),
                            P(DATA_AXIS, None))
                  + (P(DATA_AXIS, None, None),) * len(more),
                  out_specs=P(DATA_AXIS, None, MODEL_AXIS, None),
                  # pallas_call outputs carry no varying-axes metadata
                  check_vma=False)
        def run(q_l, k_l, v_l, seg_l, *sel_l):
            return local(q_l, k_l, v_l, seg_l, causal=causal,
                         scale=scale, sliding_window=sliding_window,
                         **dict(zip(more, sel_l)))

        return run(q, k, v, seg_ids, *more.values())

    return attn


def decode_attention(
    q: jnp.ndarray,        # [B, nq, hd] -- one new token per stream
    k_cache: jnp.ndarray,  # [B, nkv, S, hd] (head-major)
    v_cache: jnp.ndarray,  # [B, nkv, S, hv]
    valid_mask: jnp.ndarray,  # [B, S] bool: which cache slots hold real
                              # tokens (left-padded prompts leave invalid
                              # low slots, so a prefix length is not enough)
    *,
    scale: Optional[float] = None,
    logits_soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
    slot: Optional[jnp.ndarray] = None,  # [B] int32 current write index,
                                         # required with sliding_window
) -> jnp.ndarray:
    """Single-step decode attention against ONE layer's padded KV
    cache, in plain XLA (GSPMD partitions it on a mesh): the reference
    of the Pallas kernel, which reads the stacked cache in place
    (``ops/decode_attention.py``; ``models/transformer.py`` routes),
    and the path where the kernel does not apply (CPU, heads under 64,
    a mesh nothing divides).

    The caller has already written the new token's K/V (and marked its
    slot valid). Replaces `flash_attn_with_kvcache`
    (reference ``attn.py:238``). Cache slot indices are sequential
    stream positions, so the sliding window keeps slots in
    ``(slot - window, slot]``.
    """
    b, nq, hd = q.shape
    nkv, s = k_cache.shape[1], k_cache.shape[2]
    group = nq // nkv
    scale = scale if scale is not None else hd ** -0.5

    qg = q.reshape(b, nkv, group, hd)
    scores = jnp.einsum("bhgd,bhkd->bhgk", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    if logits_soft_cap is not None:
        scores = logits_soft_cap * jnp.tanh(scores / logits_soft_cap)
    keep = valid_mask
    if sliding_window is not None:
        assert slot is not None, "sliding_window decode needs slot indices"
        idx = jnp.arange(s, dtype=jnp.int32)[None, :]
        keep = keep & ((slot[:, None] - idx) < sliding_window)
    scores = jnp.where(keep[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", probs.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, nq, v_cache.shape[-1]).astype(q.dtype)
