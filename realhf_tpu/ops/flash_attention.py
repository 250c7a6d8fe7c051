"""Pallas flash attention over packed segments (TPU).

TPU-native replacement for the reference's flash-attn varlen kernels
(``realhf/impl/model/modules/attn.py:20-23``): tiled online-softmax
attention (flash-attention-2 schedule) with

- causal masking,
- segment-id masking for packed variable-length sequences (the
  cu_seqlens equivalent),
- GQA (query-head groups share KV heads),
- a custom VJP with Pallas backward kernels (dq and dkv passes),
  recomputing probabilities from the saved log-sum-exp.

Layout contract: q [B, L, nq, hd], k/v [B, L, nkv, hd], seg_ids [B, L]
(0 = padding). L must be a multiple of the Q block; hd should be a
multiple of 128 for MXU tiling (128 for llama-family models). K and V
(forward, dq) and Q, dO, lse, delta (dkv) are kept whole in VMEM per
(batch, head), which bounds L: ``FLASH_MAX_LEN`` below is what the
v5e compiler accepts for forward AND backward at the head sizes of
the supported families; ``flash_attention`` raises above it. Longer
rows need more microbatches (shorter packed rows) or a
context-parallel mesh (ring attention); streaming KV by DMA is future
work.

Mosaic requires the last two dims of every block to be (8, 128)-tile
aligned, so 1D row metadata rides wider layouts: q-side segment ids
and the saved lse/delta are broadcast over a 128-lane axis, k-side
segment ids over an 8-sublane axis (same scheme as jax's bundled
flash kernel).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BQ = 256
DEFAULT_BK = 512
#: Longest packed row the kernel takes. Asked of the v5e compiler
#: (libtpu 0.0.34, bf16, tests/ops/test_chip_compile.py): the backward
#: compiles to L = 5120 at (14 q, 2 kv, hd 64) and to 6144 at
#: (32, 8, 128) and runs out of VMEM one kilotoken above either; the
#: forward alone compiles to 8192 and is refused at 16384.
FLASH_MAX_LEN = 4096
NEG_INF = -2.0 ** 30
LANES = 128
SUBLANES = 8


def _blocks(l: int, bq: int, bk: int):
    bq = min(bq, l)
    bk = min(bk, l)
    while l % bq:
        bq //= 2
    while l % bk:
        bk //= 2
    return max(bq, 8), max(bk, 8)


# ----------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, segq_ref, segk_ref,  # inputs
                o_ref, lse_ref,  # outputs
                *, scale: float, bk: int, causal: bool):
    qi = pl.program_id(2)
    bq, hd = q_ref.shape[-2], q_ref.shape[-1]
    l = k_ref.shape[-2]

    q = q_ref[0, 0].astype(jnp.float32) * scale  # [BQ, hd]
    seg_q = segq_ref[0, :, 0]  # [BQ]
    q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, hd), jnp.float32)

    n_kv = pl.cdiv((qi + 1) * bq, bk) if causal else l // bk

    def body(j, carry):
        m, l_sum, acc = carry
        k = k_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)  # [BK, hd]
        v = v_ref[0, 0, pl.ds(j * bk, bk), :]
        seg_k = segk_ref[0, 0, pl.ds(j * bk, bk)]  # [BK]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [BQ, BK]
        k_idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (seg_q[:, None] == seg_k[None, :]) & (seg_q[:, None] != 0)
        if causal:
            mask &= q_idx >= k_idx
        s = jnp.where(mask, s, NEG_INF)

        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l_sum * alpha + p.sum(axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l_sum, acc = jax.lax.fori_loop(0, n_kv, body, (m0, l0, acc0))
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


def _flash_fwd(q, k, v, seg_ids, scale, causal, bq, bk):
    b, l, nq, hd = q.shape
    nkv = k.shape[2]
    group = nq // nkv
    bq, bk = _blocks(l, bq, bk)

    qt = q.transpose(0, 2, 1, 3)  # [B, nq, L, hd]
    kt = k.transpose(0, 2, 1, 3)  # [B, nkv, L, hd]
    vt = v.transpose(0, 2, 1, 3)
    segq, segk = _expand_segments(seg_ids)

    grid = (b, nq, l // bq)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bk=bk, causal=causal),
        out_shape=(
            jax.ShapeDtypeStruct(qt.shape, q.dtype),
            jax.ShapeDtypeStruct((b, nq, l, LANES), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda bi, h, qi: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, l, hd),
                         lambda bi, h, qi, g=group: (bi, h // g, 0, 0)),
            pl.BlockSpec((1, 1, l, hd),
                         lambda bi, h, qi, g=group: (bi, h // g, 0, 0)),
            pl.BlockSpec((1, bq, LANES), lambda bi, h, qi: (bi, qi, 0)),
            pl.BlockSpec((1, SUBLANES, l), lambda bi, h, qi: (bi, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bq, hd), lambda bi, h, qi: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda bi, h, qi: (bi, h, qi, 0)),
        ),
        name="flash_fwd",
    )(qt, kt, vt, segq, segk)
    return out.transpose(0, 2, 1, 3), lse


# ----------------------------------------------------------------------
# Backward
# ----------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, segq_ref, segk_ref, do_ref,
                   lse_ref, delta_ref, dq_ref,
                   *, scale: float, bk: int, causal: bool):
    qi = pl.program_id(2)
    bq, hd = q_ref.shape[-2], q_ref.shape[-1]
    l = k_ref.shape[-2]

    q = q_ref[0, 0].astype(jnp.float32) * scale
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, :, 0]
    delta = delta_ref[0, 0, :, 0]
    seg_q = segq_ref[0, :, 0]
    q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    n_kv = pl.cdiv((qi + 1) * bq, bk) if causal else l // bk

    def body(j, dq):
        k = k_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        seg_k = segk_ref[0, 0, pl.ds(j * bk, bk)]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (seg_q[:, None] == seg_k[None, :]) & (seg_q[:, None] != 0)
        if causal:
            mask &= q_idx >= k_idx
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, n_kv, body, jnp.zeros((bq, hd), jnp.float32))
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, segq_ref, segk_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref,
                    *, scale: float, bq: int, causal: bool):
    ki = pl.program_id(2)
    bk, hd = k_ref.shape[-2], k_ref.shape[-1]
    l = q_ref.shape[-2]

    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    seg_k = segk_ref[0, 0, pl.ds(ki * bk, bk)]
    k_idx = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    start_q = (ki * bk) // bq if causal else 0
    n_q = l // bq

    def body(j, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(j * bq, bq), :].astype(jnp.float32) * scale
        do = do_ref[0, 0, pl.ds(j * bq, bq), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(j * bq, bq), 0]
        delta = delta_ref[0, 0, pl.ds(j * bq, bq), 0]
        seg_q = segq_ref[0, pl.ds(j * bq, bq), 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_idx = j * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        mask = (seg_q[:, None] == seg_k[None, :]) & (seg_q[:, None] != 0)
        if causal:
            mask &= q_idx >= k_idx
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros((bk, hd), jnp.float32)
    dv0 = jnp.zeros((bk, hd), jnp.float32)
    dk, dv = jax.lax.fori_loop(start_q, n_q, body, (dk0, dv0))
    # Per-q-head partials; summed over each KV group outside (race-free).
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _flash_bwd(res, g, scale, causal, bq, bk):
    q, k, v, seg_ids, out, lse = res
    do = g
    b, l, nq, hd = q.shape
    nkv = k.shape[2]
    group = nq // nkv
    bq_, bk_ = _blocks(l, bq, bk)

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    ot = out.transpose(0, 2, 1, 3)
    segq, segk = _expand_segments(seg_ids)

    delta = (ot.astype(jnp.float32) * dot.astype(jnp.float32)).sum(-1)
    delta = jnp.broadcast_to(delta[..., None], (b, nq, l, LANES))

    grid_q = (b, nq, l // bq_)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, bk=bk_,
                          causal=causal),
        out_shape=jax.ShapeDtypeStruct(qt.shape, jnp.float32),
        grid=grid_q,
        in_specs=[
            pl.BlockSpec((1, 1, bq_, hd), lambda bi, h, qi: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, l, hd),
                         lambda bi, h, qi, g_=group: (bi, h // g_, 0, 0)),
            pl.BlockSpec((1, 1, l, hd),
                         lambda bi, h, qi, g_=group: (bi, h // g_, 0, 0)),
            pl.BlockSpec((1, bq_, LANES), lambda bi, h, qi: (bi, qi, 0)),
            pl.BlockSpec((1, SUBLANES, l), lambda bi, h, qi: (bi, 0, 0)),
            pl.BlockSpec((1, 1, bq_, hd), lambda bi, h, qi: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, bq_, LANES),
                         lambda bi, h, qi: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, bq_, LANES),
                         lambda bi, h, qi: (bi, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq_, hd),
                               lambda bi, h, qi: (bi, h, qi, 0)),
        name="flash_bwd_dq",
    )(qt, kt, vt, segq, segk, dot, lse, delta)

    grid_k = (b, nq, l // bk_)
    dk_partial, dv_partial = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, bq=bq_,
                          causal=causal),
        out_shape=(
            jax.ShapeDtypeStruct((b, nq, l, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, nq, l, hd), jnp.float32),
        ),
        grid=grid_k,
        in_specs=[
            pl.BlockSpec((1, 1, l, hd), lambda bi, h, ki: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, bk_, hd),
                         lambda bi, h, ki, g_=group: (bi, h // g_, ki, 0)),
            pl.BlockSpec((1, 1, bk_, hd),
                         lambda bi, h, ki, g_=group: (bi, h // g_, ki, 0)),
            pl.BlockSpec((1, l, LANES), lambda bi, h, ki: (bi, 0, 0)),
            pl.BlockSpec((1, SUBLANES, l), lambda bi, h, ki: (bi, 0, 0)),
            pl.BlockSpec((1, 1, l, hd), lambda bi, h, ki: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, l, LANES), lambda bi, h, ki: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, l, LANES), lambda bi, h, ki: (bi, h, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bk_, hd), lambda bi, h, ki: (bi, h, ki, 0)),
            pl.BlockSpec((1, 1, bk_, hd), lambda bi, h, ki: (bi, h, ki, 0)),
        ),
        name="flash_bwd_dkv",
    )(qt, kt, vt, segq, segk, dot, lse, delta)

    # Sum q-head partials within each KV group.
    dk = dk_partial.reshape(b, nkv, group, l, hd).sum(2).transpose(0, 2, 1, 3)
    dv = dv_partial.reshape(b, nkv, group, l, hd).sum(2).transpose(0, 2, 1, 3)
    dq_ = dq.transpose(0, 2, 1, 3).astype(q.dtype)
    return (dq_, dk.astype(k.dtype), dv.astype(v.dtype), None)


# ----------------------------------------------------------------------
# Public API
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_attention(q, k, v, seg_ids, scale, causal, bq, bk):
    out, _ = _flash_fwd(q, k, v, seg_ids, scale, causal, bq, bk)
    return out


def _flash_attention_fwd(q, k, v, seg_ids, scale, causal, bq, bk):
    out, lse = _flash_fwd(q, k, v, seg_ids, scale, causal, bq, bk)
    return out, (q, k, v, seg_ids, out, lse)


_flash_attention.defvjp(
    _flash_attention_fwd,
    lambda scale, causal, bq, bk, res, g: _flash_bwd(
        res, g, scale, causal, bq, bk))


def flash_attention(q, k, v, seg_ids, *, causal: bool = True,
                    scale: Optional[float] = None,
                    logits_soft_cap: Optional[float] = None,
                    block_q: int = DEFAULT_BQ,
                    block_k: int = DEFAULT_BK) -> jnp.ndarray:
    """Packed-segment flash attention; drop-in for
    `ops.attention.packed_attention_xla` on TPU."""
    if logits_soft_cap is not None:
        raise NotImplementedError(
            "soft cap not yet supported by the flash kernel; use the XLA "
            "path (packed_attention(..., use_flash=False)).")
    if q.shape[1] > FLASH_MAX_LEN:
        raise ValueError(
            f"flash_attention: packed row of {q.shape[1]} tokens exceeds "
            f"FLASH_MAX_LEN={FLASH_MAX_LEN}, the longest row whose "
            "forward and backward kernels fit the chip's VMEM. Split "
            "the batch into more microbatches (the MFC's n_mbs) so "
            "packed rows get shorter, or shard the sequence over a "
            "context-parallel mesh (ring attention).")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return _flash_attention(q, k, v, seg_ids.astype(jnp.int32),
                            float(scale), causal, block_q, block_k)
