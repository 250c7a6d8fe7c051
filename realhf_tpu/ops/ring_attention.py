"""Ring attention: context parallelism over a mesh axis.

Fills the reference's explicitly-missing capability (context
parallelism is a TODO at ``realhf/impl/model/backend/megatron.py:60``;
max sequence length there is bounded by one TP group's activation
memory). Here the sequence dim is sharded over a "ctx" mesh axis:
each device holds L/ctx tokens of every stream, K/V shards rotate
around the ring with `lax.ppermute`, and partial attention results
merge with the online-softmax combine -- so attention memory and
compute scale 1/ctx per device while packed-segment and causal
semantics are preserved via global position offsets.

The per-round partial attention runs BLOCKWISE (flash-style online
softmax over [block_q, block_k] tiles) once the local shard exceeds a
block, so per-device attention memory is O(bq*bk) regardless of
context length -- 32k+ contexts train at ctx>=4 without ever
materializing [Lq_loc, Lk_loc] scores. Fusing the ring rounds into a
single Pallas kernel with overlapped RDMA
(pltpu.make_async_remote_copy) remains the next optimization.
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -2.0 ** 30


def _fit_block(lc: int, block: int) -> int:
    """Largest divisor of lc that is <= block (>= 1)."""
    b = min(block, lc)
    while lc % b:
        b -= 1
    return b


def _partial_attention(q, k, v, seg_q, seg_k, q_off, k_off, scale, causal,
                       sliding_window=None):
    """One ring step: q [B, Lq, nq, hd] vs k/v [B, Lk, nkv, hd] with
    global offsets; returns (m [B, nq, Lq], l, acc [B, nq, Lq, hd])."""
    b, lq, nq, hd = q.shape
    nkv = k.shape[2]
    group = nq // nkv
    qg = (q * scale).reshape(b, lq, nkv, group, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32))
    s = s.reshape(b, nq, lq, -1)
    mask = (seg_q[:, :, None] == seg_k[:, None, :]) & (seg_q[:, :, None] != 0)
    qi = q_off + jnp.arange(lq)
    ki = k_off + jnp.arange(k.shape[1])
    if causal:
        mask = mask & (qi[:, None] >= ki[None, :])[None]
    if sliding_window is not None:
        # global stream indices make the window exact across ring steps
        mask = mask & ((qi[:, None] - ki[None, :]) < sliding_window)[None]
    s = jnp.where(mask[:, None], s, NEG_INF)
    m = s.max(axis=-1)  # [B, nq, Lq]
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    pv = p.reshape(b, nkv, group, lq, -1)
    acc = jnp.einsum("bhgqk,bkhd->bhgqd", pv, v.astype(jnp.float32))
    acc = acc.reshape(b, nq, lq, hd)
    return m, l, acc


def _combine(state, new):
    m0, l0, a0 = state
    m1, l1, a1 = new
    m = jnp.maximum(m0, m1)
    w0 = jnp.exp(m0 - m)
    w1 = jnp.exp(m1 - m)
    return m, l0 * w0 + l1 * w1, a0 * w0[..., None] + a1 * w1[..., None]


def _partial_attention_blockwise(q, k, v, seg_q, seg_k, q_off, k_off,
                                 scale, causal, sliding_window,
                                 bq, bk, vary=lambda x: x):
    """Blockwise (flash-style) version of ``_partial_attention``: the
    score matrix only ever exists as [B, nq, bq, bk] tiles, so one
    ring step's attention memory is O(bq*bk) instead of
    O(Lq_loc * Lk_loc) -- the piece that made 32k contexts OOM. Both
    scans have static trip counts and are reverse-differentiable."""
    b, lq, nq, hd = q.shape
    lk = k.shape[1]
    nqc, nkc = lq // bq, lk // bk

    # chunk axes to the front for scan
    qc = q.reshape(b, nqc, bq, nq, hd).transpose(1, 0, 2, 3, 4)
    sqc = seg_q.reshape(b, nqc, bq).transpose(1, 0, 2)
    kc = k.reshape(b, nkc, bk, *k.shape[2:]).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nkc, bk, *v.shape[2:]).transpose(1, 0, 2, 3, 4)
    skc = seg_k.reshape(b, nkc, bk).transpose(1, 0, 2)

    def per_q_chunk(_, xs):
        qi, q_blk, sq_blk = xs

        def per_k_chunk(carry, ys):
            kj, k_blk, v_blk, sk_blk = ys
            part = _partial_attention(
                q_blk, k_blk, v_blk, sq_blk, sk_blk,
                q_off + qi * bq, k_off + kj * bk, scale, causal,
                sliding_window)
            return _combine(carry, part), None

        # vary: mark the carry device-varying over the sharded mesh
        # axes (shard_map vma tracking; see _vary in ring_attention)
        init = (vary(jnp.full((b, nq, bq), NEG_INF, jnp.float32)),
                vary(jnp.zeros((b, nq, bq), jnp.float32)),
                vary(jnp.zeros((b, nq, bq, hd), jnp.float32)))
        (m, l, acc), _ = jax.lax.scan(
            per_k_chunk, init,
            (jnp.arange(nkc), kc, vc, skc))
        return None, (m, l, acc)

    _, (m, l, acc) = jax.lax.scan(
        per_q_chunk, None, (jnp.arange(nqc), qc, sqc))
    # [nqc, B, nq, bq(, hd)] -> [B, nq, Lq(, hd)]
    m = m.transpose(1, 2, 0, 3).reshape(b, nq, lq)
    l = l.transpose(1, 2, 0, 3).reshape(b, nq, lq)
    acc = acc.transpose(1, 2, 0, 3, 4).reshape(b, nq, lq, hd)
    return m, l, acc


def ring_attention(
    q: jnp.ndarray,        # [B, L, nq, hd] -- L sharded over `axis`
    k: jnp.ndarray,
    v: jnp.ndarray,
    seg_ids: jnp.ndarray,  # [B, L]
    mesh: Mesh,
    axis: str = "ctx",
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    block_q: int = 512,
    block_k: int = 512,
) -> jnp.ndarray:
    """Sequence-parallel attention over the given mesh axis.

    Call with GLOBAL arrays under jit; shard_map splits L over `axis`
    internally. Differentiable (shard_map + ppermute autodiff).
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    n = mesh.shape[axis]
    lc = q.shape[1] // n
    # Keep the batch and head dims sharded over their own mesh axes
    # (when present) instead of replicating them into the shard_map.
    data_ax = "data" if "data" in mesh.axis_names and mesh.shape["data"] > 1 \
        else None
    model_ax = "model" if ("model" in mesh.axis_names
                           and mesh.shape["model"] > 1
                           and q.shape[2] % mesh.shape["model"] == 0
                           and k.shape[2] % mesh.shape["model"] == 0) \
        else None

    def local_fn(q, k, v, seg):
        # local shapes: q [b_loc, Lc, nq_loc, hd], seg [b_loc, Lc]
        b, _, nq, hd = q.shape
        idx = jax.lax.axis_index(axis)
        q_off = idx * lc

        def _vary(x):
            # Mark as device-varying over every sharded axis so the
            # fori_loop carry type stays stable (shard_map vma tracking):
            # the loop body mixes in q/k/v, which vary over all of them.
            axes = tuple(a for a in (axis, data_ax, model_ax)
                         if a is not None)
            return jax.lax.pcast(x, axes, to="varying")

        m = _vary(jnp.full((b, nq, lc), NEG_INF, jnp.float32))
        lsum = _vary(jnp.zeros((b, nq, lc), jnp.float32))
        acc = _vary(jnp.zeros((b, nq, lc, hd), jnp.float32))

        # blockwise (flash-style) per-step attention once the local
        # shard outgrows one block -- long-context memory stays
        # O(block_q * block_k) per device. Blocks round down to
        # divisors of lc so the tiled path never silently degrades to
        # the dense [Lq_loc, Lk_loc] score tensor.
        bq_fit = _fit_block(lc, block_q)
        bk_fit = _fit_block(lc, block_k)
        blockwise = lc > bq_fit or lc > bk_fit

        def body(r, carry):
            m, lsum, acc, k, v, seg_k = carry
            src = (idx - r) % n  # whose KV shard we currently hold
            if blockwise:
                part = _partial_attention_blockwise(
                    q, k, v, seg, seg_k, q_off, src * lc, scale,
                    causal, sliding_window, bq_fit, bk_fit,
                    vary=_vary)
            else:
                part = _partial_attention(q, k, v, seg, seg_k, q_off,
                                          src * lc, scale, causal,
                                          sliding_window)
            m, lsum, acc = _combine((m, lsum, acc), part)
            perm = [(i, (i + 1) % n) for i in range(n)]
            k = jax.lax.ppermute(k, axis, perm)
            v = jax.lax.ppermute(v, axis, perm)
            seg_k = jax.lax.ppermute(seg_k, axis, perm)
            return m, lsum, acc, k, v, seg_k

        m, lsum, acc, _, _, _ = jax.lax.fori_loop(
            0, n, body, (m, lsum, acc, k, v, seg))
        safe = jnp.where(lsum > 0, lsum, 1.0)
        out = jnp.where((m > NEG_INF / 2)[..., None], acc / safe[..., None],
                        0.0)
        return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, Lc, nq, hd]

    spec4 = P(data_ax, axis, model_ax, None)
    spec2 = P(data_ax, axis)
    return shard_map(
        local_fn, mesh=mesh,
        in_specs=(spec4, spec4, spec4, spec2),
        out_specs=spec4,
    )(q, k, v, seg_ids)
