"""Fused ring attention: one Pallas kernel per device with the KV
ring riding inter-chip RDMA (``pltpu.make_async_remote_copy``)
overlapped against flash compute.

The shard_map/ppermute formulation (``ops/ring_attention.py``) leaves
the comm/compute overlap to XLA's scheduler and re-enters jitted
glue between rounds. Here one kernel owns the whole ring: KV shards
live in a double-buffered HBM slab, each round's send to the right
neighbor is issued BEFORE the round's flash compute so the transfer
hides behind it, and slot reuse is fenced by a neighbor handshake
(regular semaphore: a receiver frees a slot only after its own reads
AND its forwarding send of that slot have completed). Per-round
compute is the same tiled online-softmax (flash-2 schedule, GQA,
packed-segment + causal + sliding-window masks on GLOBAL positions)
as ``ops/flash_attention.py``.

By default the ring is BIDIRECTIONAL: each device's KV shard splits
into two halves that counter-rotate (dir 0 rightward, dir 1
leftward), so both ICI ring directions carry traffic and per-round
transfer time halves -- the full-bisection-bandwidth pattern. Falls
back to one direction when a half-shard would not tile.

Ring choreography per device and direction (n = ring size,
slot = r % 2):

  round r first cell:  r==0: neighbor barrier (all members entered)
                       r>0:  wait recv[slot]  (this round's KV landed)
                             wait send[1-slot] (our r-1 send drained)
                             signal LEFT: "my slot 1-slot is free"
                       r<n-1: (r>0: wait RIGHT's free signal)
                              start RDMA kbuf/vbuf/segk[slot] ->
                              right neighbor's [1-slot]
  every cell:          local DMA of this (batch, kv-head) KV slice
                       HBM slab -> VMEM, flash-accumulate the q tile
  round n-1:           normalize and write o

Cross-round accumulator state (m / l / unnormalized acc) persists in
unblocked HBM slabs (``pl.ANY`` outputs) moved by explicit local DMAs
each cell -- Mosaic's output pipeline forbids revisiting blocked
output windows across non-adjacent grid cells, and these are the same
bytes the shard_map formulation carries through its fori_loop anyway.

Interpret-mode tested on the virtual CPU mesh (remote DMAs + remote
semaphore signals are emulated by ``pltpu.InterpretParams``); real
multi-chip validation pending hardware (docs/PARITY.md).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from realhf_tpu.ops.ring_attention import ring_attention

NEG_INF = -2.0 ** 30
LANES = 128
SUBLANES = 8


def _fit_block(lc: int, block: int) -> int:
    b = min(block, lc)
    while lc % b:
        b -= 1
    if b < 8:
        # a silent mis-grid (empty q dimension / dropped tail tokens)
        # would return uninitialized output -- refuse instead
        raise ValueError(
            f"local context shard of {lc} tokens has no >=8 tile "
            f"divisor <= {block}; pad the sequence or adjust the "
            "ctx degree for ring_attention_fused.")
    return b


def _ring_kernel(q_ref, segq_ref,                     # blocked inputs
                 kin_ref, vin_ref, segin_ref,         # ANY inputs
                 o_ref,                                # ANY output
                 kbuf_ref, vbuf_ref, segk_ref,        # ANY ring slabs
                 m_ref, l_ref, acc_ref,               # ANY state slabs
                 k_vmem, v_vmem, sk_vmem,             # VMEM KV scratch
                 m_vmem, l_vmem, acc_vmem, o_vmem,    # VMEM state
                 kv_sems,                              # local KV copies
                 misc_sems,                            # state/out copies
                 send_sems, recv_sems,                 # RDMA [3, 2, nd]
                 free_sems,                            # handshake [nd]
                 *, n: int, axis: str, bq: int, bk: int, group: int,
                 n_dirs: int, scale: float, causal: bool,
                 sliding_window: Optional[int]):
    r = pl.program_id(0)
    bi = pl.program_id(1)
    hk = pl.program_id(2)
    qi = pl.program_id(3)
    n_qb = pl.num_programs(3)
    my = jax.lax.axis_index(axis)
    right = jax.lax.rem(my + 1, n)
    left = jax.lax.rem(my + n - 1, n)
    slot = jax.lax.rem(r, 2)
    nxt = 1 - slot
    lch = k_vmem.shape[1]                  # per-direction shard length
    lc = lch * n_dirs

    # direction d sends to send_to[d]; the device that sends TO us in
    # direction d is from_of[d] (dir 0 rotates right, dir 1 left)
    send_to = [right, left]
    from_of = [left, right]

    first_cell = jnp.logical_and(
        jnp.logical_and(bi == 0, hk == 0), qi == 0)

    def slab_rdma(d, slot_src, slot_dst, sem_i):
        """RDMA descriptors for direction d's three ring slabs."""
        return [
            pltpu.make_async_remote_copy(
                src_ref=src.at[d, slot_src], dst_ref=src.at[d, slot_dst],
                send_sem=send_sems.at[i, sem_i, d],
                recv_sem=recv_sems.at[i, sem_i, d],
                device_id={axis: send_to[d]},
                device_id_type=pltpu.DeviceIdType.MESH)
            for i, src in enumerate((kbuf_ref, vbuf_ref, segk_ref))
        ]

    # ---- round bookkeeping (once per round) --------------------------
    @pl.when(jnp.logical_and(first_cell, r == 0))
    def _round0_setup():
        # every ring member must have entered the kernel (allocated
        # its slabs) before anyone RDMAs into it
        bar = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(bar, inc=1, device_id={axis: left},
                               device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_signal(bar, inc=1, device_id={axis: right},
                               device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(bar, 2)
        # local KV halves -> ring slot 0 (what round 0 sends from)
        cps = [pltpu.make_async_copy(src.at[d], dst.at[d, 0],
                                     kv_sems.at[i, d])
               for d in range(n_dirs)
               for i, (src, dst) in enumerate(
                   ((kin_ref, kbuf_ref), (vin_ref, vbuf_ref),
                    (segin_ref, segk_ref)))]
        for c in cps:
            c.start()
        for c in cps:
            c.wait()

    @pl.when(jnp.logical_and(first_cell, r > 0))
    def _round_start():
        # this round's KV landed in [slot]; our forwarding sends of
        # [nxt] (issued in round r-1 from slot (r-1)%2 == nxt) have
        # drained, so each direction's sender may overwrite [nxt]
        for d in range(n_dirs):
            for desc in slab_rdma(d, nxt, slot, slot):
                desc.wait()

        @pl.when(r < n - 1)
        def _free_slots():
            # matched by each sender's _wait_free at its round r
            # (sends happen at rounds 0..n-2); an unguarded signal at
            # round n-1 would leave the semaphores non-zero at exit
            for d in range(n_dirs):
                pltpu.semaphore_signal(
                    free_sems.at[d], inc=1,
                    device_id={axis: from_of[d]},
                    device_id_type=pltpu.DeviceIdType.MESH)

    @pl.when(jnp.logical_and(first_cell, r < n - 1))
    def _round_send():
        # overlap: the sends for round r+1 fly while round r computes
        for d in range(n_dirs):
            @pl.when(r > 0)
            def _wait_free(d=d):
                pltpu.semaphore_wait(free_sems.at[d], 1)

            for desc in slab_rdma(d, slot, nxt, nxt):
                desc.start()

    # ---- this cell's KV slices: HBM slabs -> VMEM --------------------
    kv_cps = [c for d in range(n_dirs) for c in (
        pltpu.make_async_copy(kbuf_ref.at[d, slot, bi, hk],
                              k_vmem.at[d], kv_sems.at[0, d]),
        pltpu.make_async_copy(vbuf_ref.at[d, slot, bi, hk],
                              v_vmem.at[d], kv_sems.at[1, d]),
        pltpu.make_async_copy(segk_ref.at[d, slot, bi],
                              sk_vmem.at[d], kv_sems.at[2, d]),
    )]
    for c in kv_cps:
        c.start()

    # ---- cross-round accumulator state: HBM slab -> VMEM -------------
    @pl.when(r > 0)
    def _load_state():
        cps = [
            pltpu.make_async_copy(
                m_ref.at[bi, hk, :, pl.ds(qi * bq, bq)], m_vmem,
                misc_sems.at[0]),
            pltpu.make_async_copy(
                l_ref.at[bi, hk, :, pl.ds(qi * bq, bq)], l_vmem,
                misc_sems.at[1]),
            pltpu.make_async_copy(
                acc_ref.at[bi, hk, :, pl.ds(qi * bq, bq)], acc_vmem,
                misc_sems.at[2]),
        ]
        for c in cps:
            c.start()
        for c in cps:
            c.wait()

    @pl.when(r == 0)
    def _init_state():
        m_vmem[...] = jnp.full(m_vmem.shape, NEG_INF, jnp.float32)
        l_vmem[...] = jnp.zeros(l_vmem.shape, jnp.float32)
        acc_vmem[...] = jnp.zeros(acc_vmem.shape, jnp.float32)

    for c in kv_cps:
        c.wait()

    # ---- flash-accumulate this q tile vs each direction's shard ------
    q_off = my * (n_qb * bq) + qi * bq
    seg_q = segq_ref[0, :, 0]              # [bq]
    n_kb = lch // bk

    for g in range(group):
        q = q_ref[0, 0, g].astype(jnp.float32) * scale     # [bq, hd]
        hd = q.shape[-1]
        carry = (m_vmem[g], l_vmem[g], acc_vmem[g])

        for d in range(n_dirs):
            # dir 0 holds the [0:lch] half of shard (my - r) % n;
            # dir 1 the [lch:lc] half of shard (my + r) % n
            src_dev = jax.lax.rem(my - r + n, n) if d == 0 \
                else jax.lax.rem(my + r, n)
            k_off = src_dev * lc + d * lch

            def body(j, carry, q=q, d=d, k_off=k_off):
                m, l_sum, acc = carry
                k = k_vmem[d, pl.ds(j * bk, bk), :].astype(jnp.float32)
                v = v_vmem[d, pl.ds(j * bk, bk), :]
                seg_k = sk_vmem[d, 0, pl.ds(j * bk, bk)]
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [bq, bk]
                qg = q_off + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 0)
                kg = (k_off + j * bk
                      + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
                mask = (seg_q[:, None] == seg_k[None, :]) \
                    & (seg_q[:, None] != 0)
                if causal:
                    mask &= qg >= kg
                if sliding_window is not None:
                    mask &= (qg - kg) < sliding_window
                s = jnp.where(mask, s, NEG_INF)
                m_new = jnp.maximum(m, s.max(axis=1))
                p = jnp.exp(s - m_new[:, None])
                alpha = jnp.exp(m - m_new)
                l_new = l_sum * alpha + p.sum(axis=1)
                acc_new = acc * alpha[:, None] + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return m_new, l_new, acc_new

            carry = jax.lax.fori_loop(0, n_kb, body, carry)

        m, l_sum, acc = carry
        m_vmem[g] = m
        l_vmem[g] = l_sum
        acc_vmem[g] = acc

        @pl.when(r == n - 1)
        def _finalize(m=m, l_sum=l_sum, acc=acc, g=g):
            row_valid = m > NEG_INF / 2
            safe_l = jnp.where(l_sum > 0, l_sum, 1.0)
            out = jnp.where(row_valid[:, None], acc / safe_l[:, None],
                            0.0)
            o_vmem[g] = out.astype(o_vmem.dtype)

    # ---- state / output: VMEM -> HBM slabs ---------------------------
    @pl.when(r < n - 1)
    def _store_state():
        cps = [
            pltpu.make_async_copy(
                m_vmem, m_ref.at[bi, hk, :, pl.ds(qi * bq, bq)],
                misc_sems.at[0]),
            pltpu.make_async_copy(
                l_vmem, l_ref.at[bi, hk, :, pl.ds(qi * bq, bq)],
                misc_sems.at[1]),
            pltpu.make_async_copy(
                acc_vmem, acc_ref.at[bi, hk, :, pl.ds(qi * bq, bq)],
                misc_sems.at[2]),
        ]
        for c in cps:
            c.start()
        for c in cps:
            c.wait()

    @pl.when(r == n - 1)
    def _store_out():
        cp = pltpu.make_async_copy(
            o_vmem, o_ref.at[bi, hk, :, pl.ds(qi * bq, bq)],
            misc_sems.at[3])
        cp.start()
        cp.wait()


def _plan_dirs(lc: int, block_k: int, want_bidir: bool):
    """(n_dirs, lch, bk): split the local shard across both ICI ring
    directions when each half still tiles; else one direction."""
    if want_bidir and lc % 2 == 0 and lc // 2 >= 8:
        try:
            return 2, lc // 2, _fit_block(lc // 2, block_k)
        except ValueError:
            pass  # the half has no tileable block; the full shard may
    return 1, lc, _fit_block(lc, block_k)


def _fused_local(q, k, v, seg, *, mesh, axis, n, scale, causal,
                 sliding_window, bq, bk, n_dirs, lch, interpret,
                 collective_id):
    """Per-device body under shard_map. Local shapes:
    q [b, lc, nq, hd], k/v [b, lc, nkv, hd], seg [b, lc]."""
    b, lc, nq, hd = q.shape
    nkv = k.shape[2]
    group = nq // nkv
    n_qb = lc // bq

    qt = q.transpose(0, 2, 1, 3).reshape(b, nkv, group, lc, hd)
    segq = jnp.broadcast_to(seg[:, :, None], (b, lc, LANES))
    # dir-major KV halves: [nd, b, nkv, lch, hd] (contiguous split of
    # the sequence dim; nd == 1 keeps the whole shard in "half" 0)
    kt = k.transpose(0, 2, 1, 3).reshape(
        b, nkv, n_dirs, lch, hd).transpose(2, 0, 1, 3, 4)
    vt = v.transpose(0, 2, 1, 3).reshape(
        b, nkv, n_dirs, lch, hd).transpose(2, 0, 1, 3, 4)
    segk = jnp.broadcast_to(seg[:, None, :], (b, SUBLANES, lc)).reshape(
        b, SUBLANES, n_dirs, lch).transpose(2, 0, 1, 3)

    grid = (n, b, nkv, n_qb)
    kernel = functools.partial(
        _ring_kernel, n=n, axis=axis, bq=bq, bk=bk, group=group,
        n_dirs=n_dirs, scale=scale, causal=causal,
        sliding_window=sliding_window)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, group, bq, hd),
                         lambda r, bi, hk, qi: (bi, hk, 0, qi, 0)),
            pl.BlockSpec((1, bq, LANES),
                         lambda r, bi, hk, qi: (bi, qi, 0)),  # segq
            any_spec, any_spec, any_spec,        # local k / v / segk
        ],
        out_shape=(
            # o + ring slabs + cross-round state, all manually DMA'd
            jax.ShapeDtypeStruct((b, nkv, group, lc, hd), q.dtype),
            jax.ShapeDtypeStruct((n_dirs, 2) + kt.shape[1:], kt.dtype),
            jax.ShapeDtypeStruct((n_dirs, 2) + vt.shape[1:], vt.dtype),
            jax.ShapeDtypeStruct((n_dirs, 2) + segk.shape[1:],
                                 segk.dtype),
            jax.ShapeDtypeStruct((b, nkv, group, lc), jnp.float32),
            jax.ShapeDtypeStruct((b, nkv, group, lc), jnp.float32),
            jax.ShapeDtypeStruct((b, nkv, group, lc, hd), jnp.float32),
        ),
        out_specs=(any_spec,) * 7,
        scratch_shapes=[
            pltpu.VMEM((n_dirs, lch, hd), k.dtype),     # k slices
            pltpu.VMEM((n_dirs, lch, hd), v.dtype),     # v slices
            pltpu.VMEM((n_dirs, SUBLANES, lch), seg.dtype),
            pltpu.VMEM((group, bq), jnp.float32),       # m
            pltpu.VMEM((group, bq), jnp.float32),       # l
            pltpu.VMEM((group, bq, hd), jnp.float32),   # acc
            pltpu.VMEM((group, bq, hd), q.dtype),       # out tile
            pltpu.SemaphoreType.DMA((3, n_dirs)),       # local KV
            pltpu.SemaphoreType.DMA((4,)),              # state / out
            pltpu.SemaphoreType.DMA((3, 2, n_dirs)),    # RDMA send
            pltpu.SemaphoreType.DMA((3, 2, n_dirs)),    # RDMA recv
            pltpu.SemaphoreType.REGULAR((n_dirs,)),     # slot free
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id),
        interpret=(pltpu.InterpretParams() if interpret else False),
        name="ring_attn_fused",
    )(qt, segq, kt, vt, segk)

    o = out[0].reshape(b, nq, lc, hd).transpose(0, 2, 1, 3)
    return o.astype(q.dtype)


def ring_attention_fused(
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
    block_q: int = 256,
    block_k: int = 512,
    bidirectional: bool = True,
    interpret: bool = False,
    collective_id: int = 7,
) -> jnp.ndarray:
    """Drop-in for :func:`ring_attention` with the fused-RDMA kernel
    on the forward pass. Differentiable: the backward delegates to the
    shard_map/ppermute formulation's VJP (recompute-based -- the same
    work gradient checkpointing already schedules), so gradients are
    bit-identical to the unfused path while the forward gains the
    overlapped ring.

    ``bidirectional`` (default): each device's KV shard splits in two
    halves that counter-rotate (dir 0 rightward, dir 1 leftward), so
    both ICI ring directions carry traffic and per-round transfer time
    halves; falls back to one direction when a half would not tile.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    n = mesh.shape[axis]
    if n == 1:
        return ring_attention(q, k, v, seg_ids, mesh, axis,
                              causal=causal, scale=scale,
                              sliding_window=sliding_window)
    lc = q.shape[1] // n
    bq = _fit_block(lc, block_q)
    n_dirs, lch, bk = _plan_dirs(lc, block_k, bidirectional)

    data_ax = "data" if "data" in mesh.axis_names \
        and mesh.shape["data"] > 1 else None
    model_ax = "model" if ("model" in mesh.axis_names
                           and mesh.shape["model"] > 1
                           and q.shape[2] % mesh.shape["model"] == 0
                           and k.shape[2] % mesh.shape["model"] == 0) \
        else None
    spec4 = P(data_ax, axis, model_ax, None)
    spec2 = P(data_ax, axis)

    local = functools.partial(
        _fused_local, mesh=mesh, axis=axis, n=n, scale=scale,
        causal=causal, sliding_window=sliding_window, bq=bq, bk=bk,
        n_dirs=n_dirs, lch=lch, interpret=interpret,
        collective_id=collective_id)
    fused_fwd = shard_map(local, mesh=mesh,
                          in_specs=(spec4, spec4, spec4, spec2),
                          out_specs=spec4, check_vma=False)

    @jax.custom_vjp
    def attn(q, k, v, seg):
        return fused_fwd(q, k, v, seg)

    def attn_fwd(q, k, v, seg):
        return fused_fwd(q, k, v, seg), (q, k, v, seg)

    def attn_bwd(res, g):
        q, k, v, seg = res
        _, vjp = jax.vjp(
            lambda q_, k_, v_: ring_attention(
                q_, k_, v_, seg, mesh, axis, causal=causal,
                scale=scale, sliding_window=sliding_window,
                block_q=block_q, block_k=block_k),
            q, k, v)
        return (*vjp(g), None)

    attn.defvjp(attn_fwd, attn_bwd)
    return attn(q, k, v, seg_ids)
