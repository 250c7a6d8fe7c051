"""Steady-state 1F1B-class pipeline schedule.

TPU-native counterpart of the reference's TrainSchedule
(``realhf/impl/model/parallelism/pipeline_parallel/static_schedule.py:319``):
explicit per-tick forward/backward instruction streams -- warm-up,
steady body, cool-down -- instead of differentiating through the GPipe
rotation scan (parallel/pipeline.py). Three things change versus GPipe
autodiff:

1. **Explicit backward pipeline.** ``pipeline_blocks_1f1b`` wraps the
   pipelined forward in a ``jax.custom_vjp``; the backward runs as its
   own scan over M + S - 1 ticks in the REVERSE rotation direction
   (stage s handles microbatch m at tick ``m + (S-1-s)``), recomputing
   each stage-tick forward from the saved stage input and applying the
   cotangent with ``jax.vjp`` -- the instruction-stream structure of
   TrainSchedule's BackwardPass/SendGrad/RecvGrad, expressed as one
   reverse ``lax.ppermute`` per tick.

2. **1F1B-class residual memory.** The forward saves ONLY each stage's
   microbatch INPUT boundary activations: one ``[M, Bm, L, H]``
   buffer per stage == exactly one full-batch boundary activation set
   (M * Bm == B), independent of BOTH the tick count and the stage
   depth. GPipe autodiff instead saves O(T) per-tick residuals --
   whole per-block activation sets unless ``pipeline_remat="tick"``
   stacks a second checkpoint level. Because the residual total does
   not grow with M, the microbatch count can rise to shrink the
   bubble: the engine defaults to M = 4*pp here vs 2*pp for GPipe
   (bubble overhead (S-1)/M halves).

3. **Masked bubble ticks.** Warm-up/cool-down ticks on inactive stages
   run a ``lax.cond`` no-op branch instead of computing garbage the
   way the GPipe scan does. Per pass, each stage computes exactly M
   stage-steps instead of M + S - 1 (a (S-1)/(M+S-1) FLOP saving;
   on lockstep silicon it returns energy/HBM slack rather than
   wall-clock).

The schedule needs the same mesh contract as GPipe: blocks sharded
P("pipe") on the leading layer axis, activations pipe-replicated,
manual over "pipe" only (parallel/smap.py).
Rotary phase inputs (cos/sin) receive zero cotangents -- they are
functions of integer positions, so no real gradient path exists
through them.
"""

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from realhf_tpu.parallel.mesh import PIPE_AXIS

GPIPE = "gpipe"
ONE_F_ONE_B = "1f1b"
SCHEDULES = (GPIPE, ONE_F_ONE_B)

# ----------------------------------------------------------------------
# Instruction streams (pure python -- golden-testable, drive the docs
# and the bench's analytic bubble accounting; the scans below realize
# exactly these streams via index arithmetic)
# ----------------------------------------------------------------------
WARMUP = "warmup"
STEADY = "steady"
COOLDOWN = "cooldown"


@dataclasses.dataclass(frozen=True)
class Tick:
    """One (stage, tick) instruction: op "F"/"B" on a microbatch, or a
    masked "NOOP" bubble tick."""
    op: str               # "F" | "B" | "NOOP"
    microbatch: int       # -1 for NOOP
    phase: str            # warmup | steady | cooldown


def _phase_of(t: int, n_stages: int, n_microbatches: int) -> str:
    """Global phase of pass-tick t: warm-up until every stage has
    work, steady while all S stages compute, cool-down while the
    trailing stages drain."""
    if t < n_stages - 1:
        return WARMUP
    if t < n_microbatches:
        return STEADY
    return COOLDOWN


def forward_stage_stream(n_stages: int, n_microbatches: int,
                         stage: int) -> List[Tick]:
    """Per-tick instructions of one stage for the forward pass
    (M + S - 1 ticks; stage s runs F(m) at tick m + s)."""
    out = []
    for t in range(n_microbatches + n_stages - 1):
        m = t - stage
        phase = _phase_of(t, n_stages, n_microbatches)
        if 0 <= m < n_microbatches:
            out.append(Tick("F", m, phase))
        else:
            out.append(Tick("NOOP", -1, phase))
    return out


def backward_stage_stream(n_stages: int, n_microbatches: int,
                          stage: int) -> List[Tick]:
    """Backward-pass instructions (M + S - 1 ticks): the mirror
    pipeline -- stage s runs B(m) at tick m + (S - 1 - stage), so the
    LAST stage leads and input-cotangents rotate backwards."""
    rev = n_stages - 1 - stage
    out = []
    for t in range(n_microbatches + n_stages - 1):
        m = t - rev
        phase = _phase_of(t, n_stages, n_microbatches)
        if 0 <= m < n_microbatches:
            out.append(Tick("B", m, phase))
        else:
            out.append(Tick("NOOP", -1, phase))
    return out


def train_stage_stream(n_stages: int, n_microbatches: int,
                       stage: int) -> List[Tick]:
    """Full train-step stream: forward pass then backward pass
    (2 * (M + S - 1) ticks). The backward cannot begin before the last
    forward output's cotangent exists (it comes from the head/loss
    OUTSIDE the pipeline), so the two passes concatenate rather than
    interleave; the 1F1B property lives in the backward's own
    warm-up/steady/cool-down structure and the bounded residuals."""
    return (forward_stage_stream(n_stages, n_microbatches, stage)
            + backward_stage_stream(n_stages, n_microbatches, stage))


def train_schedule(n_stages: int, n_microbatches: int) -> List[List[Tick]]:
    """All stages' train streams (index = stage)."""
    return [train_stage_stream(n_stages, n_microbatches, s)
            for s in range(n_stages)]


# ----------------------------------------------------------------------
# Analytics (consumed by search/engine.py's cost model)
# ----------------------------------------------------------------------
def default_microbatches(pp: int, schedule: str = ONE_F_ONE_B) -> int:
    """Engine default microbatch count. 1F1B holds one full-batch
    boundary activation set per stage REGARDLESS of M, so it can
    afford twice GPipe's microbatch count and halve the (S-1)/M
    bubble overhead; GPipe autodiff residuals grow with the tick
    count, so it stays at 2*pp."""
    return 4 * pp if schedule == ONE_F_ONE_B else 2 * pp


def ticks_per_pass(n_stages: int, n_microbatches: int) -> int:
    return n_microbatches + n_stages - 1


def train_bubble_factor(pp: int, n_mb: Optional[int] = None,
                        schedule: str = ONE_F_ONE_B) -> float:
    """Wall-clock multiplier over perfect pipeline scaling for one
    train step: (M + pp - 1) / M at the schedule's (default)
    microbatch count. The schedules share the per-M formula; they
    differ through the M each can afford (see default_microbatches),
    which is what re-ranks pp candidates in the allocation search."""
    if pp <= 1:
        return 1.0
    m = n_mb or default_microbatches(pp, schedule)
    return (m + pp - 1) / m


# ----------------------------------------------------------------------
# The pipelined forward with an explicit 1F1B backward
# ----------------------------------------------------------------------
def pipeline_blocks_1f1b(
    pipe,                           # parallel.pipeline.PipelineContext
    blocks: Any,                    # stacked pytree, leading dim n_layers
    n_layers: int,
    x,                              # [B, L, H] residual after embedding
    seg_ids,                        # [B, L] int
    cos,                            # [B, L, hd/2]
    sin,                            # [B, L, hd/2]
    block_step,                     # (slab, layer_ids, x, seg, cos, sin)
                                    #   -> (y, aux_scalars_dict)
    return_aux: bool = False,
):
    """Run the block stack as a 1F1B-scheduled pipeline; returns
    (hidden, aux) exactly like ``pipeline.pipeline_blocks``.

    Differentiable via a custom VJP: the forward saves one stage-input
    boundary activation per microbatch (``[M, Bm, L, H]`` per stage ==
    one full-batch set); the backward is its own reverse-rotation scan
    that recomputes each tick's forward from that input (block-level
    ``jax.checkpoint`` inside ``block_step`` still bounds the
    transient per-tick memory). Aux losses are weighted by each
    microbatch's REAL stream count, so a partially-padded trailing
    microbatch contributes proportionally (same semantics as the
    GPipe path after the ISSUE 6 fix).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from realhf_tpu.parallel import smap
    from realhf_tpu.parallel.pipeline import (microbatch_weights,
                                              pad_streams)

    S, M = pipe.n_stages, pipe.n_microbatches
    assert n_layers % S == 0, (n_layers, S)
    per_stage = n_layers // S

    (x, seg_ids, cos, sin), b_orig = pad_streams(
        [x, seg_ids, cos, sin], M)
    B, L, H = x.shape
    Bm = B // M
    T = ticks_per_pass(S, M)
    mb_w = jnp.asarray(microbatch_weights(b_orig, Bm, M))  # [M] f32

    # Aux output structure of one stage-step, needed to build the
    # cond's zero branch and the custom_vjp cotangent structure.
    slab_s = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((per_stage,) + a.shape[1:],
                                       a.dtype), blocks)
    _, aux_shapes = jax.eval_shape(
        block_step, slab_s,
        jax.ShapeDtypeStruct((per_stage,), jnp.int32),
        jax.ShapeDtypeStruct((Bm, L, H), x.dtype),
        jax.ShapeDtypeStruct((Bm, L), seg_ids.dtype),
        jax.ShapeDtypeStruct((Bm, L, cos.shape[-1]), cos.dtype),
        jax.ShapeDtypeStruct((Bm, L, sin.shape[-1]), sin.dtype))
    aux_keys = sorted(aux_shapes)

    def _mb_split(a):
        """[B, ...] -> pipe-varying [M, Bm, ...] (stages index their
        own microbatch with a stage-varying index)."""
        return smap.to_varying(a.reshape(M, Bm, *a.shape[1:]))

    def _pick(a, m):
        import jax as _jax
        return _jax.lax.dynamic_index_in_dim(a, m, 0, keepdims=False)

    @partial(smap.pipe_shard_map, mesh=pipe.mesh,
             in_specs=(P(PIPE_AXIS), P(None), P(None), P(None), P(None),
                       P(None)),
             out_specs=(P(PIPE_AXIS), P(), P(PIPE_AXIS)))
    def fwd_run(blocks_l, xr, seg, cosr, sinr, w):
        idx = jax.lax.axis_index(PIPE_AXIS)
        layer_ids = idx * per_stage + jnp.arange(per_stage,
                                                 dtype=jnp.int32)
        mbs_x, mbs_seg = _mb_split(xr), _mb_split(seg)
        mbs_cos, mbs_sin = _mb_split(cosr), _mb_split(sinr)
        wv = smap.to_varying(w)
        state0 = smap.to_varying(jnp.zeros((Bm, L, H), xr.dtype))
        xsave0 = smap.to_varying(jnp.zeros((M, Bm, L, H), xr.dtype))
        outbuf0 = smap.to_varying(jnp.zeros((M, Bm, L, H), xr.dtype))
        aux0 = {k: smap.to_varying(
            jnp.zeros(aux_shapes[k].shape, aux_shapes[k].dtype))
            for k in aux_keys}

        def compute(m, xin):
            return block_step(blocks_l, layer_ids, xin,
                              _pick(mbs_seg, m), _pick(mbs_cos, m),
                              _pick(mbs_sin, m))

        def tick(carry, t):
            state, xsave, outbuf, aux_acc = carry
            m = jnp.clip(t - idx, 0, M - 1)
            valid = ((t - idx) >= 0) & ((t - idx) < M)
            inj = _pick(mbs_x, m)
            xin = jnp.where(idx == 0, inj, state)
            xsave = jax.lax.dynamic_update_index_in_dim(
                xsave, jnp.where(valid, xin, _pick(xsave, m)), m, 0)
            y, aux = jax.lax.cond(
                valid, lambda xc: compute(m, xc),
                lambda xc: (jnp.zeros_like(xc), aux0), xin)
            # real-stream aux weight of this tick's microbatch (zero
            # contribution on bubble ticks: aux is already zeroed)
            wt = _pick(wv, m)
            aux_acc = {k: aux_acc[k] + aux[k] * wt for k in aux_keys}
            outbuf = jax.lax.dynamic_update_index_in_dim(
                outbuf,
                jnp.where((idx == S - 1) & valid, y, _pick(outbuf, m)),
                m, 0)
            nxt = jax.lax.ppermute(
                y, PIPE_AXIS, [(i, (i + 1) % S) for i in range(S)])
            return (nxt, xsave, outbuf, aux_acc), None

        (_, xsave, outbuf, aux_acc), _ = jax.lax.scan(
            tick, (state0, xsave0, outbuf0, aux0), jnp.arange(T))
        # sorted: one psum per aux key, same order on every stage
        # (det-unsorted-iter)
        aux_tot = {k: jax.lax.psum(v, PIPE_AXIS)
                   for k, v in sorted(aux_acc.items())}
        return outbuf[None], aux_tot, xsave[None]

    @partial(smap.pipe_shard_map, mesh=pipe.mesh,
             in_specs=(P(PIPE_AXIS), P(PIPE_AXIS), P(None), P(None),
                       P(None), P(None), P(PIPE_AXIS), P(None)),
             out_specs=(P(PIPE_AXIS), P(PIPE_AXIS)))
    def bwd_run(blocks_l, xsave_l, seg, cosr, sinr, w, g_l, g_aux):
        idx = jax.lax.axis_index(PIPE_AXIS)
        rev = (S - 1) - idx
        layer_ids = idx * per_stage + jnp.arange(per_stage,
                                                 dtype=jnp.int32)
        mbs_seg = _mb_split(seg)
        mbs_cos, mbs_sin = _mb_split(cosr), _mb_split(sinr)
        wv = smap.to_varying(w)
        xsave = xsave_l[0]
        g_loc = g_l[0]
        g_aux_v = {k: smap.to_varying(g_aux[k]) for k in aux_keys}
        gstate0 = smap.to_varying(jnp.zeros((Bm, L, H), g_l.dtype))
        dblk0 = jax.tree.map(jnp.zeros_like, blocks_l)
        dxbuf0 = smap.to_varying(jnp.zeros((M, Bm, L, H), g_l.dtype))

        def tick(carry, t):
            gstate, dblk, dxbuf = carry
            m = jnp.clip(t - rev, 0, M - 1)
            valid = ((t - rev) >= 0) & ((t - rev) < M)
            gy = jnp.where(idx == S - 1, _pick(g_loc, m), gstate)
            xin = _pick(xsave, m)
            wt = _pick(wv, m)
            g_aux_t = {k: g_aux_v[k] * wt for k in aux_keys}

            def live(op):
                xin, gy, g_aux_t = op

                def f(blk, xi):
                    return block_step(blk, layer_ids, xi,
                                      _pick(mbs_seg, m),
                                      _pick(mbs_cos, m),
                                      _pick(mbs_sin, m))

                _, vjp_fn = jax.vjp(f, blocks_l, xin)
                return vjp_fn((gy, g_aux_t))

            def dead(op):
                return (jax.tree.map(jnp.zeros_like, blocks_l),
                        jnp.zeros_like(op[0]))

            dblk_t, dx_t = jax.lax.cond(valid, live, dead,
                                        (xin, gy, g_aux_t))
            dblk = jax.tree.map(jnp.add, dblk, dblk_t)
            dxbuf = jax.lax.dynamic_update_index_in_dim(
                dxbuf,
                jnp.where((idx == 0) & valid, dx_t, _pick(dxbuf, m)),
                m, 0)
            nxt = jax.lax.ppermute(
                dx_t, PIPE_AXIS, [(i, (i - 1) % S) for i in range(S)])
            return (nxt, dblk, dxbuf), None

        (_, dblk, dxbuf), _ = jax.lax.scan(
            tick, (gstate0, dblk0, dxbuf0), jnp.arange(T))
        return dblk, dxbuf[None]

    def _primal(blocks, xp, segp, cosp, sinp):
        outs, aux, _ = fwd_run(blocks, xp, segp, cosp, sinp, mb_w)
        return outs, aux

    pipelined = jax.custom_vjp(_primal)

    def _fwd(blocks, xp, segp, cosp, sinp):
        outs, aux, xsave = fwd_run(blocks, xp, segp, cosp, sinp, mb_w)
        return (outs, aux), (blocks, xsave, segp, cosp, sinp)

    def _bwd(res, g):
        g_outs, g_aux = g
        blocks_r, xsave, segp, cosp, sinp = res
        dblocks, dxbuf = bwd_run(blocks_r, xsave, segp, cosp, sinp,
                                 mb_w, g_outs, g_aux)
        dx = dxbuf[0].reshape(B, L, H)
        # integer segments carry float0 cotangents; rotary phases are
        # functions of integer positions -- no gradient path exists
        dseg = np.zeros(segp.shape, jax.dtypes.float0)
        return (dblocks, dx, dseg, jnp.zeros_like(cosp),
                jnp.zeros_like(sinp))

    pipelined.defvjp(_fwd, _bwd)

    outs, aux = pipelined(blocks, x, seg_ids, cos, sin)
    hidden = outs[S - 1].reshape(B, L, H)[:b_orig]
    if return_aux:
        return hidden, aux
    return hidden, {}


def fwd_residual_shapes(pipe, x) -> Dict[str, Any]:
    """``jax.eval_shape`` view of what the 1F1B VJP keeps resident
    between forward and backward beyond the (replicated) original
    inputs: the saved stage-input buffer, ``[S, M, Bm, L, H]`` == one
    full-batch boundary activation set per stage -- independent of
    n_layers and of the tick count. Exposed for the
    peak-residual-memory test."""
    import jax

    from realhf_tpu.parallel.pipeline import pad_streams

    S, M = pipe.n_stages, pipe.n_microbatches

    def residuals(x):
        (xp,), _ = pad_streams([x], M)
        B, L, H = xp.shape
        return jax.numpy.zeros((S, M, B // M, L, H), xp.dtype)

    return jax.eval_shape(residuals, x)
