"""1F1B schedule correctness: golden instruction streams, numerical
equivalence vs the GPipe path and the single-mesh scan, bounded VJP
residual memory, and the schedule analytics the cost model consumes.

Runs on pp-only meshes; pp x dp/tp layout parity lives in
test_pipeline.py.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from realhf_tpu.models import sharding as shard_rules
from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.ops.moe import LOAD_STAT
from realhf_tpu.parallel import schedule as S
from realhf_tpu.parallel.mesh import ParallelismConfig, make_mesh
from realhf_tpu.parallel.pipeline import (PipelineContext,
                                          microbatch_weights)


# ----------------------------------------------------------------------
# Instruction-stream goldens (warm-up / steady / cool-down, S in {2,4},
# M in {S, 2S, 3S})
# ----------------------------------------------------------------------
def _ops(stream):
    return [(t.op, t.microbatch) for t in stream]


def test_forward_stream_golden_s2_m2():
    # T = 3 ticks; stage 0: F0 F1 drain, stage 1: bubble F0 F1
    assert _ops(S.forward_stage_stream(2, 2, 0)) == [
        ("F", 0), ("F", 1), ("NOOP", -1)]
    assert _ops(S.forward_stage_stream(2, 2, 1)) == [
        ("NOOP", -1), ("F", 0), ("F", 1)]


def test_backward_stream_golden_s2_m2():
    # the mirror: the LAST stage leads the backward pipeline
    assert _ops(S.backward_stage_stream(2, 2, 1)) == [
        ("B", 0), ("B", 1), ("NOOP", -1)]
    assert _ops(S.backward_stage_stream(2, 2, 0)) == [
        ("NOOP", -1), ("B", 0), ("B", 1)]


def test_forward_stream_golden_s4_m4_phases():
    st0 = S.forward_stage_stream(4, 4, 0)
    st3 = S.forward_stage_stream(4, 4, 3)
    assert _ops(st0) == [("F", 0), ("F", 1), ("F", 2), ("F", 3),
                         ("NOOP", -1), ("NOOP", -1), ("NOOP", -1)]
    assert _ops(st3) == [("NOOP", -1), ("NOOP", -1), ("NOOP", -1),
                         ("F", 0), ("F", 1), ("F", 2), ("F", 3)]
    # global phases: warm-up until all stages busy (t < S-1), steady
    # while every stage computes, cool-down while trailing stages drain
    assert [t.phase for t in st0] == [
        "warmup", "warmup", "warmup", "steady",
        "cooldown", "cooldown", "cooldown"]


@pytest.mark.parametrize("n_stages", [2, 4])
@pytest.mark.parametrize("mult", [1, 2, 3])
def test_stream_properties(n_stages, mult):
    m = n_stages * mult
    t_pass = S.ticks_per_pass(n_stages, m)
    for stage in range(n_stages):
        fwd = S.forward_stage_stream(n_stages, m, stage)
        bwd = S.backward_stage_stream(n_stages, m, stage)
        train = S.train_stage_stream(n_stages, m, stage)
        assert len(fwd) == len(bwd) == t_pass
        assert train == fwd + bwd
        # each stage runs each microbatch exactly once per pass, in
        # increasing order, with exactly S-1 bubble ticks
        f_mbs = [t.microbatch for t in fwd if t.op == "F"]
        b_mbs = [t.microbatch for t in bwd if t.op == "B"]
        assert f_mbs == list(range(m)) and b_mbs == list(range(m))
        assert sum(t.op == "NOOP" for t in fwd) == n_stages - 1
        # stage s leads the forward by s ticks; stage S-1-s leads the
        # backward by the same offset (reverse rotation)
        assert fwd[stage].op == "F" and fwd[stage].microbatch == 0
        rev = n_stages - 1 - stage
        assert bwd[rev].op == "B" and bwd[rev].microbatch == 0
    # cross-stage dataflow: stage s+1 consumes microbatch m exactly
    # one tick after stage s produced it (and mirrored for backward)
    for stage in range(n_stages - 1):
        a = S.forward_stage_stream(n_stages, m, stage)
        b = S.forward_stage_stream(n_stages, m, stage + 1)
        for t, tick in enumerate(a):
            if tick.op == "F":
                assert b[t + 1].microbatch == tick.microbatch


def test_analytics():
    assert S.ticks_per_pass(4, 4) == 7
    # defaults: 1F1B affords twice the microbatches -> smaller factor
    assert S.default_microbatches(4, "1f1b") == 16
    assert S.default_microbatches(4, "gpipe") == 8
    assert S.train_bubble_factor(4, schedule="1f1b") == \
        pytest.approx(19 / 16)
    assert S.train_bubble_factor(4, schedule="gpipe") == \
        pytest.approx(11 / 8)
    assert S.train_bubble_factor(1) == 1.0


def test_microbatch_weights_partial_trailing():
    # b_orig=5 streams over M=3 microbatches of Bm=2: 2+2+1 real
    w = microbatch_weights(5, 2, 3)
    np.testing.assert_allclose(w, [2 / 5, 2 / 5, 1 / 5])
    # fully padded trailing microbatch weighs zero
    np.testing.assert_allclose(microbatch_weights(4, 2, 3),
                               [0.5, 0.5, 0.0])
    assert w.dtype == np.float32


# ----------------------------------------------------------------------
# Numerical equivalence (pp-only meshes)
# ----------------------------------------------------------------------
def _cfg(**kw):
    kw.setdefault("n_layers", 4)
    kw.setdefault("n_kv_heads", 2)
    kw.setdefault("n_q_heads", 4)
    kw.setdefault("hidden_dim", 32)
    kw.setdefault("intermediate_dim", 64)
    kw.setdefault("vocab_size", 128)
    kw.setdefault("apply_rotary", True)
    kw.setdefault("layer_norm_type", "rms")
    kw.setdefault("mlp_type", "llama")
    kw.setdefault("use_attention_bias", False)
    kw.setdefault("use_attn_proj_bias", False)
    kw.setdefault("use_mlp_bias", False)
    kw.setdefault("activation_function", "silu")
    kw.setdefault("compute_dtype", "float32")
    return TransformerConfig(**kw)


def _batch(cfg, b=4, l=32, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(2, cfg.vocab_size, size=(b, l)).astype(np.int32)
    seg = np.ones((b, l), np.int32)
    seg[:, l // 2:] = 2
    seg[-1, -l // 4:] = 0
    return jnp.asarray(ids), jnp.asarray(seg)


def _pp_mesh(n_stages):
    parallel = ParallelismConfig(pipeline_parallel_size=n_stages)
    return make_mesh(parallel, devices=jax.devices("cpu")[:n_stages])


@pytest.mark.parametrize("n_stages,n_mb", [(2, 2), (2, 4), (4, 4),
                                           (4, 8)])
def test_1f1b_forward_matches_scan(n_stages, n_mb):
    cfg = _cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ids, seg = _batch(cfg)
    ref, _ = jax.jit(lambda p, i, s: T.forward(cfg, p, i, s))(
        params, ids, seg)
    mesh = _pp_mesh(n_stages)
    pipe = PipelineContext(mesh=mesh, n_stages=n_stages,
                           n_microbatches=n_mb, schedule="1f1b")
    p_sharded = jax.device_put(params,
                               shard_rules.param_shardings(cfg, mesh))
    got, _ = jax.jit(
        lambda p, i, s: T.forward(cfg, p, i, s, pipeline=pipe))(
            p_sharded, ids, seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_1f1b_grads_match_gpipe_and_scan():
    """Acceptance: 1F1B gradients numerically equivalent to the GPipe
    path (rtol <= 1e-5 on CPU), both equivalent to the single-mesh
    scan."""
    cfg = _cfg(gradient_checkpointing=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ids, seg = _batch(cfg)

    def loss(p, pipe):
        h, _ = T.forward(cfg, p, ids, seg, pipeline=pipe)
        logits = T.lm_logits(cfg, p, h)
        return (jax.nn.log_softmax(logits) ** 2).mean()

    gref = jax.jit(jax.grad(lambda p: loss(p, None)))(params)
    mesh = _pp_mesh(4)
    p_sharded = jax.device_put(params,
                               shard_rules.param_shardings(cfg, mesh))
    grads = {}
    for sched in ("gpipe", "1f1b"):
        pipe = PipelineContext(mesh=mesh, n_stages=4, n_microbatches=4,
                               schedule=sched)
        grads[sched] = jax.tree.map(
            np.asarray,
            jax.jit(jax.grad(lambda p: loss(p, pipe)))(p_sharded))
    for sched in ("gpipe", "1f1b"):
        for a, b in zip(jax.tree.leaves(grads[sched]),
                        jax.tree.leaves(gref)):
            np.testing.assert_allclose(a, np.asarray(b), rtol=1e-5,
                                       atol=1e-5)
    # and against each other, the acceptance comparison proper
    for a, b in zip(jax.tree.leaves(grads["1f1b"]),
                    jax.tree.leaves(grads["gpipe"])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_1f1b_pads_stream_remainder_and_weights_aux():
    """B not divisible by M: padded internally; MoE aux weighs real
    microbatches by their real-stream counts (the pipeline.py:122
    regression: a half-padded trailing microbatch used to count as
    full)."""
    from realhf_tpu.models.config import MoEConfig
    cfg = _cfg(mlp_type="moe",
               moe=MoEConfig(num_experts=4, top_k=2, aux_loss_coeff=0.01,
                             z_loss_coeff=0.001))
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    ids, seg = _batch(cfg, b=3)
    seg = jnp.asarray(np.ones((3, 32), np.int32))

    fwd = jax.jit(
        lambda p, i, s: T.forward(cfg, p, i, s, return_aux=True))
    ref_h, _, _ = fwd(params, ids, seg)
    # M=2 microbatches of Bm=2 streams: mb0 = streams {0,1} (2 real),
    # mb1 = stream {2} + one pad (1 real) -> weights 2/3, 1/3
    _, _, aux_a = fwd(params, ids[:2], seg[:2])
    _, _, aux_b = fwd(params, ids[2:], seg[2:])
    # the losses: the pipeline reports no load statistic
    aux_ref = {k: (2 * aux_a[k] + 1 * aux_b[k]) / 3 for k in aux_a
               if k != LOAD_STAT}
    # the OLD equal-weight semantics, to prove the fix changed them
    aux_old = {k: (aux_a[k] + aux_b[k]) / 2 for k in aux_a}

    mesh = _pp_mesh(2)
    p_sharded = jax.device_put(params,
                               shard_rules.param_shardings(cfg, mesh))
    for sched in ("gpipe", "1f1b"):
        pipe = PipelineContext(mesh=mesh, n_stages=2, n_microbatches=2,
                               schedule=sched)
        h, _, aux_pipe = jax.jit(
            lambda p, i, s: T.forward(cfg, p, i, s, return_aux=True,
                                      pipeline=pipe))(p_sharded, ids, seg)
        assert h.shape == ref_h.shape
        for k in aux_ref:
            np.testing.assert_allclose(float(aux_pipe[k]),
                                       float(aux_ref[k]),
                                       atol=1e-6, rtol=1e-5)
            # where the two semantics are distinguishable on this
            # data, the pipeline must match the stream-weighted one
            gap = abs(float(aux_ref[k]) - float(aux_old[k]))
            if gap > 1e-5:
                assert abs(float(aux_pipe[k]) - float(aux_old[k])) \
                    > gap / 2, f"{sched}/{k}: aux still equal-weighted"
        assert any(abs(float(aux_ref[k]) - float(aux_old[k])) > 1e-5
                   for k in aux_ref), "test data cannot discriminate"


# ----------------------------------------------------------------------
# Residual memory: the VJP keeps <= one full-batch boundary set per
# stage, independent of depth
# ----------------------------------------------------------------------
def test_vjp_residuals_depth_independent_via_eval_shape():
    cfg16, cfg32 = _cfg(n_layers=16), _cfg(n_layers=32)
    ids, seg = _batch(_cfg(), b=8, l=64)
    mesh = _pp_mesh(4)
    pipe = PipelineContext(mesh=mesh, n_stages=4, n_microbatches=8,
                           schedule="1f1b")
    x = jnp.zeros((8, 64, 32), jnp.float32)
    res = S.fwd_residual_shapes(pipe, x)
    # ONE boundary activation set per stage: [S, M, Bm, L, H] with
    # M * Bm == B -- total S * B * L * H, no n_layers anywhere
    assert res.shape == (4, 8, 1, 64, 32)
    assert int(np.prod(res.shape)) == 4 * 8 * 64 * 32

    # and through the real VJP: residual bytes between fwd and bwd do
    # not grow with depth (compare eval_shape of the vjp closure)
    def vjp_residual_bytes(cfg):
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        p_sh = jax.eval_shape(lambda: params)

        def run(p):
            h, _ = T.forward(cfg, p, ids, seg, pipeline=pipe)
            return (h ** 2).mean()

        # eval_shape the full grad: abstract evaluation only -- the
        # assertion is that it TRACES with the bounded custom-vjp
        # residuals (an O(T * layers) residual would still trace, so
        # the hard guarantee is the explicit buffer shape above; this
        # check pins the API end-to-end)
        out = jax.eval_shape(jax.grad(run), p_sh)
        return sum(int(np.prod(l.shape)) * l.dtype.itemsize
                   for l in jax.tree.leaves(out))

    b16 = vjp_residual_bytes(cfg16)
    b32 = vjp_residual_bytes(cfg32)
    # grad output scales with params (depth), sanity only
    assert b32 > b16


def test_vjp_saved_buffer_smaller_than_gpipe_tick_residuals():
    """The 1F1B residual buffer (S * B * L * H) is strictly smaller
    than even GPipe's best case -- the remat_tick profile saves
    (M + S - 1) tick outputs per stage vs 1F1B's M inputs."""
    Sn, M = 4, 8
    # per stage: 1F1B saves M * Bm = B boundary rows; GPipe/remat_tick
    # saves T * Bm rows with T = M + S - 1
    b_rows_1f1b = M
    b_rows_gpipe_tick = S.ticks_per_pass(Sn, M)
    assert b_rows_1f1b < b_rows_gpipe_tick


def test_engine_default_schedule_and_infer_ctx():
    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.parallel.mesh import MeshContext

    cfg = _cfg(gradient_checkpointing=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    parallel = ParallelismConfig(pipeline_parallel_size=2)
    mesh = make_mesh(parallel, devices=jax.devices("cpu")[:2])
    ctx = MeshContext(ModelName("actor", 0), mesh, parallel)
    engine = Engine(cfg, ctx, params)

    def schedule(eng, train):
        """Which schedule the engine's forward traces: only 1F1B has a
        backward pipeline of its own (a custom VJP)."""
        ids = np.ones((eng.n_streams, 8), np.int32)
        text = str(jax.make_jaxpr(
            lambda p: eng._forward(p, ids, ids, train=train)[0])(
                eng.params))
        return "1f1b" if "custom_vjp_call" in text else "gpipe"

    assert engine.n_streams == 8  # 4 * pp microbatches
    assert schedule(engine, train=True) == "1f1b"
    assert schedule(engine, train=False) == "gpipe"

    gp = dataclasses.replace(parallel, pipeline_schedule="gpipe")
    engine2 = Engine(cfg, MeshContext(ModelName("actor", 0),
                                      make_mesh(gp, jax.devices("cpu")[:2]),
                                      gp), params)
    assert engine2.n_streams == 4  # 2 * pp
    assert schedule(engine2, train=True) == "gpipe"
    assert schedule(engine2, train=False) == "gpipe"

    with pytest.raises(ValueError):
        ParallelismConfig(pipeline_schedule="zigzag")


def test_sft_trains_on_pp_only_mesh_1f1b():
    """End-to-end on the old-jax-safe pp-only mesh: SFT train_step
    through the 1F1B schedule decreases the loss; inference logprobs
    run through the GPipe context on the same engine."""
    from realhf_tpu.api import model as model_api
    from realhf_tpu.api.config import ModelName
    from realhf_tpu.api.data import SequenceSample
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.engine.optim import OptimizerConfig
    from realhf_tpu.interfaces.sft import SFTInterface
    from realhf_tpu.parallel.mesh import MeshContext

    cfg = _cfg(gradient_checkpointing=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    parallel = ParallelismConfig(pipeline_parallel_size=2)
    mesh = make_mesh(parallel, devices=jax.devices("cpu")[:2])
    ctx = MeshContext(ModelName("actor", 0), mesh, parallel)
    engine = Engine(cfg, ctx, params,
                    optimizer=OptimizerConfig(
                        lr=1e-3, warmup_steps_proportion=0.0,
                        lr_scheduler_type="constant"),
                    total_train_steps=10)
    model = model_api.Model(ModelName("actor", 0), engine, None)

    rng = np.random.default_rng(0)
    n_seqs = 16
    seqlens = [int(x) for x in rng.integers(8, 25, size=n_seqs)]
    flat = np.concatenate([rng.integers(2, cfg.vocab_size, size=l)
                           for l in seqlens]).astype(np.int32)
    pmask = np.concatenate([
        np.concatenate([np.ones(2, bool), np.zeros(l - 2, bool)])
        for l in seqlens])
    batch = SequenceSample.from_default(
        ids=list(range(n_seqs)), seqlens=seqlens,
        data=dict(packed_input_ids=flat, prompt_mask=pmask))
    s1 = SFTInterface().train_step(model, batch)
    s2 = SFTInterface().train_step(model, batch)
    assert np.isfinite(s1["loss"]) and s2["loss"] < s1["loss"]

    lp = engine.forward_logprobs(
        np.tile(flat[:32], (2, 1)).astype(np.int32),
        np.ones((2, 32), np.int32))
    assert np.asarray(lp).shape == (2, 32)
