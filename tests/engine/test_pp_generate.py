"""Generation on pipeline- and context-parallel meshes.

The reference streams tokens through PP stages at decode time
(``realhf/impl/model/parallelism/pipeline_parallel/static_schedule.py:195``
GenerateSchedule, ``backend/pipe_runner.py:847``). The TPU-first
equivalent (engine.decode_engine) reshards the weights onto a collapsed
dp x tp mesh over the same devices and decodes there; these tests pin

  - token/logprob parity between a PP engine's generate and a plain
    dp/tp engine holding the same weights,
  - the same for a ctx (ring-attention) mesh and for gen_tp_size
    overriding the decode tp degree,
  - weight-version tracking: after a train step or set_params the view
    decodes with the NEW weights,
  - the inflight-batching path building its generator from the view.
"""

import numpy as np
import pytest

import jax

from realhf_tpu.api.config import ModelName
from realhf_tpu.engine import packing
from realhf_tpu.engine.engine import Engine
from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.ops.sampling import GenerationHyperparameters
from realhf_tpu.parallel.mesh import (
    MeshContext,
    ParallelismConfig,
    make_mesh,
    parse_parallelism,
)


def tiny_cfg(**kw):
    base = dict(n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
                intermediate_dim=64, vocab_size=64, apply_rotary=True,
                layer_norm_type="rms", mlp_type="llama",
                use_attention_bias=False, use_attn_proj_bias=False,
                use_mlp_bias=False, activation_function="silu",
                compute_dtype="float32")
    base.update(kw)
    return TransformerConfig(**base)


def make_engine(cfg, parallel, optimizer=None, seed=0):
    ctx = MeshContext(ModelName("test", 0), make_mesh(parallel), parallel)
    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    return Engine(cfg, ctx, params, optimizer=optimizer,
                  total_train_steps=10)


def greedy_gcfg(max_new=8):
    return GenerationHyperparameters(max_new_tokens=max_new,
                                     min_new_tokens=1, greedy=True)


def prompts_small(n=4, lo=3, hi=9):
    rng = np.random.default_rng(0)
    return [rng.integers(1, 60, size=(int(l),)).astype(np.int32)
            for l in rng.integers(lo, hi, size=(n,))]


def run_generate(eng, prompts, gcfg):
    ids, seg, pos = packing.left_padded_prompts(prompts, pad_id=0)
    out = eng.generate(ids, seg, pos, jax.random.PRNGKey(7), gcfg,
                       eos_token_id=None, pad_token_id=0)
    return (np.asarray(out.tokens), np.asarray(out.logprobs),
            np.asarray(out.lengths))


class TestDecodeView:

    def test_pp_generate_matches_dense(self):
        cfg = tiny_cfg()
        prompts = prompts_small()
        gcfg = greedy_gcfg()
        ref = make_engine(cfg, ParallelismConfig(
            data_parallel_size=4, tensor_parallel_size=2))
        pp = make_engine(cfg, ParallelismConfig(
            data_parallel_size=2, tensor_parallel_size=2,
            pipeline_parallel_size=2))
        rt, rl, rn = run_generate(ref, prompts, gcfg)
        pt, pl, pn = run_generate(pp, prompts, gcfg)
        # identical weights + greedy + identical collapsed layout
        np.testing.assert_array_equal(rn, pn)
        np.testing.assert_array_equal(rt, pt)
        np.testing.assert_allclose(rl, pl, atol=1e-5)
        view = pp.decode_engine()
        assert view is not pp
        assert view.ctx.pp_size == 1
        assert view.ctx.dp_size == 4 and view.ctx.tp_size == 2
        # second call reuses the cached view (no rebuild)
        assert pp.decode_engine() is view

    def test_ctx_generate_matches_dense(self):
        cfg = tiny_cfg()
        prompts = prompts_small()
        gcfg = greedy_gcfg()
        ref = make_engine(cfg, ParallelismConfig(
            data_parallel_size=4, tensor_parallel_size=2))
        cp = make_engine(cfg, ParallelismConfig(
            data_parallel_size=2, tensor_parallel_size=2,
            context_parallel_size=2))
        rt, _, rn = run_generate(ref, prompts, gcfg)
        ct, _, cn = run_generate(cp, prompts, gcfg)
        np.testing.assert_array_equal(rn, cn)
        np.testing.assert_array_equal(rt, ct)

    def test_gen_tp_size_override(self):
        cfg = tiny_cfg()
        prompts = prompts_small()
        gcfg = greedy_gcfg()
        pp = make_engine(cfg, ParallelismConfig(
            data_parallel_size=2, tensor_parallel_size=2,
            pipeline_parallel_size=2, gen_tp_size=4))
        view = pp.decode_engine()
        assert view.ctx.tp_size == 4 and view.ctx.dp_size == 2
        ref = make_engine(cfg, ParallelismConfig(
            data_parallel_size=2, tensor_parallel_size=4))
        rt, _, _ = run_generate(ref, prompts, gcfg)
        pt, _, _ = run_generate(pp, prompts, gcfg)
        np.testing.assert_array_equal(rt, pt)

    def test_gen_tp_on_plain_mesh(self):
        """g on a dp/tp mesh (no pp/ctx) is honored, not ignored:
        decode runs on a view at the requested tp."""
        cfg = tiny_cfg()
        prompts = prompts_small()
        gcfg = greedy_gcfg()
        eng = make_engine(cfg, ParallelismConfig(
            data_parallel_size=4, tensor_parallel_size=2, gen_tp_size=4))
        view = eng.decode_engine()
        assert view is not eng
        assert view.ctx.tp_size == 4 and view.ctx.dp_size == 2
        ref = make_engine(cfg, ParallelismConfig(
            data_parallel_size=2, tensor_parallel_size=4))
        rt, _, _ = run_generate(ref, prompts, gcfg)
        et, _, _ = run_generate(eng, prompts, gcfg)
        np.testing.assert_array_equal(rt, et)

    def test_view_tracks_weight_updates(self):
        """set_params (the realloc / cross-group install landing point)
        replaces the params pytree; the next generate must decode with
        the NEW weights through the SAME cached view object."""
        cfg = tiny_cfg()
        prompts = prompts_small()
        gcfg = greedy_gcfg()
        pp = make_engine(cfg, ParallelismConfig(
            data_parallel_size=2, tensor_parallel_size=2,
            pipeline_parallel_size=2))
        t0, _, _ = run_generate(pp, prompts, gcfg)
        view0 = pp.decode_engine()

        fresh = jax.tree.map(np.asarray,
                             T.init_params(cfg, jax.random.PRNGKey(5)))
        pp.set_params(fresh)
        t1, _, _ = run_generate(pp, prompts, gcfg)
        assert pp.decode_engine() is view0
        ref = make_engine(cfg, ParallelismConfig(
            data_parallel_size=4, tensor_parallel_size=2), seed=5)
        rt, _, _ = run_generate(ref, prompts, gcfg)
        np.testing.assert_array_equal(rt, t1)
        assert (t0 != t1).any()  # different weights, different tokens

    def test_inflight_on_pp_mesh(self):
        cfg = tiny_cfg()
        prompts = prompts_small()
        gcfg = GenerationHyperparameters(
            max_new_tokens=6, min_new_tokens=1, greedy=True,
            force_no_logits_mask=True)
        pp = make_engine(cfg, ParallelismConfig(
            data_parallel_size=2, tensor_parallel_size=2,
            pipeline_parallel_size=2))
        eng = pp.decode_engine()
        gen = eng.inflight_generator(
            gcfg, n_slots=2, max_prompt_len=16, eos_token_id=None,
            pad_token_id=0)
        finished = gen.generate_all(prompts, jax.random.PRNGKey(3))
        assert len(finished) == len(prompts)
        ref = make_engine(cfg, ParallelismConfig(
            data_parallel_size=4, tensor_parallel_size=2))
        rt, _, rn = run_generate(ref, prompts, gcfg)
        by_idx = {f.request_id: f for f in finished}
        for i in range(len(prompts)):
            g = int(rn[i])
            np.testing.assert_array_equal(
                np.asarray(by_idx[i].tokens[:g]), rt[i, :g])


def test_parse_gen_tp():
    p = parse_parallelism("d2t2p2g4")
    assert p.gen_tp_size == 4 and p.pipeline_parallel_size == 2
    assert "g4" in str(p)
    assert parse_parallelism("d4t2").gen_tp_size == 0


class TestDropDecodeView:

    def test_drop_frees_and_rebuilds(self):
        """drop_decode_view frees the view's weight copy (bytes -> 0);
        the next rollout reshards and decodes identically."""
        cfg = tiny_cfg()
        prompts = prompts_small()
        gcfg = greedy_gcfg()
        eng = make_engine(cfg, ParallelismConfig(
            data_parallel_size=2, pipeline_parallel_size=2,
            tensor_parallel_size=2))
        assert eng.decode_view_param_bytes() == 0  # lazy: no view yet
        tok1, lp1, _ = run_generate(eng, prompts, gcfg)
        held = eng.decode_view_param_bytes()
        assert held > 0
        # mesh-wide: one logical copy replicated over the view's dp
        # groups (d4t2 view on the 8-device d2p2t2 mesh -> 4x)
        logical = sum(l.size * l.dtype.itemsize
                      for l in jax.tree.leaves(eng.params))
        assert eng._decode_view.ctx.dp_size == 4
        assert held == logical * 4

        eng.drop_decode_view()
        assert eng.decode_view_param_bytes() == 0
        assert eng._decode_view.params is None

        tok2, lp2, _ = run_generate(eng, prompts, gcfg)  # reshards
        assert eng.decode_view_param_bytes() == held
        np.testing.assert_array_equal(tok1, tok2)
        np.testing.assert_allclose(lp1, lp2, rtol=1e-5, atol=1e-6)

    def test_drop_noop_on_plain_mesh(self):
        """dp/tp meshes decode in place: nothing to drop, no error."""
        cfg = tiny_cfg()
        eng = make_engine(cfg, ParallelismConfig(
            data_parallel_size=4, tensor_parallel_size=2))
        run_generate(eng, prompts_small(), greedy_gcfg())
        assert eng.decode_view_param_bytes() == 0
        eng.drop_decode_view()
