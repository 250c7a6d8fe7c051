"""Engine tests: jitted train step with microbatch accumulation on the
8-device mesh (loss decreases), generation (greedy parity with the
step-by-step decode; logprob consistency with forward_logprobs), and
packing round-trips."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from realhf_tpu.api.config import ModelName
from realhf_tpu.engine import packing
from realhf_tpu.engine.engine import Engine
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.ops import functional as F
from realhf_tpu.ops.sampling import GenerationHyperparameters
from realhf_tpu.parallel.mesh import MeshContext, ParallelismConfig, make_mesh


def tiny_cfg(**kw):
    base = dict(n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
                intermediate_dim=64, vocab_size=64, apply_rotary=True,
                layer_norm_type="rms", mlp_type="llama",
                use_attention_bias=False, use_attn_proj_bias=False,
                use_mlp_bias=False, activation_function="silu",
                compute_dtype="float32")
    base.update(kw)
    return TransformerConfig(**base)


def make_engine(cfg, dp=2, tp=4, optimizer=None, seed=0):
    parallel = ParallelismConfig(data_parallel_size=dp,
                                 tensor_parallel_size=tp)
    ctx = MeshContext(ModelName("test", 0), make_mesh(parallel), parallel)
    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    return Engine(cfg, ctx, params, optimizer=optimizer,
                  total_train_steps=100)


class TestPacking:

    def test_plan_and_roundtrip(self):
        rng = np.random.default_rng(0)
        lens = rng.integers(3, 40, size=(13,)).tolist()
        info = packing.plan_packing(lens, n_streams=4, bucket=16)
        assert info.max_len % 16 == 0
        flat = rng.integers(0, 100, size=(sum(lens),)).astype(np.int32)
        arr = packing.pack_tokens(info, flat)
        assert arr.shape == (4, info.max_len)
        back = packing.unpack_tokens(info, arr)
        np.testing.assert_array_equal(back, flat)
        seg = packing.segment_ids(info)
        # each sequence's segment is consistent and unique
        assert seg.max() == 13
        for i, ln in enumerate(lens):
            s, off = info.stream[i], info.offset[i]
            assert (seg[s, off:off + ln] == i + 1).all()

    def test_pack_shorter_key(self):
        lens = [5, 7, 3, 4]
        info = packing.plan_packing(lens, n_streams=2, bucket=8)
        short = [l - 1 for l in lens]
        flat = np.arange(sum(short), dtype=np.float32)
        arr = packing.pack_tokens(info, flat, seqlens=short)
        back = packing.unpack_tokens(info, arr, seqlens=short)
        np.testing.assert_array_equal(back, flat)

    def test_balance(self):
        rng = np.random.default_rng(1)
        lens = rng.integers(10, 100, size=(64,))
        info = packing.plan_packing(lens.tolist(), n_streams=8, bucket=1)
        totals = np.zeros(8, np.int64)
        for i, ln in enumerate(lens):
            totals[info.stream[i]] += ln
        assert totals.max() - totals.min() <= lens.max()

    def test_left_padded_prompts(self):
        prompts = [np.array([1, 2, 3]), np.array([4, 5, 6, 7, 8])]
        ids, seg, pos = packing.left_padded_prompts(prompts, pad_id=0,
                                                    bucket=8)
        assert ids.shape == (2, 8)
        np.testing.assert_array_equal(ids[0, -3:], [1, 2, 3])
        np.testing.assert_array_equal(seg[0, :5], 0)
        np.testing.assert_array_equal(pos[1, -5:], np.arange(5))


class TestTrainEngine:

    def test_sft_loss_decreases(self):
        cfg = tiny_cfg()
        engine = make_engine(cfg, optimizer=OptimizerConfig(
            lr=1e-2, warmup_steps_proportion=0.0, lr_scheduler_type="constant"))

        rng = np.random.default_rng(0)
        # fixed tiny corpus packed into 2 microbatches of 2 streams
        def batch():
            ids = rng.integers(0, 64, size=(2, 2, 32)).astype(np.int32)
            seg = np.ones((2, 2, 32), np.int32)
            return [dict(input_ids=ids[i], seg_ids=seg[i]) for i in range(2)]
        mbs = batch()

        def loss_fn(params, h, mb):
            lp = F.shifted_logprobs_from_hidden(
                cfg, params, h, mb["input_ids"], mb["seg_ids"])
            valid = jnp.concatenate(
                [(mb["seg_ids"][:, 1:] != 0), jnp.zeros((2, 1), bool)], axis=1)
            loss = -(lp * valid).sum() / valid.sum()
            return loss, {"nll": loss}

        losses = [engine.train_batch(mbs, loss_fn, loss_fn_key="sft")["loss"]
                  for _ in range(15)]
        assert losses[-1] < losses[0] * 0.6, losses
        assert engine.version == 15

    def test_microbatch_equals_full_batch_grads(self):
        """1 microbatch vs 2 microbatches over the same data must give
        the same updated params (token-weighted accumulation)."""
        cfg = tiny_cfg()
        rng = np.random.default_rng(3)
        ids = rng.integers(0, 64, size=(4, 16)).astype(np.int32)
        seg = np.ones((4, 16), np.int32)

        def loss_fn(params, h, mb):
            lp = F.shifted_logprobs_from_hidden(
                cfg, params, h, mb["input_ids"], mb["seg_ids"])
            valid = mb["seg_ids"][:, 1:] != 0
            loss = -(lp[:, :-1] * valid).sum() / valid.sum()
            return loss, {}

        opt = OptimizerConfig(lr=1e-2, warmup_steps_proportion=0.0,
                              lr_scheduler_type="constant",
                              gradient_clipping=0.0)
        e1 = make_engine(cfg, optimizer=opt, seed=7)
        e2 = make_engine(cfg, optimizer=opt, seed=7)
        e1.train_batch([dict(input_ids=ids, seg_ids=seg)], loss_fn,
                       loss_fn_key="f")
        e2.train_batch(
            [dict(input_ids=ids[:2], seg_ids=seg[:2]),
             dict(input_ids=ids[2:], seg_ids=seg[2:])],
            loss_fn, loss_weights=[1.0, 1.0], loss_fn_key="f")
        for a, b in zip(jax.tree.leaves(e1.params), jax.tree.leaves(e2.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


class TestGeneration:

    def test_greedy_matches_manual_decode(self):
        cfg = tiny_cfg()
        engine = make_engine(cfg)
        prompts = [np.array([3, 5, 7]), np.array([2, 4, 6, 8, 10])]
        ids, seg, pos = packing.left_padded_prompts(prompts, pad_id=0,
                                                    bucket=8)
        g = GenerationHyperparameters(max_new_tokens=6, greedy=True)
        out = engine.generate(ids, seg, pos, jax.random.PRNGKey(0), g,
                              eos_token_id=None, pad_token_id=0)
        assert out.tokens.shape == (2, 6)
        # manual single-stream decode for prompt 1 (no padding effects)
        cfg_ids = jnp.asarray(prompts[1][None].astype(np.int32))
        h, cache = T.prefill(cfg, engine.params, cfg_ids,
                             jnp.ones_like(cfg_ids))
        cache = T.extend_kv_cache(cache, 6)
        tok = jnp.argmax(T.lm_logits(cfg, engine.params, h[:, -1]), -1)
        toks = [int(tok[0])]
        for t in range(5):
            hs, cache = T.decode_step(cfg, engine.params, cache,
                                      tok.astype(jnp.int32),
                                      jnp.array([5 + t], jnp.int32))
            tok = jnp.argmax(T.lm_logits(cfg, engine.params, hs), -1)
            toks.append(int(tok[0]))
        assert np.asarray(out.tokens)[1].tolist() == toks

    def test_eos_stops_and_pads(self):
        cfg = tiny_cfg()
        engine = make_engine(cfg)
        prompts = [np.array([3, 5, 7, 9])]
        ids, seg, pos = packing.left_padded_prompts(prompts, pad_id=0,
                                                    bucket=4)
        # find the greedy first token, then declare it the EOS token:
        g0 = GenerationHyperparameters(max_new_tokens=1, greedy=True)
        first = int(np.asarray(engine.generate(
            ids, seg, pos, jax.random.PRNGKey(0), g0,
            eos_token_id=None, pad_token_id=0).tokens)[0, 0])
        g = GenerationHyperparameters(max_new_tokens=5, greedy=True)
        out = engine.generate(ids, seg, pos, jax.random.PRNGKey(0), g,
                              eos_token_id=first, pad_token_id=63)
        toks = np.asarray(out.tokens)[0]
        assert toks[0] == first
        assert (toks[1:] == 63).all()  # padded after EOS
        assert int(out.lengths[0]) == 1
        assert not bool(out.no_eos_mask[0])

    def test_sampled_logprobs_match_recompute(self):
        """Generated-token logprobs (greedy, temp=1) must equal the
        forward_logprobs recomputation over the full sequence --
        the PPO actor_gen -> actor_inf consistency contract."""
        cfg = tiny_cfg()
        engine = make_engine(cfg)
        prompts = [np.array([3, 5, 7, 11, 13]), np.array([2, 4, 6])]
        ids, seg, pos = packing.left_padded_prompts(prompts, pad_id=0,
                                                    bucket=8)
        g = GenerationHyperparameters(max_new_tokens=4, greedy=True)
        out = engine.generate(ids, seg, pos, jax.random.PRNGKey(0), g,
                              eos_token_id=None, pad_token_id=0)
        gen_tokens = np.asarray(out.tokens)
        gen_lp = np.asarray(out.logprobs)

        for i, p in enumerate(prompts):
            full = np.concatenate([p, gen_tokens[i]]).astype(np.int32)[None]
            lp = np.asarray(engine.forward_logprobs(
                full, np.ones_like(full)))[0]
            # positions len(p)-1 .. len(p)+3-1 hold gen-token logprobs
            start = len(p) - 1
            np.testing.assert_allclose(lp[start:start + 4], gen_lp[i],
                                       rtol=2e-4, atol=2e-4)

    def test_min_new_tokens_suppresses_eos(self):
        cfg = tiny_cfg()
        engine = make_engine(cfg)
        prompts = [np.array([1, 2, 3, 4])]
        ids, seg, pos = packing.left_padded_prompts(prompts, pad_id=0,
                                                    bucket=4)
        g0 = GenerationHyperparameters(max_new_tokens=1, greedy=True)
        first = int(np.asarray(engine.generate(
            ids, seg, pos, jax.random.PRNGKey(0), g0,
            eos_token_id=None, pad_token_id=0).tokens)[0, 0])
        g = GenerationHyperparameters(max_new_tokens=4, greedy=True,
                                      min_new_tokens=3)
        out = engine.generate(ids, seg, pos, jax.random.PRNGKey(0), g,
                              eos_token_id=first, pad_token_id=63)
        toks = np.asarray(out.tokens)[0]
        assert toks[0] != first  # EOS suppressed on the first steps
        assert int(out.lengths[0]) >= 3
