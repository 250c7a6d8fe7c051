"""Fused minibatch loop (Engine.train_minibatches): N sequential
optimizer steps inside one jitted dispatch must match the same
sequence of train_batch calls exactly -- update order, gradient
weighting, stats, and early-stop skip semantics."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from realhf_tpu.api.config import ModelName
from realhf_tpu.engine.engine import Engine
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.ops import functional as F
from realhf_tpu.parallel.mesh import (
    MeshContext,
    ParallelismConfig,
    make_mesh,
)


def tiny_cfg():
    return TransformerConfig(
        n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
        intermediate_dim=64, vocab_size=64, apply_rotary=True,
        layer_norm_type="rms", mlp_type="llama",
        use_attention_bias=False, use_attn_proj_bias=False,
        use_mlp_bias=False, activation_function="silu",
        compute_dtype="float32")


def make_engine(cfg, seed=0):
    parallel = ParallelismConfig(data_parallel_size=2,
                                 tensor_parallel_size=4)
    ctx = MeshContext(ModelName("fuse", 0), make_mesh(parallel), parallel)
    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    return Engine(cfg, ctx, params,
                  optimizer=OptimizerConfig(lr=1e-3,
                                            warmup_steps_proportion=0.0,
                                            lr_scheduler_type="constant"),
                  total_train_steps=100)


def sft_loss(cfg):
    def loss_fn(p, h, mb):
        lp = F.shifted_logprobs_from_hidden(cfg, p, h, mb["input_ids"],
                                            mb["seg_ids"])
        return -lp.mean(), {"nll": -lp.mean()}
    return loss_fn


def make_minibatches(cfg, n_minibatch=3, n_mbs=2, s=2, l=16, seed=0):
    rng = np.random.default_rng(seed)
    return [
        [dict(input_ids=rng.integers(2, cfg.vocab_size,
                                     size=(s, l)).astype(np.int32),
              seg_ids=np.ones((s, l), np.int32))
         for _ in range(n_mbs)]
        for _ in range(n_minibatch)
    ]


class TestFusedMinibatchParity:

    def test_params_and_stats_match_sequential(self):
        cfg = tiny_cfg()
        loss_fn = sft_loss(cfg)
        mbs = make_minibatches(cfg)
        weights = [[3.0, 1.0] for _ in mbs]

        seq_engine = make_engine(cfg)
        seq_stats = [seq_engine.train_batch(m, loss_fn, loss_weights=w,
                                            loss_fn_key="sft")
                     for m, w in zip(mbs, weights)]

        fused_engine = make_engine(cfg)
        fused_stats = fused_engine.train_minibatches(
            mbs, loss_fn, loss_weights=weights, loss_fn_key="sft")

        assert len(fused_stats) == len(seq_stats)
        for a, b in zip(seq_stats, fused_stats):
            assert set(a) == set(b)
            for k in a:
                assert np.isclose(a[k], b[k], rtol=1e-5, atol=1e-6), \
                    (k, a[k], b[k])
        for pa, pb in zip(jax.tree.leaves(seq_engine.params),
                          jax.tree.leaves(fused_engine.params)):
            np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                       rtol=1e-5, atol=1e-6)
        assert fused_engine.version == seq_engine.version == len(mbs)

    def test_single_minibatch_delegates_to_train_batch(self):
        cfg = tiny_cfg()
        loss_fn = sft_loss(cfg)
        mbs = make_minibatches(cfg, n_minibatch=1)
        eng = make_engine(cfg)
        out = eng.train_minibatches(mbs, loss_fn, loss_fn_key="sft")
        assert len(out) == 1 and np.isfinite(out[0]["loss"])
        assert eng.version == 1

    def test_early_stop_skip_applies_per_minibatch(self):
        # minibatch 0 skips (params unchanged by it), minibatch 1
        # applies: fused must equal sequential under the reserved
        # __skip_update__ stat
        cfg = tiny_cfg()

        def loss_fn(p, h, mb):
            lp = F.shifted_logprobs_from_hidden(
                cfg, p, h, mb["input_ids"], mb["seg_ids"])
            loss = -lp.mean()
            skip = (mb["skip_flag"].sum() > 0).astype(jnp.float32)
            return loss, {"__skip_update__": skip}

        mbs = make_minibatches(cfg, n_minibatch=2, n_mbs=2)
        for i, group in enumerate(mbs):
            for mb in group:
                mb["skip_flag"] = np.full((2, 16), 1 - i, np.float32)

        seq_engine = make_engine(cfg)
        seq_stats = [seq_engine.train_batch(m, loss_fn, loss_fn_key="es")
                     for m in mbs]
        fused_engine = make_engine(cfg)
        fused_stats = fused_engine.train_minibatches(mbs, loss_fn,
                                                     loss_fn_key="es")
        assert seq_stats[0]["early_stop_skipped"] == 1.0
        assert fused_stats[0]["early_stop_skipped"] == 1.0
        assert fused_stats[1]["early_stop_skipped"] == 0.0
        for pa, pb in zip(jax.tree.leaves(seq_engine.params),
                          jax.tree.leaves(fused_engine.params)):
            np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                       rtol=1e-5, atol=1e-6)
