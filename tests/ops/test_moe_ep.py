"""Expert parallelism: expert weights E-sharded over the data axis.

Exceeds the reference (its MoETokenDispatcher says "Currently does not
support expert parallel", token_dispatcher.py:26-27): EP on the TPU
framework is a sharding layout, and the GShard dispatch/combine
einsums become all-to-alls inserted by GSPMD. These tests pin

- numerical parity of the EP forward/backward with the replicated
  capacity dispatch (same params, same batch, 8-device dp4 x tp2 mesh
  vs single device),
- that the expert weights are actually placed over the data axis,
- an end-to-end SFT train step on an EP mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from realhf_tpu.api.config import ModelName
from realhf_tpu.engine.engine import Engine
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.models import sharding as shard_rules
from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import MoEConfig, TransformerConfig
from realhf_tpu.parallel.mesh import MeshContext, ParallelismConfig, make_mesh


def ep_cfg(expert_parallel=True, capacity=2.0):
    return TransformerConfig(
        n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
        intermediate_dim=64, vocab_size=128, apply_rotary=True,
        layer_norm_type="rms", mlp_type="moe", use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu", compute_dtype="float32",
        moe=MoEConfig(num_experts=8, top_k=2, capacity_factor=capacity,
                      aux_loss_coeff=0.01, z_loss_coeff=0.001,
                      use_grouped_gemm=False, expert_parallel=expert_parallel))


def make_engine(cfg, parallel, name="ep", train=False):
    devices = jax.devices("cpu")[:parallel.world_size]
    mesh = make_mesh(parallel, devices=devices)
    ctx = MeshContext(ModelName(name, 0), mesh, parallel)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    opt = OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0,
                          lr_scheduler_type="constant") if train else None
    return Engine(cfg, ctx, params, optimizer=opt,
                  total_train_steps=10 if train else None)


def batch(cfg, n_streams=4, length=32, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(2, cfg.vocab_size, size=(n_streams, length)) \
        .astype(np.int32)
    seg = np.ones((n_streams, length), np.int32)
    seg[:, length - 4:] = 0  # trailing pad exercises valid masking
    ids[seg == 0] = 0
    return ids, seg


class TestExpertParallel:

    def test_pspec_places_experts_on_data_axis(self):
        cfg = ep_cfg()
        specs = shard_rules.param_pspecs(cfg)
        assert specs["blocks"]["mlp"]["wg"] == P(None, "data", None, "model")
        assert specs["blocks"]["mlp"]["wd"] == P(None, "data", "model", None)
        specs_rep = shard_rules.param_pspecs(ep_cfg(expert_parallel=False))
        assert specs_rep["blocks"]["mlp"]["wg"] == \
            P(None, None, None, "model")

    def test_ep_forward_matches_replicated(self):
        """dp4 x tp2 EP logprobs == single-device capacity dispatch."""
        cfg = ep_cfg()
        ep_engine = make_engine(
            cfg, ParallelismConfig(data_parallel_size=4,
                                   tensor_parallel_size=2))
        # expert weights must live on the data axis
        wg = ep_engine.params["blocks"]["mlp"]["wg"]
        assert wg.sharding.spec[1] == "data", wg.sharding
        ref_engine = make_engine(ep_cfg(expert_parallel=False),
                                 ParallelismConfig(), name="rep")
        ids, seg = batch(cfg)
        lp_ep = np.asarray(ep_engine.forward_logprobs(ids, seg))
        lp_ref = np.asarray(ref_engine.forward_logprobs(ids, seg))
        np.testing.assert_allclose(lp_ep, lp_ref, rtol=2e-4, atol=2e-5)

    def test_ep_train_step(self):
        """One SFT train step on the EP mesh: finite loss, params move,
        and the step matches the replicated engine's."""
        cfg = ep_cfg()
        ep_engine = make_engine(
            cfg, ParallelismConfig(data_parallel_size=4,
                                   tensor_parallel_size=2), train=True)
        ref_engine = make_engine(ep_cfg(expert_parallel=False),
                                 ParallelismConfig(), name="rep",
                                 train=True)
        ids, seg = batch(cfg)

        def loss_fn_for(engine):
            cfg_ = engine.cfg
            from realhf_tpu.ops import functional as F

            def loss_fn(p, h, mb):
                lp = F.shifted_logprobs_from_hidden(
                    cfg_, p, h, mb["input_ids"], mb["seg_ids"])
                seg_ = mb["seg_ids"]
                valid = jnp.concatenate(
                    [(seg_[:, 1:] == seg_[:, :-1]) & (seg_[:, 1:] != 0),
                     jnp.zeros_like(seg_[:, :1], bool)], axis=1)
                nll = -(lp * valid).sum() / jnp.maximum(valid.sum(), 1)
                return nll, {"nll": nll}

            return loss_fn

        mb = dict(input_ids=ids, seg_ids=seg)
        s_ep = ep_engine.train_batch([mb], loss_fn_for(ep_engine),
                                     loss_fn_key="ep")
        s_ref = ref_engine.train_batch([mb], loss_fn_for(ref_engine),
                                       loss_fn_key="rep")
        assert np.isfinite(s_ep["loss"])
        np.testing.assert_allclose(s_ep["loss"], s_ref["loss"],
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(s_ep["nll"], s_ref["nll"],
                                   rtol=2e-4, atol=2e-5)

    def test_ep_rejects_ragged_and_bad_divisibility(self):
        cfg = ep_cfg(capacity=None)
        cfg.moe.use_grouped_gemm = True
        if hasattr(jax.lax, "ragged_dot"):
            with pytest.raises(ValueError, match="expert_parallel"):
                make_engine(cfg, ParallelismConfig(data_parallel_size=4,
                                                   tensor_parallel_size=2))
        cfg3 = ep_cfg()
        cfg3.moe = MoEConfig(num_experts=6, top_k=2, capacity_factor=2.0,
                             use_grouped_gemm=False, expert_parallel=True)
        with pytest.raises(ValueError, match="divisible"):
            make_engine(cfg3, ParallelismConfig(data_parallel_size=4,
                                                tensor_parallel_size=2))
