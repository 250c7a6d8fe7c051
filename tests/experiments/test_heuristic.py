"""Heuristic allocation mode (reference ppo_exp.py:419): size-based
decoupled per-MFC layouts without the MCMC search."""

import numpy as np
import pytest

from realhf_tpu.api.config import ModelInterfaceType
from realhf_tpu.experiments.common import apply_overrides
from realhf_tpu.experiments.heuristic import (
    DEFAULT_HBM_BUDGET,
    apply_heuristic_allocations,
    choose_layout,
    heuristic_allocations,
)
from realhf_tpu.experiments.ppo_exp import PPOConfig
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.models.operators import n_params

LLAMA_7B = dict(n_layers=32, n_kv_heads=32, n_q_heads=32, hidden_dim=4096,
                intermediate_dim=11008, vocab_size=32000, n_positions=4096,
                apply_rotary=True, layer_norm_type="rms", mlp_type="llama",
                use_attention_bias=False, use_attn_proj_bias=False,
                use_mlp_bias=False, activation_function="silu")


def _ppo_spec(model_cfg):
    cfg = PPOConfig(experiment_name="heur", trial_name="t0")
    apply_overrides(cfg, {"dataset.path": "/dev/null",
                          "dataset.train_bs_n_seqs": "8"})
    spec = cfg.build()
    for mspec in spec.models.values():
        mspec.path = None
        mspec.random_init_config = dict(model_cfg)
    return spec


def test_choose_layout_7b():
    cfg = TransformerConfig(**LLAMA_7B)
    train = choose_layout(cfg, 8, ModelInterfaceType.TRAIN_STEP,
                          trainable=True)
    gen = choose_layout(cfg, 8, ModelInterfaceType.GENERATE,
                        trainable=False)
    inf = choose_layout(cfg, 8, ModelInterfaceType.INFERENCE,
                        trainable=False)
    # 7B + Adam state needs all 8 chips' worth of TP
    assert train.tensor_parallel_size == 8
    assert train.world_size == 8 and train.sequence_parallel
    # bf16 weights alone fit at narrower TP: generation goes DP-wide
    assert gen.tensor_parallel_size < train.tensor_parallel_size
    assert gen.data_parallel_size > 1
    assert inf.world_size == 8
    # non-train layouts fit the HBM budget by construction; the train
    # state (18 B/param) exceeds 8 v5e chips even at full TP, so the
    # planner clamps to max TP (more chips or remat/offload needed)
    for lay, mult in ((gen, 3.0), (inf, 2.4)):
        per_chip = n_params(cfg) * mult / lay.tensor_parallel_size
        assert per_chip <= DEFAULT_HBM_BUDGET


def test_ppo_decoupled_layout_on_8_devices():
    """The VERDICT acceptance: allocation_mode=heuristic produces a
    valid decoupled PPO layout on 8 devices."""
    spec = _ppo_spec(LLAMA_7B)
    primaries, overrides = heuristic_allocations(spec, 8)
    assert set(primaries) == {"actor", "critic", "ref", "reward"}
    for role, par in primaries.items():
        assert par.world_size <= 8 and par.world_size >= 1
    # the trainable actor's primary differs from its generation layout
    # => decoupled allocation with a weight replica + realloc
    assert "actor_gen" in overrides
    assert not overrides["actor_gen"].same_layout(primaries["actor"])

    apply_heuristic_allocations(spec, 8)
    assert spec.models["actor"].parallel.same_layout(primaries["actor"])
    assert spec.allocations["actor_gen"].same_layout(
        overrides["actor_gen"])


def test_small_model_collapses_to_dp():
    tiny = dict(LLAMA_7B, n_layers=2, hidden_dim=256, intermediate_dim=512,
                vocab_size=1000, n_kv_heads=4, n_q_heads=4)
    spec = _ppo_spec(tiny)
    primaries, overrides = heuristic_allocations(spec, 8)
    # everything fits on one chip: tp=1 everywhere, no replicas
    for par in primaries.values():
        assert par.tensor_parallel_size == 1
        assert par.data_parallel_size == 8
    assert overrides == {}


def test_choose_layout_70b_uses_pipeline():
    """70B training on 128 chips: 18 B/param (~1.2 TB) cannot fit at
    TP<=8 alone; the heuristic holds TP at one ICI ring and shards
    layers over pipeline stages (generation stays pp=1)."""
    cfg = TransformerConfig(
        n_layers=80, n_kv_heads=8, n_q_heads=64, hidden_dim=8192,
        intermediate_dim=28672, vocab_size=32000, n_positions=4096,
        apply_rotary=True, layer_norm_type="rms", mlp_type="llama",
        use_attention_bias=False, use_attn_proj_bias=False,
        use_mlp_bias=False, activation_function="silu")
    train = choose_layout(cfg, 128, ModelInterfaceType.TRAIN_STEP,
                          trainable=True)
    assert train.tensor_parallel_size <= 8
    assert train.pipeline_parallel_size > 1
    assert cfg.n_layers % train.pipeline_parallel_size == 0
    state_bytes = n_params(cfg) * 18
    per_chip = state_bytes / (train.tensor_parallel_size
                              * train.pipeline_parallel_size)
    assert per_chip <= DEFAULT_HBM_BUDGET
    gen = choose_layout(cfg, 128, ModelInterfaceType.GENERATE,
                        trainable=False)
    assert gen.pipeline_parallel_size == 1
