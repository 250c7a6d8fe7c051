"""End-to-end PPO with the actor TRAINING on a pipeline mesh and
rollout generation on the collapsed decode view (gen-TP override via
the allocation shorthand's "g"). Covers the full chain: PPOConfig
actor_gen_alloc="d2t2p2g4" -> parse_parallelism -> ModelHost
_install_gen_tp (same-layout + g allocation is NOT dropped) ->
Engine.decode_engine -> rollout/train weight-version tracking
(importance ratio ~= 1)."""

import json

import numpy as np
import pytest

from realhf_tpu.base.testing import IntegerTokenizer
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.experiments.common import apply_overrides
from realhf_tpu.experiments.ppo_exp import PPOConfig
from realhf_tpu.parallel.mesh import ParallelismConfig

from tiny_model import TINY


@pytest.mark.slow  # full trial / multi-process, ~8-20 s (CHANGES.md, PR 22)
def test_ppo_pp_actor_decode_view(tmp_path):
    from realhf_tpu.system.inline import InlineRunner

    rng = np.random.default_rng(1)
    path = tmp_path / "prompts.jsonl"
    with open(path, "w") as f:
        for i in range(16):
            f.write(json.dumps(
                {"id": i, "prompt": " ".join(
                    f"w{int(x)}" for x in rng.integers(0, 50, 4))}) + "\n")

    cfg = PPOConfig(experiment_name="ppgene2e", trial_name="t0",
                    total_train_epochs=1, benchmark_steps=2,
                    actor_gen_alloc="d2t2p2g4")
    apply_overrides(cfg, {
        "dataset.path": str(path),
        "dataset.train_bs_n_seqs": "8",
        "dataset.max_seqlen": "16",
        "ppo.max_new_tokens": "8",
        "ppo.min_new_tokens": "1",
        "ppo.ppo_n_minibatches": "2",
    })
    spec = cfg.build()
    for role, mspec in spec.models.items():
        mspec.path = None
        mspec.random_init_config = dict(TINY)
        mspec.bf16 = False
        if role == "actor":
            mspec.parallel = ParallelismConfig(
                data_parallel_size=2, tensor_parallel_size=2,
                pipeline_parallel_size=2)
            # free the view's second weight copy after every rollout
            # (ModelSpec knob wired through ModelHost.execute)
            mspec.drop_decode_view_after_rollout = True
        else:
            mspec.parallel = ParallelismConfig(
                data_parallel_size=2, tensor_parallel_size=4)
        if mspec.optimizer is not None:
            mspec.optimizer = OptimizerConfig(
                lr=1e-3, warmup_steps_proportion=0.0,
                lr_scheduler_type="constant")
    spec.tokenizer = IntegerTokenizer(vocab_size=1000)

    runner = InlineRunner(spec)
    stats = runner.run()
    assert np.isfinite(stats["actor_train"]["actor_loss"])
    # rollout ran with the CURRENT actor weights through the view
    assert abs(stats["actor_train"]["importance_weight"] - 1.0) < 0.1

    eng = runner.host.models["actor"].engine
    assert eng.ctx.parallel.gen_tp_size == 4  # g4 reached the engine
    view = eng._decode_view
    assert view is not None, "decode view never engaged"
    assert view.ctx.tp_size == 4 and view.ctx.dp_size == 2
    assert view.ctx.pp_size == 1
    # drop_decode_view_after_rollout: the view's weight copy was freed
    # after the last generate MFC (steady-state HBM = one copy)
    assert eng.decode_view_param_bytes() == 0
    assert view.params is None
