"""End-to-end system tests: experiment configs -> inline runner, for
SFT and the 6-MFC PPO graph, on the virtual 8-device mesh. Mirrors the
role of the reference's profile/mock system tests
(``experiments/benchmark/profile_exp.py``)."""


import numpy as np
import pytest

from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.experiments.common import apply_overrides
from realhf_tpu.experiments.dpo_exp import DPOConfig
from realhf_tpu.experiments.ppo_exp import PPOConfig
from realhf_tpu.experiments.sft_exp import SFTConfig
from realhf_tpu.parallel.mesh import ParallelismConfig


from realhf_tpu.base.testing import IntegerTokenizer

from tiny_model import TINY, write_jsonl


def FakeTokenizer():
    """Deterministic tokenizer (builtin hash() is randomized per
    process, making losses irreproducible run-to-run)."""
    return IntegerTokenizer(vocab_size=1000)




@pytest.fixture
def sft_data(tmp_path):
    rng = np.random.default_rng(0)
    path = tmp_path / "sft.jsonl"
    write_jsonl(path, [
        {"id": i,
         "prompt": " ".join(f"w{int(x)}" for x in rng.integers(0, 50, 3)),
         "answer": " " + " ".join(["good"] * int(rng.integers(2, 6)))}
        for i in range(16)])
    return str(path)


@pytest.fixture
def prompt_data(tmp_path):
    rng = np.random.default_rng(1)
    path = tmp_path / "prompts.jsonl"
    write_jsonl(path, [
        {"id": i,
         "prompt": " ".join(f"w{int(x)}" for x in rng.integers(0, 50, 4))}
        for i in range(16)])
    return str(path)


def _patch_random_models(spec, tokenizer):
    for role, mspec in spec.models.items():
        mspec.path = None
        mspec.random_init_config = dict(TINY)
        mspec.bf16 = False
        mspec.parallel = ParallelismConfig(
            data_parallel_size=2, tensor_parallel_size=4)
        if mspec.optimizer is not None:
            mspec.optimizer = OptimizerConfig(
                lr=1e-3, warmup_steps_proportion=0.0,
                lr_scheduler_type="constant")
    spec.tokenizer = tokenizer


def test_apply_overrides_nested_and_frozen():
    cfg = SFTConfig()
    apply_overrides(cfg, {
        "experiment_name": "exp1",
        "model.optimizer.lr": "3e-4",
        "model.parallel.data_parallel_size": "4",
        "dataset.train_bs_n_seqs": "32",
        "save_freq_steps": "none",
    })
    assert cfg.experiment_name == "exp1"
    assert cfg.model.optimizer.lr == 3e-4
    assert cfg.model.parallel.data_parallel_size == 4  # frozen replaced
    assert cfg.dataset.train_bs_n_seqs == 32
    assert cfg.save_freq_steps is None
    with pytest.raises(AttributeError):
        apply_overrides(cfg, {"model.nonexistent": "1"})


def test_sft_end_to_end(sft_data, tmp_path):
    from realhf_tpu.system.inline import InlineRunner

    cfg = SFTConfig(experiment_name="sfttest", trial_name="t0",
                    total_train_epochs=2)
    apply_overrides(cfg, {"dataset.path": sft_data,
                          "dataset.train_bs_n_seqs": "8",
                          "dataset.max_seqlen": "32"})
    spec = cfg.build()
    _patch_random_models(spec, FakeTokenizer())
    runner = InlineRunner(spec)
    stats = runner.run()
    assert np.isfinite(stats["trainDefault"]["loss"])
    # final save happened
    import os
    from realhf_tpu.base import constants
    assert os.path.exists(os.path.join(constants.run_save_path(),
                                       "default", "config.json"))


def test_ppo_end_to_end(prompt_data):
    from realhf_tpu.system.inline import InlineRunner

    cfg = PPOConfig(experiment_name="ppotest", trial_name="t0",
                    total_train_epochs=1, benchmark_steps=2)
    apply_overrides(cfg, {
        "dataset.path": prompt_data,
        "dataset.train_bs_n_seqs": "8",
        "dataset.max_seqlen": "16",
        "ppo.max_new_tokens": "8",
        "ppo.min_new_tokens": "1",
        "ppo.top_k": "16",
        "ppo.ppo_n_minibatches": "2",
    })
    spec = cfg.build()
    assert len(spec.mfcs) == 6
    _patch_random_models(spec, FakeTokenizer())
    runner = InlineRunner(spec)
    stats = runner.run()
    assert "actor_train" in stats and "critic_train" in stats
    assert np.isfinite(stats["actor_train"]["actor_loss"])
    assert np.isfinite(stats["critic_train"]["value_loss"])
    assert abs(stats["actor_train"]["importance_weight"] - 1.0) < 0.1


def test_dpo_end_to_end(tmp_path):
    from realhf_tpu.system.inline import InlineRunner

    rng = np.random.default_rng(2)
    path = tmp_path / "pairs.jsonl"
    write_jsonl(path, [
        {"id": i,
         "prompt": " ".join(f"w{int(x)}" for x in rng.integers(0, 50, 3)),
         "pos_answers": [" good answer here"],
         "neg_answers": [" bad reply instead"]}
        for i in range(8)])
    cfg = DPOConfig(experiment_name="dpotest", trial_name="t0",
                    total_train_epochs=1)
    apply_overrides(cfg, {"dataset.path": str(path),
                          "dataset.train_bs_n_seqs": "8",
                          "dataset.max_seqlen": "24"})
    spec = cfg.build()
    _patch_random_models(spec, FakeTokenizer())
    runner = InlineRunner(spec)
    stats = runner.run()
    assert np.isfinite(stats["actor_train"]["loss"])


def test_quickstart_cli(sft_data, monkeypatch):
    """Drive the argparse CLI surface itself (config path errors)."""
    from realhf_tpu.apps import quickstart

    with pytest.raises(ValueError):
        quickstart.parse_overrides(["no_equals_sign"])
    assert quickstart.parse_overrides(["a.b=1", "c=x"]) == {
        "a.b": "1", "c": "x"}


@pytest.mark.slow  # full trial / multi-process, ~8-20 s (CHANGES.md, PR 22)
def test_ppo_decoupled_allocation(prompt_data):
    """PPO with actor_gen and ref_inf on different layouts than the
    trainable models: weight replicas must stay in sync through
    parameter reallocation (importance ratio ~= 1 proves the generation
    replica carried the current actor weights)."""
    from realhf_tpu.system.inline import InlineRunner

    cfg = PPOConfig(experiment_name="ppodec", trial_name="t0",
                    total_train_epochs=1, benchmark_steps=2)
    apply_overrides(cfg, {
        "dataset.path": prompt_data,
        "dataset.train_bs_n_seqs": "8",
        "dataset.max_seqlen": "16",
        "ppo.max_new_tokens": "8",
        "ppo.min_new_tokens": "1",
        "ppo.ppo_n_minibatches": "2",
        "ppo.force_no_logits_mask": "true",
        "ppo.top_k": "0",   # no warping: sampled logprobs must equal
        "ppo.top_p": "1.0",  # the recomputed ones without mask replay
        "actor_gen_alloc": "d8t1",   # generation layout: pure DP
        "ref_inf_alloc": "d1t8",     # ref inference: pure TP
    })
    spec = cfg.build()
    assert set(spec.allocations) == {"actor_gen", "ref_inf"}
    _patch_random_models(spec, FakeTokenizer())
    runner = InlineRunner(spec)
    assert set(runner.replicas) == {"actor_gen", "ref_inf"}
    stats = runner.run()
    # ratio ~= 1 on each step's first minibatch requires the gen
    # replica to hold the freshly trained actor weights every step
    assert abs(stats["actor_train"]["importance_weight"] - 1.0) < 0.1
    assert runner.replica_mgr.last_reshard_secs is not None


def test_recover_resume(sft_data):
    """Interrupt an SFT run, then resume: step counters restore, the
    model reloads from the checkpoint, and already-consumed data ids
    are skipped in the interrupted epoch."""
    from realhf_tpu.base import recover
    from realhf_tpu.system.inline import InlineRunner

    def make_spec():
        cfg = SFTConfig(experiment_name="rectest", trial_name="t0",
                        total_train_epochs=2, save_freq_steps=1)
        apply_overrides(cfg, {"dataset.path": sft_data,
                              "dataset.train_bs_n_seqs": "8",
                              "dataset.max_seqlen": "32"})
        spec = cfg.build()
        _patch_random_models(spec, FakeTokenizer())
        return spec

    spec = make_spec()
    spec.ctl.benchmark_steps = 1  # simulate dying after step 1
    r1 = InlineRunner(spec, recover_mode="resume")
    r1.run()
    assert recover.exists()
    info = recover.load()
    assert info.last_step_info.global_step == 1
    consumed = set(info.hash_vals_to_ignore)
    assert len(consumed) == 8

    spec2 = make_spec()
    r2 = InlineRunner(spec2, recover_mode="resume")
    assert r2.global_step == 1
    # the recovered model came from the checkpoint (path set)
    assert spec2.models["default"].path is not None
    stats = r2.run()
    assert np.isfinite(stats["trainDefault"]["loss"])
    # epoch 0's remaining batch skipped the consumed ids
    final = recover.load()
    assert len(set(final.hash_vals_to_ignore) | consumed) >= 8


def test_ppo_auto_offload(prompt_data):
    """auto_offload: ref/reward weights live on HOST between steps
    (offload post-hook after their last MFC), and reload transparently
    on the next step's use (reference model_worker.py:542-552)."""
    from realhf_tpu.system.inline import InlineRunner

    cfg = PPOConfig(experiment_name="ppooff", trial_name="t0",
                    total_train_epochs=1, benchmark_steps=2)
    apply_overrides(cfg, {
        "dataset.path": prompt_data,
        "dataset.train_bs_n_seqs": "8",
        "dataset.max_seqlen": "16",
        "ppo.max_new_tokens": "8",
        "ppo.min_new_tokens": "1",
        "ppo.top_k": "16",
        "ppo.ppo_n_minibatches": "2",
    })
    spec = cfg.build()
    _patch_random_models(spec, FakeTokenizer())
    spec.auto_offload = True
    runner = InlineRunner(spec)
    stats = runner.run()
    # both steps finished with offload/reload cycles in between
    assert np.isfinite(stats["actor_train"]["actor_loss"])
    # non-trainable roles ended the step offloaded to host
    assert runner.models["ref"].engine.offloaded
    assert runner.models["reward"].engine.offloaded
    # trainable roles never offload
    assert not runner.models["actor"].engine.offloaded
    assert not runner.models["critic"].engine.offloaded


@pytest.mark.slow  # full trial / multi-process, ~8-20 s (CHANGES.md, PR 22)
def test_profile_mode_end_to_end():
    """Profile/mock mode (reference profile_exp.py:61): the 6-MFC PPO
    graph runs on fully synthetic data (random models + random
    prompts) through the real runtime, recording per-MFC timings."""
    from realhf_tpu.experiments.profile_exp import (
        ProfileConfig,
        mfc_timing_summary,
    )
    from realhf_tpu.system.inline import InlineRunner

    cfg = ProfileConfig(experiment_name="proftest", trial_name="t0",
                        benchmark_steps=1)
    apply_overrides(cfg, {
        "model_size": "tiny",
        "n_prompts": "8",
        "prompt_len_min": "4",
        "prompt_len_max": "8",
        "bf16": "false",
        "dataset.train_bs_n_seqs": "8",
        "ppo.max_new_tokens": "4",
        "ppo.min_new_tokens": "1",
        "ppo.force_no_logits_mask": "true",
        "ppo.top_k": "0",
        "ppo.top_p": "1.0",
        "ppo.ppo_n_minibatches": "2",
    })
    spec = cfg.build()
    assert len(spec.mfcs) == 6
    for mspec in spec.models.values():
        mspec.parallel = ParallelismConfig(data_parallel_size=2,
                                           tensor_parallel_size=4)
    runner = InlineRunner(spec)
    stats, timings = mfc_timing_summary(runner.run)
    assert np.isfinite(stats["actor_train"]["actor_loss"])
    # every MFC of the graph was timed by its mfc:* span
    assert {n.name for n in spec.mfcs} == set(timings)
    assert all(v > 0 for v in timings.values())


def test_grpo_end_to_end(prompt_data):
    """Critic-free GRPO experiment: 4-MFC graph (no value model),
    group sampling nested in batch elements, runs end to end."""
    from realhf_tpu.experiments.grpo_exp import GRPOConfig
    from realhf_tpu.system.inline import InlineRunner

    cfg = GRPOConfig(experiment_name="grpotest", trial_name="t0",
                     total_train_epochs=1, benchmark_steps=2)
    apply_overrides(cfg, {
        "dataset.path": prompt_data,
        "dataset.train_bs_n_seqs": "4",
        "dataset.max_seqlen": "16",
        "grpo.max_new_tokens": "6",
        "grpo.min_new_tokens": "1",
        "grpo.group_size": "4",
        "grpo.ppo_n_minibatches": "2",
    })
    spec = cfg.build()
    assert len(spec.mfcs) == 4
    assert "critic" not in spec.models
    _patch_random_models(spec, FakeTokenizer())
    runner = InlineRunner(spec)
    stats = runner.run()
    assert np.isfinite(stats["actor_train"]["grpo_loss"])
    assert abs(stats["actor_train"]["importance_weight"] - 1.0) < 0.1


def test_usercode_injection_custom_reward(monkeypatch):
    """REALHF_TPU_PACKAGE_PATH (reference REAL_PACKAGE_PATH +
    import_usercode): a user .py registers a custom rule-based reward
    interface that experiments can reference by name."""
    from realhf_tpu.api import model as model_api
    from realhf_tpu.api.config import ModelInterfaceAbstraction
    from realhf_tpu.api.data import SequenceSample
    from realhf_tpu.base.importing import import_usercode

    model_api.ALL_INTERFACE_CLASSES.pop("token_count_reward", None)
    monkeypatch.setenv("REALHF_TPU_PACKAGE_PATH",
                       "/root/repo/examples/custom_reward.py")
    assert import_usercode() == ["/root/repo/examples/custom_reward.py"]
    assert "token_count_reward" in model_api.ALL_INTERFACE_CLASSES

    itf = model_api.make_interface(ModelInterfaceAbstraction(
        "token_count_reward", dict(target_token=7, scale=2.0)))
    ids = np.asarray([7, 7, 1, 2, 7, 3, 5, 7, 7], np.int32)
    pm = np.asarray([1, 1, 0, 0, 0, 1, 0, 0, 0], bool)
    inp = SequenceSample.from_default(
        ids=["a", "b"], seqlens=[5, 4],
        data=dict(packed_input_ids=ids, prompt_mask=pm))
    out = itf.inference(None, inp)
    # seq a: non-prompt tokens [1, 2, 7] -> 1/3 * 2; seq b: [5, 7, 7] -> 2/3 * 2
    np.testing.assert_allclose(out.data["rewards"],
                               [2.0 / 3, 4.0 / 3], rtol=1e-6)
