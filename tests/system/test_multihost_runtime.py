"""Multi-host model workers: one role's mesh spanning TWO worker
processes that form a jax.distributed world (the reference's
multi-node model: one NCCL world, a model sharded over several
ModelWorkers, global_comm.py:44). Worker group [0, 1] hosts the SFT
role on a d2t4 mesh -- data parallelism across the two processes
(DCN), tensor parallelism within each process's 4 virtual CPU devices
(ICI) -- driven end-to-end by the master over ZMQ: collective train
steps, a collective checkpoint gather, leader-reply protocol."""

import os

import numpy as np
import pytest

from realhf_tpu.base.testing import IntegerTokenizer
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.experiments.common import apply_overrides
from realhf_tpu.experiments.sft_exp import SFTConfig
from realhf_tpu.parallel.mesh import ParallelismConfig

from tiny_model import TINY, write_jsonl

# each worker process gets 4 virtual CPU devices; the 2-process world
# has 8 global devices for the d2t4 mesh
WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    "REALHF_TPU_LOCAL_DEVICE_COUNT": "4",
    "PYTHONPATH": "/root/repo",
}




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


@pytest.mark.slow  # multi-process trial, >15 s alone (CHANGES.md, PR 22)
def test_sft_worker_group_spanning_two_processes(sft_data):
    from realhf_tpu.apps.main import main_start
    from realhf_tpu.base import constants

    cfg = SFTConfig(experiment_name="mhsft", trial_name="t0",
                    total_train_epochs=1)
    apply_overrides(cfg, {"dataset.path": sft_data,
                          "dataset.train_bs_n_seqs": "8",
                          "dataset.max_seqlen": "32"})
    spec = cfg.build()
    for role, mspec in spec.models.items():
        mspec.path = None
        mspec.random_init_config = dict(TINY)
        mspec.bf16 = False
        # dp across the two worker processes, tp within each
        mspec.parallel = ParallelismConfig(
            data_parallel_size=2, tensor_parallel_size=4,
            sequence_parallel=True)
        if mspec.optimizer is not None:
            mspec.optimizer = OptimizerConfig(
                lr=1e-3, warmup_steps_proportion=0.0,
                lr_scheduler_type="constant")
    spec.tokenizer = IntegerTokenizer()
    spec.n_model_workers = 2
    spec.worker_assignment = {"default": [0, 1]}
    assert spec.multihost

    out = main_start(spec, env=WORKER_ENV, timeout=900)
    assert out["complete"]
    assert out["global_step"] == 2  # 16 samples / bs 8
    assert np.isfinite(out["stats"]["trainDefault"]["loss"])
    # STREAMED collective checkpoint (VERDICT r4 #5): per-layer
    # gathers both members joined, leader-only writes, one safetensors
    # shard per layer (+1 for embeddings/head) and streamed opt state
    save_dir = os.path.join(constants.run_save_path(), "default")
    assert os.path.exists(os.path.join(save_dir, "config.json"))
    shards = [f for f in os.listdir(save_dir)
              if f.endswith(".safetensors")]
    assert len(shards) == TINY["n_layers"] + 1, shards
    assert os.path.exists(os.path.join(save_dir, "optimizer_state.npz"))


@pytest.mark.slow  # multi-process trial, >15 s alone (CHANGES.md, PR 22)
def test_ppo_actor_group_with_single_worker_roles(tmp_path):
    """The 6-MFC PPO graph with the ACTOR spanning a 2-process worker
    group (d2t4 over 8 global devices) while critic/ref/reward stay on
    single workers: grouped GENERATION (identical sampling keys from
    the shared seed on both members), data-plane flow from the group
    leader to single-worker roles, grouped train steps, and mixed
    group/non-group dispatch in one trial."""
    from realhf_tpu.apps.main import main_start
    from realhf_tpu.experiments.ppo_exp import PPOConfig

    rng = np.random.default_rng(1)
    data = tmp_path / "prompts.jsonl"
    write_jsonl(data, [
        {"id": i,
         "prompt": " ".join(f"w{int(x)}" for x in rng.integers(0, 50, 4))}
        for i in range(16)])

    cfg = PPOConfig(experiment_name="mhppo", trial_name="t0",
                    total_train_epochs=1, benchmark_steps=2)
    apply_overrides(cfg, {
        "dataset.path": str(data),
        "dataset.train_bs_n_seqs": "8",
        "dataset.max_seqlen": "16",
        "ppo.max_new_tokens": "8",
        "ppo.min_new_tokens": "1",
        "ppo.top_k": "16",
        "ppo.ppo_n_minibatches": "2",
    })
    spec = cfg.build()
    assert len(spec.mfcs) == 6
    for role, mspec in spec.models.items():
        mspec.path = None
        mspec.random_init_config = dict(TINY)
        mspec.bf16 = False
        if role == "actor":  # spans the 2-process group
            mspec.parallel = ParallelismConfig(
                data_parallel_size=2, tensor_parallel_size=4)
        else:  # single-worker roles use that worker's 4 local devices
            mspec.parallel = ParallelismConfig(
                data_parallel_size=2, tensor_parallel_size=2)
        if mspec.optimizer is not None:
            mspec.optimizer = OptimizerConfig(
                lr=1e-3, warmup_steps_proportion=0.0,
                lr_scheduler_type="constant")
    spec.tokenizer = IntegerTokenizer()
    spec.n_model_workers = 2
    spec.worker_assignment = {"actor": [0, 1], "critic": 0, "ref": 1,
                              "reward": 1}
    assert spec.multihost

    out = main_start(spec, env=WORKER_ENV, timeout=1800)
    assert out["complete"]
    assert out["global_step"] == 2
    stats = out["stats"]
    assert np.isfinite(stats["actor_train"]["actor_loss"])
    assert np.isfinite(stats["critic_train"]["value_loss"])
    assert abs(stats["actor_train"]["importance_weight"] - 1.0) < 0.1


def test_worker_group_spec_helpers():
    from realhf_tpu.api.experiment import ExperimentSpec

    spec = ExperimentSpec.__new__(ExperimentSpec)
    spec.worker_assignment = {"actor": [1, 2], "ref": 0}
    spec.models = {"actor": None, "ref": None}
    spec.allocations = {}
    assert spec.workers_of_role("actor") == [1, 2]
    assert spec.worker_of_role("actor") == 1
    assert spec.workers_of_role("ref") == [0]
    assert spec.workers_of_role("unlisted") == [0]
    assert spec.multihost
    spec.worker_assignment = {"actor": 1}
    assert not spec.multihost
    spec.worker_assignment = {"actor": [1, 1]}
    with pytest.raises(ValueError, match="duplicate"):
        spec.workers_of_role("actor")


def test_cross_group_spec_helpers():
    from realhf_tpu.api.experiment import ExperimentSpec, MFCAllocation
    from realhf_tpu.parallel.mesh import ParallelismConfig

    spec = ExperimentSpec.__new__(ExperimentSpec)
    spec.worker_assignment = {"actor": 0}
    spec.models = {"actor": None}
    par = ParallelismConfig(data_parallel_size=2)
    spec.allocations = {"actor_gen": MFCAllocation(par, workers=[1])}
    assert spec.workers_of_node("actor_gen", "actor") == [1]
    assert spec.workers_of_node("actor_train", "actor") == [0]
    assert spec.is_cross_group("actor_gen", "actor")
    assert not spec.is_cross_group("actor_train", "actor")
    assert not spec.multihost  # two single-worker groups, no shared mesh
    # bare ParallelismConfig allocations keep the role's group
    spec.allocations = {"actor_gen": par}
    assert spec.alloc_of("actor_gen").parallel is par
    assert spec.workers_of_node("actor_gen", "actor") == [0]
    assert not spec.is_cross_group("actor_gen", "actor")
    # a multi-worker exec group does need the shared world
    spec.allocations = {"actor_gen": MFCAllocation(par, workers=[1, 2])}
    assert spec.multihost
