"""Cross-group parameter reallocation, general form (VERDICT r4 #4;
reference ``comm/param_realloc.py:141,312``: arbitrary src/dst 3D
layouts on arbitrary device sets).

Three cases the round-4 suite did not cover:

1. The SENDER group spans multiple OS processes: the actor trains on
   worker group [0, 1] (one mesh over both processes' devices, the
   host-gather for publication is a collective), while its generation
   MFC lives on worker [2] with a DIFFERENT 3D layout.  Weights must
   flow primary-group -> data plane -> differently-laid-out replica
   every step.

2. The RECEIVER is a different ROLE: the KL reference model is
   repointed at the actor role (``ModelName("actor", 1)``, the
   ppo_ref_ema recipe) but hosted on its OWN worker group with its own
   layout, EMA-tracking the trainable actor through the cross-group
   stream (install applies ``target = eta*src + (1-eta)*target``).

3. The RECEIVER group spans multiple OS processes: actor trains on
   worker [0], generates on workers [1, 2] whose replica mesh spans
   both processes -- every member fetches the chunk stream and joins
   the collective per-leaf device_put install.
"""


import numpy as np
import pytest

from realhf_tpu.api.config import ModelName
from realhf_tpu.api.dfg import ParamReallocHook
from realhf_tpu.api.experiment import MFCAllocation
from realhf_tpu.base.testing import IntegerTokenizer
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.experiments.common import apply_overrides
from realhf_tpu.experiments.ppo_exp import PPOConfig
from realhf_tpu.parallel.mesh import ParallelismConfig

from tiny_model import TINY, write_jsonl

# 2 virtual CPU devices per worker process; a 3-process world has 6.
WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
    "REALHF_TPU_LOCAL_DEVICE_COUNT": "2",
    "PYTHONPATH": "/root/repo",
}




@pytest.fixture
def prompt_data(tmp_path):
    rng = np.random.default_rng(1)
    path = tmp_path / "prompts.jsonl"
    write_jsonl(path, [
        {"id": i,
         "prompt": " ".join(f"w{int(x)}" for x in rng.integers(0, 50, 4))}
        for i in range(24)])
    return str(path)


def _base_cfg(prompt_data, name):
    cfg = PPOConfig(experiment_name=name, trial_name="t0",
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
    return cfg


@pytest.mark.slow  # multi-process trial, >15 s alone (CHANGES.md, PR 22)
def test_cross_group_from_multiproc_primary(prompt_data):
    """Actor trains on a TWO-PROCESS mesh (workers [0,1], d2t2);
    actor_gen executes on worker [2] with a different layout (d2t1).
    The publish-side host gather is a collective over the primary's
    two processes; the receiver repartitions onto its own mesh."""
    from realhf_tpu.apps.main import main_start

    spec = _base_cfg(prompt_data, "xgmp").build()
    for role, mspec in spec.models.items():
        mspec.path = None
        mspec.random_init_config = dict(TINY)
        mspec.bf16 = False
        mspec.parallel = (
            ParallelismConfig(data_parallel_size=2,
                              tensor_parallel_size=2)
            if role == "actor"
            else ParallelismConfig(data_parallel_size=2))
        if mspec.optimizer is not None:
            mspec.optimizer = OptimizerConfig(
                lr=1e-3, warmup_steps_proportion=0.0,
                lr_scheduler_type="constant")
    spec.tokenizer = IntegerTokenizer()
    spec.n_model_workers = 3
    spec.worker_assignment = {"actor": [0, 1], "critic": 2, "ref": 2,
                              "reward": 2}
    spec.allocations = dict(
        spec.allocations,
        actor_gen=MFCAllocation(
            ParallelismConfig(data_parallel_size=2),
            workers=[2]))
    assert spec.is_cross_group("actor_gen", "actor")
    assert spec.multihost  # the actor group spans two processes

    out = main_start(spec, env=WORKER_ENV, timeout=1800)
    assert out["complete"]
    assert out["global_step"] == 2
    stats = out["stats"]
    assert np.isfinite(stats["actor_train"]["actor_loss"])
    # rollout logprobs (replica weights) match the primary's own
    # recomputation => the synced weights are the trained weights
    assert abs(stats["actor_train"]["importance_weight"] - 1.0) < 0.1

    gen_rows = [r for r in out["exec_log"] if r["mfc"] == "actor_gen"]
    assert gen_rows and all(r["worker"] == "model_worker/2"
                            for r in gen_rows)
    train_workers = {r["worker"] for r in out["exec_log"]
                     if r["mfc"] == "actor_train"}
    assert train_workers == {"model_worker/0", "model_worker/1"}
    versions = {r["bid"]: r["param_version"]
                for r in gen_rows if "param_version" in r}
    assert versions[0] == 0   # first rollout: shared init
    assert versions[1] >= 1   # second rollout: post-train weights


@pytest.mark.slow  # multi-process trial, >15 s alone (CHANGES.md, PR 22)
def test_cross_group_ema_ref_different_role(prompt_data):
    """Different-ROLE receiver: ref_inf repointed at the actor role
    (ppo_ref_ema recipe) but placed on its OWN worker group [1] with
    its own layout; the cross-group install EMA-merges (eta=0.5) the
    actor's fresh weights into the replica every actor step."""
    from realhf_tpu.apps.main import main_start

    spec = _base_cfg(prompt_data, "xgema").build()
    ref_inf = next(n for n in spec.mfcs if n.name == "ref_inf")
    ref_inf.model_name = ModelName("actor", 1)
    del spec.models["ref"]
    ref_inf.add_pre_hook(
        ParamReallocHook(source=ModelName("actor", 0), eta=0.5))

    for role, mspec in spec.models.items():
        mspec.path = None
        mspec.random_init_config = dict(TINY)
        mspec.bf16 = False
        mspec.parallel = ParallelismConfig(data_parallel_size=2)
        if mspec.optimizer is not None:
            mspec.optimizer = OptimizerConfig(
                lr=1e-3, warmup_steps_proportion=0.0,
                lr_scheduler_type="constant")
    spec.tokenizer = IntegerTokenizer()
    spec.n_model_workers = 2
    spec.worker_assignment = {"actor": 0, "critic": 0, "reward": 0}
    spec.allocations = dict(
        spec.allocations,
        ref_inf=MFCAllocation(
            ParallelismConfig(tensor_parallel_size=2),
            workers=[1]))
    assert spec.is_cross_group("ref_inf", "actor")

    out = main_start(spec, env=WORKER_ENV, timeout=1800)
    assert out["complete"]
    assert out["global_step"] == 2
    stats = out["stats"]
    assert np.isfinite(stats["actor_train"]["actor_loss"])
    assert np.isfinite(stats["actor_train"]["kl_reward"])

    ref_rows = [r for r in out["exec_log"] if r["mfc"] == "ref_inf"]
    assert ref_rows and all(r["worker"] == "model_worker/1"
                            for r in ref_rows)
    versions = {r["bid"]: r["param_version"]
                for r in ref_rows if "param_version" in r}
    assert versions[0] == 0
    assert versions[1] >= 1  # EMA install happened after actor trained


@pytest.mark.slow  # multi-process trial, >15 s alone (CHANGES.md, PR 22)
def test_cross_group_to_multiproc_receiver(prompt_data):
    """Actor trains on worker [0]; actor_gen executes on a replica
    mesh SPANNING workers [1, 2] (d2t2 over two processes). Both
    receiver members fetch the chunk stream and join the collective
    install; the agreement protocol pins one exact version."""
    from realhf_tpu.apps.main import main_start

    spec = _base_cfg(prompt_data, "xgmr").build()
    for role, mspec in spec.models.items():
        mspec.path = None
        mspec.random_init_config = dict(TINY)
        mspec.bf16 = False
        mspec.parallel = ParallelismConfig(data_parallel_size=2)
        if mspec.optimizer is not None:
            mspec.optimizer = OptimizerConfig(
                lr=1e-3, warmup_steps_proportion=0.0,
                lr_scheduler_type="constant")
    spec.tokenizer = IntegerTokenizer()
    spec.n_model_workers = 3
    spec.worker_assignment = {"actor": 0, "critic": 0, "ref": 0,
                              "reward": 0}
    spec.allocations = dict(
        spec.allocations,
        actor_gen=MFCAllocation(
            ParallelismConfig(data_parallel_size=2,
                              tensor_parallel_size=2),
            workers=[1, 2]))
    assert spec.is_cross_group("actor_gen", "actor")
    assert spec.multihost  # the replica mesh spans two processes

    out = main_start(spec, env=WORKER_ENV, timeout=1800)
    assert out["complete"]
    assert out["global_step"] == 2
    stats = out["stats"]
    assert np.isfinite(stats["actor_train"]["actor_loss"])
    assert abs(stats["actor_train"]["importance_weight"] - 1.0) < 0.1

    gen_workers = {r["worker"] for r in out["exec_log"]
                   if r["mfc"] == "actor_gen"}
    assert gen_workers == {"model_worker/1", "model_worker/2"}
    versions = {r["bid"]: r["param_version"]
                for r in out["exec_log"]
                if r["mfc"] == "actor_gen" and "param_version" in r}
    assert versions[0] == 0
    assert versions[1] >= 1
