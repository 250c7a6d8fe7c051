"""Elastic degraded-mode training + durable checkpoints, end-to-end
over real OS worker processes (ISSUE 4 acceptance).

The preempt-notice plumbing test runs on a dummy fleet (no jax
models). The full PPO degrade/rejoin run and the corrupt-checkpoint
recovery run are ``slow``-marked: they each spawn a whole trial and
are exercised by direct invocation (``pytest -m slow tests/system/
test_zz_elastic_e2e.py``), not the tier-1 sweep."""

import multiprocessing as mp
import os
import time

import numpy as np
import pytest

from tiny_model import TINY, write_jsonl

WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "PYTHONPATH": "/root/repo",
}


def _preempt_worker_proc(record_root, exp, trial, widx):
    os.environ["REALHF_TPU_NAME_RESOLVE"] = "nfs"
    os.environ["REALHF_TPU_HEARTBEAT_INTERVAL"] = "0.2"
    from realhf_tpu.base import name_resolve
    name_resolve.reconfigure("nfs", record_root=record_root)
    from realhf_tpu.system.request_reply_stream import (
        NameResolvingReplyServer,
    )
    from realhf_tpu.system.worker_base import PollResult, Worker

    name = f"mw/{widx}"

    class PWorker(Worker):

        def _configure(self, config):
            self.stream = NameResolvingReplyServer(exp, trial, name)
            return "ok"

        def _poll(self):
            try:
                req = self.stream.poll(timeout=0.05)
            except TimeoutError:
                return PollResult(0, 0)
            self.stream.respond(req, data=req.data)
            return PollResult(1, 1)

    PWorker(exp, trial, name).run()


@pytest.fixture
def record_root(tmp_path):
    return str(tmp_path / "nr")


def test_preempt_notice_roundtrip_across_processes(record_root):
    """A real worker process receives the preempt command: publishes
    the notice, keeps answering through the grace window, exits with
    status PREEMPTED and return code 0 -- the watchdog accounts for it
    (DONE), never LOST."""
    from realhf_tpu.base import name_resolve, names
    from realhf_tpu.system.request_reply_stream import (
        NameResolvingRequestClient,
    )
    from realhf_tpu.system.watchdog import DONE, Watchdog
    from realhf_tpu.system.worker_base import (
        WorkerControlPanel,
        WorkerServerStatus,
    )

    exp, trial = "pree2e", "t0"
    ctx = mp.get_context("spawn")
    p = ctx.Process(target=_preempt_worker_proc,
                    args=(record_root, exp, trial, 0), daemon=True)
    p.start()
    try:
        name_resolve.reconfigure("nfs", record_root=record_root)
        master = NameResolvingRequestClient(exp, trial)
        panel = WorkerControlPanel(exp, trial)
        panel.connect(["mw/0"], timeout=60)
        panel.group_request("configure", kwargs={"config": {}})
        panel.group_request("start")
        master.wait_subscribers(["mw/0"], timeout=30)
        dog = Watchdog(exp, trial, ["mw/0"], timeout=2.0, grace=60.0,
                       poll_interval=0.0)

        assert panel.group_request(
            "preempt", kwargs={"grace": 1.0})["mw/0"] == "ok"
        raw = name_resolve.wait(
            names.worker_preempt(exp, trial, "mw/0"), timeout=10)
        _ts, grace = map(float, str(raw).split(":"))
        assert grace == pytest.approx(1.0)
        assert dog.preempt_notices().keys() == {"mw/0"}
        # still serving inside the grace window
        rid = master.request(["mw/0"], "compute", datas=[5])[0]
        assert master.gather_replies([rid], timeout=20)[0].data == 5
        p.join(timeout=30)
        assert p.exitcode == 0  # graceful exit, not a kill
        assert panel.get_worker_status("mw/0") == \
            WorkerServerStatus.PREEMPTED
        deadline = time.monotonic() + 15
        while dog.check()["mw/0"] != DONE and \
                time.monotonic() < deadline:
            time.sleep(0.2)
        assert dog.check()["mw/0"] == DONE
        assert dog.lost_workers() == []
        master.close()
    finally:
        p.terminate()
        p.join(timeout=10)


@pytest.fixture
def prompt_data(tmp_path):
    rng = np.random.default_rng(1)
    path = tmp_path / "prompts.jsonl"
    write_jsonl(path, [
        {"id": i,
         "prompt": " ".join(f"w{int(x)}" for x in rng.integers(0, 50, 4))}
        for i in range(48)])
    return str(path)


@pytest.fixture
def sft_data(tmp_path):
    rng = np.random.default_rng(0)
    path = tmp_path / "sft.jsonl"
    write_jsonl(path, [
        {"id": i,
         "prompt": " ".join(f"w{int(x)}" for x in rng.integers(0, 50, 3)),
         "answer": " " + " ".join(["good"] * int(rng.integers(2, 6)))}
        for i in range(24)])
    return str(path)


@pytest.mark.slow
def test_elastic_degrade_survives_preemption_e2e(prompt_data, tmp_path):
    """ISSUE 4 acceptance: inject `preempt` on the worker hosting the
    cross-group actor_gen replica mid-run. The master re-plans it onto
    the surviving primary worker, training continues (no crash, no
    data re-consumption -- exact global_step), and the rollout/update
    weight coupling stays intact (importance_weight ~ 1)."""
    from realhf_tpu.api.experiment import (
        FaultToleranceConfig,
        MFCAllocation,
    )
    from realhf_tpu.apps.main import main_start
    from realhf_tpu.base.testing import IntegerTokenizer
    from realhf_tpu.engine.optim import OptimizerConfig
    from realhf_tpu.experiments.common import apply_overrides
    from realhf_tpu.experiments.ppo_exp import PPOConfig
    from realhf_tpu.parallel.mesh import ParallelismConfig

    cfg = PPOConfig(experiment_name="elastice2e", trial_name="t0",
                    total_train_epochs=1, benchmark_steps=5)
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
    for _role, mspec in spec.models.items():
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
    spec.worker_assignment = {"actor": 0, "critic": 0, "ref": 0,
                              "reward": 0}
    spec.allocations = dict(
        spec.allocations,
        actor_gen=MFCAllocation(ParallelismConfig(data_parallel_size=2),
                                workers=[1]))
    spec.ft = FaultToleranceConfig(
        heartbeat_interval=0.5, heartbeat_timeout=8.0,
        elastic_degrade=True, elastic_rejoin=True,
        preempt_grace_secs=10.0, gather_timeout_secs=300.0)
    assert spec.is_cross_group("actor_gen", "actor")

    state = tmp_path / "faults_state"
    env = dict(
        WORKER_ENV,
        REALHF_TPU_FAULTS="preempt:model_worker/1:generate:2:10.0",
        REALHF_TPU_FAULTS_STATE=str(state))
    out = main_start(spec, env=env, timeout=1800)
    assert out["complete"]
    # no data re-consumption: exactly benchmark_steps batches trained
    assert out["global_step"] == 5
    assert np.isfinite(out["stats"]["actor_train"]["actor_loss"])
    # the preempt fault really fired
    assert "preempt:model_worker/1:generate:2" in state.read_text()
    gen_rows = sorted((r["bid"], r["worker"]) for r in out["exec_log"]
                      if r["mfc"] == "actor_gen")
    workers_used = {w for _b, w in gen_rows}
    # rollouts started on worker 1, continued on the adopter
    assert gen_rows[0][1] == "model_worker/1"
    assert "model_worker/0" in workers_used
    # rollout weights tracked training through the migration
    assert abs(out["stats"]["actor_train"]["importance_weight"] - 1.0) \
        < 0.1


@pytest.mark.slow
def test_durable_ckpt_corruption_falls_back_on_recovery_e2e(
        sft_data, tmp_path):
    """ISSUE 4 acceptance, durability half: step-2's committed shard
    is corrupted (`corrupt_ckpt`), the worker then crashes; the
    auto-recover relaunch rejects the corrupt checkpoint by checksum,
    restores from the previous committed manifest, and finishes with
    no data re-consumption."""
    from realhf_tpu.apps.main import main_start
    from realhf_tpu.base import recover
    from realhf_tpu.base.testing import IntegerTokenizer
    from realhf_tpu.engine.optim import OptimizerConfig
    from realhf_tpu.experiments.common import apply_overrides
    from realhf_tpu.experiments.sft_exp import SFTConfig
    from realhf_tpu.parallel.mesh import ParallelismConfig
    from realhf_tpu.system.ckpt_manager import CheckpointManager

    state = tmp_path / "faults_state"
    cfg = SFTConfig(experiment_name="durrec", trial_name="t0",
                    total_train_epochs=1, save_freq_steps=1,
                    recover_mode="auto")
    apply_overrides(cfg, {"dataset.path": sft_data,
                          "dataset.train_bs_n_seqs": "8",
                          "dataset.max_seqlen": "32"})
    spec = cfg.build()
    for _role, mspec in spec.models.items():
        mspec.path = None
        mspec.random_init_config = dict(TINY)
        mspec.bf16 = False
        mspec.parallel = ParallelismConfig(
            data_parallel_size=2, tensor_parallel_size=4)
        if mspec.optimizer is not None:
            mspec.optimizer = OptimizerConfig(
                lr=1e-3, warmup_steps_proportion=0.0,
                lr_scheduler_type="constant")
    spec.tokenizer = IntegerTokenizer()
    spec.n_model_workers = 1
    env = dict(
        WORKER_ENV,
        REALHF_TPU_FAULTS=(
            "corrupt_ckpt:model_worker/0:ckpt_commit:2;"
            "crash:model_worker/0:train_step:3"),
        REALHF_TPU_FAULTS_STATE=str(state))
    out = main_start(spec, recover_mode="auto", recover_retries=2,
                     env=env, timeout=900)
    assert out["complete"]
    # 24 samples / bs 8 = 3 steps; re-consumption would overshoot
    assert out["global_step"] == 3
    assert np.isfinite(out["stats"]["trainDefault"]["loss"])
    fired = state.read_text()
    assert "corrupt_ckpt:model_worker/0:ckpt_commit:2" in fired
    assert "crash:model_worker/0:train_step:3" in fired

    info = recover.load_safe()
    assert info is not None
    assert info.version == recover.RECOVER_INFO_VERSION == 4
    assert info.ckpt_manifests and "default" in info.ckpt_manifests

    from realhf_tpu.base import constants
    mgr = CheckpointManager(os.path.join(
        constants.run_save_path(), "durable", "default"))
    best = mgr.latest_verified()
    assert best is not None
    # the corrupted step-2 checkpoint is not the verified best: either
    # it was rejected (fallback proven in the relaunch log) or a
    # post-recovery save superseded it with a clean commit
    corrupt_recs = [r for r in mgr.records() if r.step == 2]
    for r in corrupt_recs:
        ok, _problems = mgr.verify(r)
        assert not ok
    assert best.step != 2
