"""End-to-end observability over real OS worker processes (ISSUE 5
acceptance): ``REALHF_TPU_TRACE=1`` yields ONE merged Chrome trace
with one lane per process and cross-process span ancestry, and a
crashing worker leaves a flight-recorder dump naming its last events.

The dummy-fleet test is tier-1 (seconds). The full PPO trial trace is
``slow``-marked like the other whole-trial e2es (run directly:
``pytest -m slow tests/observability/test_trace_e2e.py``)."""

import json
import multiprocessing as mp
import os

import numpy as np
import pytest

# tests/system/tiny_model.py's canonical tiny llama config, inlined so
# this suite stays importable on its own sys.path
TINY = dict(n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
            intermediate_dim=64, vocab_size=1100, apply_rotary=True,
            layer_norm_type="rms", mlp_type="llama",
            use_attention_bias=False, use_attn_proj_bias=False,
            use_mlp_bias=False, activation_function="silu")

WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "PYTHONPATH": "/root/repo",
}


def _write_jsonl(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


def _traced_worker_proc(record_root, root_dir, exp, trial, widx):
    """A worker_base.Worker that exercises the REAL obs wiring: the
    base class configures tracing from REALHF_TPU_TRACE, the poll loop
    flushes span buffers, and the ERROR exit path dumps the flight
    ring."""
    os.environ["REALHF_TPU_NAME_RESOLVE"] = "nfs"
    os.environ["REALHF_TPU_HEARTBEAT_INTERVAL"] = "0.2"
    os.environ["REALHF_TPU_ROOT"] = root_dir
    os.environ["REALHF_TPU_TRACE"] = "1"
    import realhf_tpu.base.constants as constants
    constants.ROOT_DIR = root_dir  # env read happens at import time
    # real workers do this in _configure; the default flight-dump path
    # resolves through the run constants
    constants.set_experiment_trial_names(exp, trial)
    from realhf_tpu.base import name_resolve
    name_resolve.reconfigure("nfs", record_root=record_root)
    from realhf_tpu.obs import flight, tracing
    from realhf_tpu.system.request_reply_stream import (
        NameResolvingReplyServer,
    )
    from realhf_tpu.system.worker_base import PollResult, Worker

    name = f"mw/{widx}"

    class TracedWorker(Worker):

        def _configure(self, config):
            self.stream = NameResolvingReplyServer(exp, trial, name)
            return "ok"

        def _poll(self):
            try:
                req = self.stream.poll(timeout=0.05)
            except TimeoutError:
                return PollResult(0, 0)
            flight.record("request", handle=req.handle_name)
            if req.handle_name == "explode":
                raise RuntimeError("injected crash")
            with tracing.span(f"mfc:{req.data}",
                              parent=tracing.extract(req.trace),
                              worker=name):
                with tracing.span(f"compute:{req.data}"):
                    pass
            self.stream.respond(req, data="ok")
            flight.record("reply", handle=req.handle_name)
            return PollResult(1, 1)

    TracedWorker(exp, trial, name).run()


def test_merged_trace_and_crash_dump_across_processes(
        tmp_path, monkeypatch):
    """Two real worker processes + the master: spans opened in the
    master are ancestors of worker spans in ONE merged Chrome trace
    with three process lanes; a crashing worker's ERROR exit leaves a
    flight dump naming its recent events."""
    import realhf_tpu.base.constants as constants
    from realhf_tpu.base import name_resolve
    from realhf_tpu.obs import tracing
    from realhf_tpu.system.request_reply_stream import (
        NameResolvingRequestClient,
    )
    from realhf_tpu.system.worker_base import (
        WorkerControlPanel,
        WorkerServerStatus,
    )

    exp, trial = "obse2e", "t0"
    record_root = str(tmp_path / "nr")
    root_dir = constants.ROOT_DIR  # conftest points this at tmp
    monkeypatch.setenv("REALHF_TPU_TRACE", "1")
    tracing.reset_default()
    constants.set_experiment_trial_names(exp, trial)

    ctx = mp.get_context("spawn")
    procs = [ctx.Process(
        target=_traced_worker_proc,
        args=(record_root, root_dir, exp, trial, i), daemon=True)
        for i in range(2)]
    for p in procs:
        p.start()
    try:
        name_resolve.reconfigure("nfs", record_root=record_root)
        master = NameResolvingRequestClient(exp, trial)
        panel = WorkerControlPanel(exp, trial)
        workers = ["mw/0", "mw/1"]
        panel.connect(workers, timeout=60)
        panel.group_request("configure", kwargs={"config": {}})
        panel.group_request("start")
        master.wait_subscribers(workers, timeout=30)

        tracing.configure(
            process_name="master", enabled=True,
            path=tracing.trace_file_path("master", exp, trial))
        with tracing.span("step", batch_id=0):
            for i, mfc in enumerate(("actor_gen", "actor_train")):
                with tracing.span(f"dispatch:{mfc}"):
                    rid = master.request([f"mw/{i}"], "compute",
                                         datas=[mfc])[0]
                    master.gather_replies([rid], timeout=30)
        tracing.flush()

        # the events the flight dump must name (>= 10)
        for _ in range(5):
            rid = master.request(["mw/0"], "compute",
                                 datas=["filler"])[0]
            master.gather_replies([rid], timeout=30)
        master.request(["mw/0"], "explode")
        procs[0].join(timeout=30)
        assert panel.get_worker_status("mw/0") == \
            WorkerServerStatus.ERROR
        panel.group_request("exit", worker_names=["mw/1"])
        procs[1].join(timeout=30)
        master.close()
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
            p.join(timeout=10)

    merged = tracing.merge_traces(experiment=exp, trial=trial)
    assert merged is not None
    spans = [e for e in json.load(open(merged))["traceEvents"]
             if e["ph"] == "X"]
    by_name = {e["name"]: e for e in spans}
    assert len({e["pid"] for e in spans}) == 3  # master + 2 workers
    assert by_name["mfc:actor_gen"]["pid"] != by_name["step"]["pid"]
    # cross-process ancestry: worker spans nest under the master's
    for mfc in ("actor_gen", "actor_train"):
        assert (by_name[f"mfc:{mfc}"]["args"]["parent_id"]
                == by_name[f"dispatch:{mfc}"]["args"]["span_id"])
        assert (by_name[f"compute:{mfc}"]["args"]["trace_id"]
                == by_name["step"]["args"]["trace_id"])

    from realhf_tpu.obs import flight
    dump = flight.dump_path("mw/0", exp, trial)
    assert os.path.exists(dump)
    doc = json.load(open(dump))
    assert doc["worker"] == "mw/0"
    assert doc["n_events"] >= 10
    assert "injected crash" in doc["reason"]
    assert doc["events"][-1]["kind"] == "request"
    assert doc["events"][-1]["handle"] == "explode"


@pytest.mark.slow
def test_quickstart_ppo_trace_e2e(tmp_path, monkeypatch):
    """The full acceptance run: the quickstart PPO example with
    ``REALHF_TPU_TRACE=1`` produces a single merged Chrome trace with
    >= 2 processes in which per-MFC compute, data-transfer, and
    realloc spans nest under the step span; an injected ``crash``
    fault leaves a flight-recorder dump naming the last >= 10
    events."""
    import realhf_tpu.base.constants as constants
    from realhf_tpu.api.experiment import (
        FaultToleranceConfig,
        MFCAllocation,
    )
    from realhf_tpu.apps.main import main_start
    from realhf_tpu.base.testing import IntegerTokenizer
    from realhf_tpu.engine.optim import OptimizerConfig
    from realhf_tpu.experiments.common import apply_overrides
    from realhf_tpu.experiments.ppo_exp import PPOConfig
    from realhf_tpu.obs import flight, tracing
    from realhf_tpu.parallel.mesh import ParallelismConfig

    rng = np.random.default_rng(1)
    prompt_data = tmp_path / "prompts.jsonl"
    _write_jsonl(prompt_data, [
        {"id": i,
         "prompt": " ".join(f"w{int(x)}"
                            for x in rng.integers(0, 50, 4))}
        for i in range(32)])

    monkeypatch.setenv("REALHF_TPU_TRACE", "1")
    cfg = PPOConfig(experiment_name="obsppo", trial_name="t0",
                    total_train_epochs=1, benchmark_steps=2,
                    recover_mode="auto")
    apply_overrides(cfg, {
        "dataset.path": str(prompt_data),
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
    # actor_gen on worker 1: forces cross-group realloc spans AND a
    # second process lane in the merged trace
    spec.allocations = dict(
        spec.allocations,
        actor_gen=MFCAllocation(
            ParallelismConfig(data_parallel_size=2), workers=[1]))
    spec.ft = FaultToleranceConfig(
        heartbeat_interval=0.5, heartbeat_timeout=30.0,
        gather_timeout_secs=600.0)

    state = tmp_path / "faults_state"
    env = dict(
        WORKER_ENV,
        REALHF_TPU_TRACE="1",
        REALHF_TPU_FAULTS="crash:model_worker/0:train_step:2",
        REALHF_TPU_FAULTS_STATE=str(state))
    out = main_start(spec, recover_mode="auto", recover_retries=2,
                     env=env, timeout=1800)
    assert out["complete"]
    assert "crash:model_worker/0:train_step:2" in state.read_text()

    # --- single merged Chrome trace, >= 2 processes ------------------
    constants.set_experiment_trial_names("obsppo", "t0")
    merged = os.path.join(tracing.trace_dir("obsppo", "t0"),
                          tracing.MERGED_TRACE_NAME)
    assert os.path.exists(merged)
    spans = [e for e in json.load(open(merged))["traceEvents"]
             if e["ph"] == "X"]
    names = {e["name"] for e in spans}
    assert len({e["pid"] for e in spans}) >= 2
    step_ids = {e["args"]["span_id"] for e in spans
                if e["name"] == "step"}
    assert step_ids
    # per-MFC compute, data-transfer, and realloc spans present...
    assert "compute:actor_gen" in names
    assert "compute:actor_train" in names
    assert "data_fetch" in names
    assert "realloc" in names  # cross-group actor_gen param sync
    # ...and nested under the step span: walk parents to a step root
    by_id = {e["args"]["span_id"]: e for e in spans}

    def has_step_ancestor(ev):
        seen = set()
        while ev is not None:
            pid = ev["args"].get("parent_id")
            if pid in step_ids:
                return True
            if pid is None or pid in seen:
                return False
            seen.add(pid)
            ev = by_id.get(pid)
        return False

    for nm in ("compute:actor_gen", "compute:actor_train",
               "data_fetch", "realloc"):
        assert any(has_step_ancestor(e) for e in spans
                   if e["name"] == nm), f"{nm} not nested under a step"

    # --- flight-recorder dump from the injected crash ----------------
    dump = flight.dump_path("model_worker/0", "obsppo", "t0")
    assert os.path.exists(dump)
    doc = json.load(open(dump))
    assert doc["n_events"] >= 10
    kinds = {e["kind"] for e in doc["events"]}
    assert "fault" in kinds and "request" in kinds

    # --- trace analytics on the real merged trace (ISSUE 13) ---------
    # the analyzer reconstructs the steps, the attribution components
    # sum to each step's wall, and a critical-path MFC is named
    from realhf_tpu.obs import analyze
    report = analyze.analyze_path(merged)
    assert report["n_steps"] >= 2
    for step in report["steps"]:
        assert sum(step["attribution"].values()) == pytest.approx(
            step["wall_secs"], abs=1e-6)
        assert step["attribution"]["compute"] > 0
    assert report["bottleneck_mfc"] is not None
    assert 0 < report["goodput"] <= 1.0
    assert report["stragglers"], report
    # the same report renders as the teardown one-liner
    assert analyze.one_line_summary(report).startswith(
        "trace report: ")
