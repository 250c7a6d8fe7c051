"""GenServerWorker in a real OS process: configure/start through the
WorkerControlPanel, serve RolloutClient traffic, hot-swap weights via
the worker command, and exit COMPLETED after a graceful drain --
the serving subsystem wired into the worker stack (docs/serving.md).
"""

import multiprocessing as mp
import os
import pickle
import time

import numpy as np
import pytest

TINY = dict(n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
            intermediate_dim=64, vocab_size=97, apply_rotary=True,
            layer_norm_type="rms", mlp_type="llama",
            use_attention_bias=False, use_attn_proj_bias=False,
            use_mlp_bias=False, activation_function="silu")


def _worker_proc(record_root, spec_path):
    # separate OS process: CPU backend must be forced before jax init
    from realhf_tpu.base.backend import force_cpu_backend
    force_cpu_backend()
    from realhf_tpu.base import name_resolve
    name_resolve.reconfigure("nfs", record_root=record_root)
    with open(spec_path, "rb") as f:
        spec = pickle.load(f)
    from realhf_tpu.serving.worker import GenServerWorker
    GenServerWorker(spec.experiment_name, spec.trial_name,
                    "gen_server/0").run()


def _make_spec(exp, trial):
    from realhf_tpu.api.experiment import (
        ExperimentSpec,
        ModelSpec,
        ServingSpec,
    )
    return ExperimentSpec(
        experiment_name=exp, trial_name=trial,
        models={"default": ModelSpec(
            path=None, random_init_config=dict(TINY),
            optimizer=None, gradient_checkpointing=False, bf16=False)},
        mfcs=[], dataset=None, seed=1,
        serving=ServingSpec(
            model_role="default", n_servers=1, n_slots=2, chunk_size=4,
            max_prompt_len=64, max_queue_depth=16,
            eos_token_id=None, pad_token_id=0,
            drain_timeout_secs=20.0,
            gconfig=dict(max_new_tokens=8, min_new_tokens=1,
                         greedy=True)))


@pytest.mark.slow  # full trial / multi-process, ~8-20 s (CHANGES.md, PR 22)
def test_gen_server_worker_process(tmp_path):
    from realhf_tpu.base import name_resolve
    from realhf_tpu.serving.server import RolloutClient
    from realhf_tpu.system.worker_base import (
        WorkerControlPanel,
        WorkerServerStatus,
    )

    record_root = str(tmp_path / "nr")
    name_resolve.reconfigure("nfs", record_root=record_root)
    exp, trial = "servetest", "t0"
    spec = _make_spec(exp, trial)
    spec_path = str(tmp_path / "spec.pkl")
    with open(spec_path, "wb") as f:
        pickle.dump(spec, f)

    ctx = mp.get_context("spawn")
    proc = ctx.Process(target=_worker_proc,
                       args=(record_root, spec_path), daemon=True)
    proc.start()
    client = None
    try:
        panel = WorkerControlPanel(exp, trial)
        panel.connect(["gen_server/0"], timeout=120)
        out = panel.group_request(
            "configure",
            kwargs=dict(config=dict(spec_path=spec_path,
                                    server_index=0)),
            timeout=240)
        assert "address" in out["gen_server/0"]
        panel.group_request("start")

        client = RolloutClient(experiment_name=exp, trial_name=trial,
                               server_name="gen_server/0")
        rng = np.random.default_rng(0)
        prompts = [rng.integers(2, 97, size=6).astype(np.int32)
                   for _ in range(3)]
        rids = [client.submit(p) for p in prompts]
        results = [client.result(r, timeout=120.0) for r in rids]
        assert all(r.ok and len(r.tokens) == 8 for r in results)
        assert all(r.weight_version == 0 for r in results)

        # weight hot-swap through the worker command plane (a pure
        # version bump re-pushes the current weights under v1)
        out = panel.group_request("update_weights",
                                  kwargs=dict(version=1), timeout=60)
        assert out["gen_server/0"]["pending_version"] == 1
        r2 = client.result(client.submit(prompts[0]), timeout=120.0)
        assert r2.ok and r2.weight_version == 1

        stats = panel.group_request("stats")["gen_server/0"]
        assert stats["finished"] == 4
        assert stats["weight_version"] == 1
        assert stats["decode_steps"] < stats["sequential_equiv_steps"]

        # exit drains (GenServerWorker._exit_hook) -> COMPLETED
        panel.group_request("exit", timeout=60)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if panel.get_worker_status("gen_server/0") == \
                    WorkerServerStatus.COMPLETED:
                break
            time.sleep(0.2)
        assert panel.get_worker_status("gen_server/0") == \
            WorkerServerStatus.COMPLETED
    finally:
        if client is not None:
            client.close()
        proc.join(timeout=30)
        if proc.is_alive():
            proc.terminate()
