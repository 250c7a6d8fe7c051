"""Fleet mode across real OS processes: a RouterWorker fronting two
GenServerWorker replicas, with a hard kill mid-stream -- the
in-flight failover story over genuine process boundaries
(docs/serving.md "Fleet, failover & circuit breakers").

The in-process lockstep drills live in tests/chaos/; this file proves
the worker/launcher wiring (remote.py `router` type, lease renewal
from real serve loops, rendezvous at server_name="router")."""

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


def _worker_proc(record_root, spec_path, worker_type, index):
    from realhf_tpu.base.backend import force_cpu_backend
    force_cpu_backend()
    from realhf_tpu.base import name_resolve
    name_resolve.reconfigure("nfs", record_root=record_root)
    with open(spec_path, "rb") as f:
        spec = pickle.load(f)
    if worker_type == "router":
        from realhf_tpu.serving.worker import RouterWorker
        RouterWorker(spec.experiment_name, spec.trial_name,
                     f"router/{index}").run()
    else:
        from realhf_tpu.serving.worker import GenServerWorker
        GenServerWorker(spec.experiment_name, spec.trial_name,
                        f"gen_server/{index}").run()


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
            model_role="default", n_servers=2, n_slots=2, chunk_size=2,
            max_prompt_len=64, max_queue_depth=16,
            eos_token_id=None, pad_token_id=0,
            drain_timeout_secs=20.0,
            # lease renewal rides the heartbeat thread, so a long
            # first-compile does not decay it; response_timeout is
            # disabled because a cold decode chunk on this CPU box
            # can exceed any sane stall threshold
            fleet_router=True, lease_ttl_secs=6.0,
            router_dispatch_timeout_secs=30.0,
            router_response_timeout_secs=None,
            gconfig=dict(max_new_tokens=24, min_new_tokens=1,
                         greedy=True)))


@pytest.mark.slow
def test_fleet_router_failover_across_processes(tmp_path):
    from realhf_tpu.base import name_resolve
    from realhf_tpu.serving.server import RolloutClient
    from realhf_tpu.system.worker_base import WorkerControlPanel

    record_root = str(tmp_path / "nr")
    name_resolve.reconfigure("nfs", record_root=record_root)
    exp, trial = "fleettest", "t0"
    spec = _make_spec(exp, trial)
    spec_path = str(tmp_path / "spec.pkl")
    with open(spec_path, "wb") as f:
        pickle.dump(spec, f)

    ctx = mp.get_context("spawn")
    procs = {}
    for i in range(2):
        procs[f"gen_server/{i}"] = ctx.Process(
            target=_worker_proc,
            args=(record_root, spec_path, "gen_server", i),
            daemon=True)
    procs["router/0"] = ctx.Process(
        target=_worker_proc,
        args=(record_root, spec_path, "router", 0), daemon=True)
    for p in procs.values():
        p.start()
    client = None
    try:
        panel = WorkerControlPanel(exp, trial)
        names = sorted(procs)
        panel.connect(names, timeout=180)
        panel.group_request_varied(
            "configure",
            {"gen_server/0": dict(config=dict(spec_path=spec_path,
                                              server_index=0)),
             "gen_server/1": dict(config=dict(spec_path=spec_path,
                                              server_index=1)),
             "router/0": dict(config=dict(spec_path=spec_path))},
            timeout=300)
        panel.group_request("start")

        # clients rendezvous on the ROUTER, never a replica
        client = RolloutClient(experiment_name=exp, trial_name=trial,
                               server_name="router")
        rng = np.random.default_rng(0)
        warm = [client.submit(
            rng.integers(2, 97, size=6).astype(np.int32), ttl=120.0)
            for _ in range(4)]
        results = [client.result(r, timeout=120.0) for r in warm]
        assert all(r.ok and len(r.tokens) == 24 for r in results)
        rstats = panel.group_request("stats",
                                     worker_names=["router/0"])
        assert rstats["router/0"]["requests"] == 4
        assert len(rstats["router/0"]["replicas"]) == 2

        # hard-kill one replica with fresh requests in flight: SIGKILL
        # means no drain, no deregistration -- the lease must decay and
        # the router must fail the work over to the survivor
        rids = [client.submit(
            rng.integers(2, 97, size=6).astype(np.int32), ttl=180.0)
            for _ in range(6)]
        procs["gen_server/0"].kill()
        results = {r: client.result(r, timeout=180.0) for r in rids}
        assert all(res.ok for res in results.values()), {
            r: res.status for r, res in results.items()}
        rstats = panel.group_request(
            "stats", worker_names=["router/0"])["router/0"]
        assert rstats["replicas"]["gen_server/0"]["lost"] is True
        # ties break toward gen_server/0, so at least one of the burst
        # was assigned to the victim and had to fail over
        assert rstats["failovers"] >= 1
        assert any(res.data.get("retried_from") == ["gen_server/0"]
                   for res in results.values())

        alive = ["gen_server/1", "router/0"]
        panel.group_request("exit", worker_names=alive, timeout=90)
    finally:
        if client is not None:
            client.close()
        for p in procs.values():
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()


def test_serve_exp_builds_fleet_spec():
    """The serve experiment CLI surfaces every fleet/router knob into
    ServingSpec (tier-1 wiring check)."""
    from realhf_tpu.experiments.serve_exp import ServeConfig

    cfg = ServeConfig(
        experiment_name="e", trial_name="t", n_servers=3,
        fleet_router=True, lease_ttl_secs=2.5,
        router_hedge_delay_secs=0.5, router_max_hedges=2,
        router_breaker_failures=4, router_breaker_cooldown_secs=1.5,
        router_dispatch_timeout_secs=3.0,
        router_response_timeout_secs=9.0, router_max_pending=77)
    spec = cfg.build()
    sv = spec.serving
    assert sv.fleet_router is True
    assert sv.n_servers == 3
    assert sv.lease_ttl_secs == 2.5
    assert sv.router_hedge_delay_secs == 0.5
    assert sv.router_max_hedges == 2
    assert sv.router_breaker_failures == 4
    assert sv.router_breaker_cooldown_secs == 1.5
    assert sv.router_dispatch_timeout_secs == 3.0
    assert sv.router_response_timeout_secs == 9.0
    assert sv.router_max_pending == 77
