"""Slow acceptance e2e (ISSUE 13): a Prometheus-shaped scrape of a
live ``run_serve`` fleet. The launcher writes ``scrape_targets.json``
resolved from the ``names.telemetry`` registry (NOT the manifest's
dead per-host ports); an HTTP GET to EVERY listed target returns
valid Prometheus text -- ``serving_*_total`` counters on the
replicas, ``router_*`` series (including the new latency histogram)
on the router -- and a replica's ``/healthz`` flips from 200 to 503
the moment a drain starts.

Run directly: ``pytest -m slow tests/telemetry/test_scrape_e2e.py``.
"""

import json
import os
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

TINY = dict(n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
            intermediate_dim=64, vocab_size=97, apply_rotary=True,
            layer_norm_type="rms", mlp_type="llama",
            use_attention_bias=False, use_attn_proj_bias=False,
            use_mlp_bias=False, activation_function="silu")

WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "JAX_PLATFORMS": "cpu",
    "PYTHONPATH": os.path.abspath(os.path.join(
        os.path.dirname(__file__), "..", "..")),
}


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
            fleet_router=True, lease_ttl_secs=6.0,
            router_dispatch_timeout_secs=60.0,
            router_response_timeout_secs=None,
            gconfig=dict(max_new_tokens=8, min_new_tokens=1,
                         greedy=True)))


def _get(address, path, timeout=15.0):
    try:
        with urllib.request.urlopen(f"http://{address}{path}",
                                    timeout=timeout) as r:
            return r.status, dict(r.headers), r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read().decode()


@pytest.mark.slow
def test_run_serve_fleet_scrape_and_drain_flip():
    from realhf_tpu.apps.main import run_serve
    from realhf_tpu.base import constants
    from realhf_tpu.obs import http as obs_http
    from realhf_tpu.serving.server import RolloutClient
    from realhf_tpu.system.worker_base import WorkerControlPanel

    exp, trial = "scrapee2e", "t0"
    spec = _make_spec(exp, trial)
    result = {}

    def _serve():
        try:
            # duration counts from AFTER bring-up: it only needs to
            # cover the traffic + scrape + drain checks below
            result["stats"] = run_serve(spec, env=dict(WORKER_ENV),
                                        duration=180.0, timeout=900.0)
        except BaseException as e:  # noqa: BLE001 - surfaced below
            result["error"] = e

    t = threading.Thread(target=_serve, daemon=True)
    t.start()
    client = None
    try:
        # -- the launcher wrote registry-resolved scrape targets -----
        constants.set_experiment_trial_names(exp, trial)
        sd_path = os.path.join(constants.run_log_path(), "obs",
                               "scrape_targets.json")
        deadline = time.monotonic() + 300
        while not os.path.exists(sd_path):
            assert "error" not in result, result["error"]
            assert time.monotonic() < deadline, \
                f"scrape targets never written to {sd_path}"
            time.sleep(0.5)
        entries = json.load(open(sd_path))
        by_worker = {e["labels"]["worker"]: e for e in entries}
        assert set(by_worker) == {"gen_server/0", "gen_server/1",
                                  "router/0"}, entries
        for e in entries:
            assert len(e["targets"]) == 1
            assert re.match(r"^[\d.]+:\d+$", e["targets"][0]), e
            assert e["labels"]["experiment"] == exp

        # -- real traffic through the router -------------------------
        client = RolloutClient(experiment_name=exp, trial_name=trial,
                               server_name="router")
        rng = np.random.default_rng(0)
        rids = [client.submit(
            rng.integers(2, 97, size=6).astype(np.int32), ttl=170.0)
            for _ in range(4)]
        results = [client.result(r, timeout=170.0) for r in rids]
        assert all(r.ok and len(r.tokens) == 8 for r in results)

        # -- every listed target answers valid Prometheus text -------
        texts = {}
        for worker, entry in by_worker.items():
            code, headers, body = _get(entry["targets"][0],
                                       "/metrics")
            assert code == 200, (worker, code)
            assert headers["Content-Type"].startswith("text/plain")
            fams = obs_http.parse_prometheus_text(body)
            assert fams, (worker, body[:200])
            texts[worker] = (body, fams)
        router_fams = texts["router/0"][1]
        assert obs_http.prom_scalar(
            router_fams, "router_requests_total") >= 4
        # satellite: the latency histogram is scrapable and yields a
        # quantile (what a real Prometheus histogram_quantile sees)
        assert obs_http.prom_histogram_quantile(
            router_fams, "router_latency_seconds", 0.95) is not None
        gen_counters = set()
        for worker in ("gen_server/0", "gen_server/1"):
            for name in texts[worker][1]:
                m = re.match(r"^(serving_[a-z0-9_]+_total)$", name)
                if m:
                    gen_counters.add(m.group(1))
        assert gen_counters, {w: sorted(texts[w][1])
                              for w in texts}

        # -- /healthz flips state on drain ---------------------------
        g0 = by_worker["gen_server/0"]["targets"][0]
        code, _, body = _get(g0, "/healthz")
        doc = json.loads(body)
        assert code == 200 and doc["state"] == "RUNNING", doc
        assert doc["fencing_epoch"] is not None  # lease state surfaced
        panel = WorkerControlPanel(exp, trial)
        panel.connect(["gen_server/0"], timeout=60)
        panel.group_request("drain", worker_names=["gen_server/0"],
                            timeout=120)
        code, _, body = _get(g0, "/healthz")
        doc = json.loads(body)
        assert code == 503 and doc["state"] == "DRAINING", doc
    finally:
        if client is not None:
            client.close()
        t.join(timeout=600)
    assert not t.is_alive(), "run_serve did not finish"
    assert "error" not in result, result.get("error")
    stats = result["stats"]
    # the ZMQ stats path carries the new histogram quantiles too
    assert stats["router/0"]["latency_p50"] is not None
    assert stats["router/0"]["latency_p95"] is not None
