"""GenServerWorker: a rollout server in the worker/scheduler stack.

The serving subsystem's process shell: a :class:`Worker` whose poll
loop IS the serve loop. It inherits the full PR-1 fault-tolerance
plumbing for free -- heartbeat beacon, status publication, watchdog
attribution, scheduler supervision (``apps.main.run_serve``) -- so a
hung generation server is detected and named like any other worker.

Extra worker commands beyond the base set:

- ``stats``: the server's scheduler/queue counters.
- ``update_weights {version, path?}``: hot-swap. With ``path``, loads
  an HF-format checkpoint and pushes it through WeightSync; without,
  re-pushes the current weights under the new version (a pure version
  bump -- the trainer advanced but this role's weights are refreshed
  out-of-band, or a staleness drill).
- ``drain``: early graceful drain without exiting.
"""

import pickle
from typing import Any, Dict

from realhf_tpu.base import constants, logging, seeding
from realhf_tpu.system import worker_base

logger = logging.getLogger("gen_server_worker", "system")


class GenServerWorker(worker_base.Worker):
    """One RolloutServer over one model role (see docs/serving.md)."""

    def _configure(self, config: Dict):
        from realhf_tpu.api.experiment import ExperimentSpec
        from realhf_tpu.engine.inflight import InflightBatchingGenerator
        from realhf_tpu.ops.sampling import GenerationHyperparameters
        from realhf_tpu.serving.fleet import FleetRegistry
        from realhf_tpu.serving.prefix_cache import RadixPrefixCache
        from realhf_tpu.serving.request_queue import RequestQueue
        from realhf_tpu.serving.server import RolloutServer
        from realhf_tpu.system.model_host import build_model

        with open(config["spec_path"], "rb") as f:
            spec: ExperimentSpec = pickle.load(f)
        self.spec = spec
        self.server_index = int(config.get("server_index", 0))
        constants.set_experiment_trial_names(spec.experiment_name,
                                             spec.trial_name)
        seeding.set_random_seed(spec.seed + 1000 + self.server_index)

        sv = spec.serving
        if sv is None:
            raise ValueError(
                "GenServerWorker needs ExperimentSpec.serving (see "
                "experiments/serve_exp.py).")
        mspec = spec.models[sv.model_role]
        self.model = build_model(sv.model_role, mspec, tokenizer=None,
                                 total_steps=1, init_seed=spec.seed)
        gconfig = GenerationHyperparameters(
            **dict(sv.gconfig, force_no_logits_mask=True))
        # paged KV pool (docs/perf.md "Paged KV & quantization"):
        # int8 implies the pool -- dequant-on-read lives in its
        # gather path
        kv_pool = None
        paged = sv.paged_kv or sv.kv_cache_dtype == "int8"
        if paged:
            from realhf_tpu.engine.kv_pool import KVPool
            from realhf_tpu.models import transformer as T
            cache_len = T.round_cache_len(
                sv.max_prompt_len + gconfig.max_new_tokens)
            n_blocks = sv.kv_pool_blocks or sv.n_slots * (
                -(-cache_len // sv.kv_block_len))
            kv_pool = KVPool(self.model.config, n_blocks,
                             sv.kv_block_len,
                             dtype=sv.kv_cache_dtype or "fp32")
            logger.info(
                "KV pool: %d blocks x %d tokens (%d bytes, dtype=%s) "
                "for %d slots.", n_blocks, sv.kv_block_len,
                n_blocks * kv_pool.block_bytes, kv_pool.dtype,
                sv.n_slots)
        backend = InflightBatchingGenerator(
            self.model.config, self.model.engine.params, gconfig,
            n_slots=sv.n_slots, max_prompt_len=sv.max_prompt_len,
            eos_token_id=sv.eos_token_id, pad_token_id=sv.pad_token_id,
            chunk_size=sv.chunk_size, spec_decode_k=sv.spec_decode_k,
            kv_pool=kv_pool,
            kv_cache_dtype=None if paged else sv.kv_cache_dtype)
        if sv.prefix_cache_bytes <= 0:
            prefix_cache = None
        elif kv_pool is not None:
            # the pool is the one KV allocator BOTH tenants share:
            # cached prefixes are pool blocks, hits alias them into
            # slot tables, eviction relieves decode OOM pressure
            from realhf_tpu.serving.prefix_cache import (
                PooledPrefixCache,
            )
            prefix_cache = PooledPrefixCache(kv_pool,
                                             sv.prefix_cache_bytes)
        else:
            prefix_cache = RadixPrefixCache(sv.prefix_cache_bytes)
        # fleet mode: register this replica under a keepalive lease so
        # the FleetRouter discovers it (and fails its work over the
        # moment the lease lapses)
        fleet = FleetRegistry(
            spec.experiment_name, spec.trial_name,
            lease_ttl=sv.lease_ttl_secs) if sv.fleet_router else None
        grow_advisor = None
        if getattr(sv, "autoscale_queue_threshold", 0) > 0:
            from realhf_tpu.system.elastic import GrowAdvisor
            grow_advisor = GrowAdvisor(sv.autoscale_queue_threshold)
        self.rollout_server = RolloutServer(
            backend,
            experiment_name=spec.experiment_name,
            trial_name=spec.trial_name,
            server_name=self.worker_name,
            queue=RequestQueue(max_depth=sv.max_queue_depth,
                               n_slots=sv.n_slots),
            max_staleness=sv.max_staleness,
            stream_tokens=sv.stream_tokens,
            prefix_cache=prefix_cache,
            fleet=fleet,
            grow_advisor=grow_advisor,
            drain_deadline_secs=sv.drain_deadline_secs,
            seed=spec.seed + self.server_index)
        self._drain_timeout = sv.drain_timeout_secs
        if fleet is not None:
            # ride the heartbeat beacon: the fleet lease must keep
            # beating while the serve loop sits in a long jit compile,
            # exactly like the PR-1 worker heartbeat itself
            self.server.add_beat_hook(self.rollout_server.lease_beat)
        logger.info("Gen server %s configured: role=%s slots=%d "
                    "staleness=%s fleet=%s prefix_cache=%dB "
                    "spec_k=%d.", self.worker_name, sv.model_role,
                    sv.n_slots, sv.max_staleness, sv.fleet_router,
                    sv.prefix_cache_bytes, sv.spec_decode_k)
        return dict(address=self.rollout_server.address)

    # ------------------------------------------------------------------
    def _poll(self) -> worker_base.PollResult:
        n = self.rollout_server.serve_step(poll_timeout=0.02)
        return worker_base.PollResult(sample_count=n,
                                      batch_count=1 if n else 0)

    def _handle_command(self, cmd: str, kwargs: Dict) -> Any:
        if cmd == "stats":
            return self.rollout_server.stats()
        if cmd == "update_weights":
            return self._update_weights(**(kwargs or {}))
        if cmd == "update_weights_chunks":
            return self._update_weights_chunks(**(kwargs or {}))
        if cmd == "drain":
            self.rollout_server.drain(timeout=self._drain_timeout)
            return self.rollout_server.stats()
        return super()._handle_command(cmd, kwargs)

    def _health_extra(self) -> Dict:
        """Serving fields for /healthz (obs/http.py): drain state
        (flips the endpoint to 503/DRAINING the moment a drain
        starts), the fleet lease's fencing epoch, weight version, and
        load figures."""
        rs = getattr(self, "rollout_server", None)
        if rs is None:
            return {}
        return dict(draining=bool(rs._draining),
                    fencing_epoch=rs.fencing_epoch,
                    weight_version=rs.weight_sync.version,
                    queue_depth=len(rs.queue),
                    live_slots=rs.scheduler.n_live)

    def _preempt_hook(self, grace: float):
        """Drain-on-preempt (docs/serving.md "Shutdown"): on a
        preemption notice the server stops admitting, bounces queued
        requests with ``protocol.DRAINING`` (the wire kinds and
        reasons are declared in serving/protocol.py, which is
        normative), and finishes (or cancels) in-flight
        sequences inside the grace window -- clients see terminal
        events, never a socket that silently vanished. The remaining
        grace after the drain lets late fetches of the final events
        complete before the PREEMPTED exit."""
        budget = max(0.0, min(self._drain_timeout, grace * 0.8))
        logger.warning("Gen server %s preempted: draining within "
                       "%.1fs.", self.worker_name, budget)
        self.rollout_server.drain(timeout=budget)

    def _update_weights(self, version: int, path: str = None) -> Dict:
        if path is not None:
            from realhf_tpu.models.hf import load_hf_checkpoint
            _, params = load_hf_checkpoint(
                path, self.spec.models[self.spec.serving.model_role]
                .hf_family)
        else:
            params = self.rollout_server.scheduler.backend.params
        self.rollout_server.weight_sync.push(params, version)
        return dict(pending_version=version,
                    installed_version=self.rollout_server.weight_sync.version)

    def _update_weights_chunks(self, message: Dict) -> Dict:
        """Chunked weight push (docs/serving.md "Chunked weight
        distribution"): apply one ``WeightDistributor`` payload. The
        receiver keeps leaf state between pushes, so a dedup'd push
        still installs a full tree; a missing-base reply makes the
        distributor resync this replica with a direct full push."""
        if getattr(self, "_chunk_receiver", None) is None:
            from realhf_tpu.serving.weight_dist import (
                ChunkedWeightReceiver,
            )
            self._chunk_receiver = ChunkedWeightReceiver(
                self.rollout_server.weight_sync)
        return self._chunk_receiver.apply(message)

    def _exit_hook(self):
        if getattr(self, "rollout_server", None) is not None:
            self.rollout_server.drain(timeout=self._drain_timeout)
            self.rollout_server.close()


class RouterWorker(worker_base.Worker):
    """The serving fleet's front door: one FleetRouter in the worker
    stack (docs/serving.md "Fleet, failover & circuit breakers").

    Same PR-1 plumbing as every worker (heartbeats, watchdog
    attribution, preemption notices); the poll loop IS the routing
    loop. Clients rendezvous exactly like against a single server::

        RolloutClient(experiment_name=..., trial_name=...,
                      server_name="router")

    Extra commands: ``stats`` (router + per-replica breaker view),
    ``drain`` (stop admission, flush in-flight), ``probe {name}``
    (hedged blocking health check of one replica).
    """

    def _configure(self, config: Dict):
        from realhf_tpu.api.experiment import ExperimentSpec
        from realhf_tpu.serving.fleet import FleetRegistry
        from realhf_tpu.serving.router import FleetRouter

        with open(config["spec_path"], "rb") as f:
            spec: ExperimentSpec = pickle.load(f)
        self.spec = spec
        constants.set_experiment_trial_names(spec.experiment_name,
                                             spec.trial_name)
        sv = spec.serving
        if sv is None:
            raise ValueError(
                "RouterWorker needs ExperimentSpec.serving (see "
                "experiments/serve_exp.py).")
        registry = FleetRegistry(spec.experiment_name, spec.trial_name,
                                 lease_ttl=sv.lease_ttl_secs)
        router_kw = dict(
            router_name=self.worker_name,
            experiment_name=spec.experiment_name,
            trial_name=spec.trial_name,
            max_pending=sv.router_max_pending,
            dispatch_timeout=sv.router_dispatch_timeout_secs,
            response_timeout=sv.router_response_timeout_secs,
            hedge_delay=sv.router_hedge_delay_secs,
            max_hedges=sv.router_max_hedges,
            breaker_failures=sv.router_breaker_failures,
            breaker_cooldown=sv.router_breaker_cooldown_secs,
            affinity_prefix_len=sv.router_affinity_prefix_len,
            fleet_poll_interval=min(0.5, sv.lease_ttl_secs / 4.0))
        if getattr(sv, "n_routers", 1) > 1:
            # sharded router plane (docs/serving.md "Sharded router
            # plane"): this shard registers its own lease/epoch in the
            # registry and owns a consistent-hash slice of rid space;
            # clients discover the ring through the registry
            # (ShardedRolloutClient), so no singleton rendezvous key
            from realhf_tpu.serving.router_shard import ShardedRouter
            self.router = ShardedRouter(registry, **router_kw)
        else:
            self.router = FleetRouter(registry, **router_kw)
        self._drain_timeout = sv.drain_timeout_secs
        logger.info("Router %s configured: lease_ttl=%.1fs hedge=%s "
                    "breaker=%d/%.1fs.", self.worker_name,
                    sv.lease_ttl_secs, sv.router_hedge_delay_secs,
                    sv.router_breaker_failures,
                    sv.router_breaker_cooldown_secs)
        return dict(address=self.router.address)

    def _poll(self) -> worker_base.PollResult:
        n = self.router.route_step(poll_timeout=0.02)
        return worker_base.PollResult(sample_count=n,
                                      batch_count=1 if n else 0)

    def _handle_command(self, cmd: str, kwargs: Dict) -> Any:
        if cmd == "stats":
            return self.router.stats()
        if cmd == "drain":
            self.router.drain(timeout=self._drain_timeout)
            return self.router.stats()
        if cmd == "probe":
            return dict(alive=self.router.probe(**(kwargs or {})))
        return super()._handle_command(cmd, kwargs)

    def _health_extra(self) -> Dict:
        router = getattr(self, "router", None)
        if router is None:
            return {}
        replicas = router._replicas
        return dict(draining=bool(router._draining),
                    pending=len(router._pending),
                    inflight=len(router._requests),
                    replicas_live=sum(1 for r in replicas.values()
                                      if not r.lost),
                    replicas_healthy=sum(
                        1 for r in replicas.values()
                        if not r.lost and not r.retiring
                        and r.breaker.allow()))

    def _preempt_hook(self, grace: float):
        budget = max(0.0, min(self._drain_timeout, grace * 0.8))
        logger.warning("Router %s preempted: draining within %.1fs.",
                       self.worker_name, budget)
        self.router.drain(timeout=budget)

    def _exit_hook(self):
        if getattr(self, "router", None) is not None:
            self.router.drain(timeout=self._drain_timeout)
            self.router.close()


class GatewayWorker(worker_base.Worker):
    """The HTTP front door in the worker stack (docs/serving.md
    "Front door"): one :class:`~realhf_tpu.serving.gateway.
    GatewayServer` exposing OpenAI-compatible streaming
    ``/v1/completions`` over SSE, fronting the router plane with
    per-tenant quotas, SLO classes, and deadline-aware shedding.

    The HTTP server runs on its own daemon threads; the worker's poll
    loop only keeps the heartbeat/watchdog plumbing fed and reports
    request throughput. Extra commands: ``stats`` (gateway + policy +
    brownout view), ``drain`` (refuse new admissions with 503).
    """

    def _configure(self, config: Dict):
        from realhf_tpu.api.experiment import ExperimentSpec
        from realhf_tpu.base import name_resolve
        from realhf_tpu.serving.gateway import (
            BrownoutLadder,
            GatewayPolicy,
            GatewayServer,
            RouterLoadProbe,
            gateway_http_key,
            telemetry_metrics_fetch,
        )

        with open(config["spec_path"], "rb") as f:
            spec: ExperimentSpec = pickle.load(f)
        self.spec = spec
        constants.set_experiment_trial_names(spec.experiment_name,
                                             spec.trial_name)
        sv = spec.serving
        if sv is None:
            raise ValueError(
                "GatewayWorker needs ExperimentSpec.serving (see "
                "experiments/serve_exp.py).")

        # one RolloutClient-shaped backend per pooled connection:
        # sharded plane -> ShardedRolloutClient (ring discovery +
        # failover), fleet -> the router, single server -> direct
        fleet = bool(sv.fleet_router)
        sharded = fleet and getattr(sv, "n_routers", 1) > 1
        if sharded:
            from realhf_tpu.serving.fleet import FleetRegistry
            from realhf_tpu.serving.router_shard import (
                ShardedRolloutClient,
            )

            def client_factory():
                return ShardedRolloutClient(FleetRegistry(
                    spec.experiment_name, spec.trial_name,
                    lease_ttl=sv.lease_ttl_secs))
        else:
            from realhf_tpu.serving.server import RolloutClient
            upstream = "router/0" if fleet else "rollout/0"

            def client_factory():
                return RolloutClient(
                    experiment_name=spec.experiment_name,
                    trial_name=spec.trial_name,
                    server_name=upstream)

        # the shed decision reads the router plane's own telemetry
        # (queue depth gauges + latency p95) -- no new signal path
        load_probe = None
        if fleet:
            load_probe = RouterLoadProbe(
                telemetry_metrics_fetch(spec.experiment_name,
                                        spec.trial_name, "router/0"),
                n_slots=sv.n_servers * sv.n_slots)
        policy = GatewayPolicy(
            tenants=dict(sv.gateway_tenants),
            default_rate=sv.gateway_tenant_rate,
            default_burst=sv.gateway_tenant_burst,
            interactive_slo_secs=sv.gateway_interactive_slo_secs,
            batch_slo_secs=sv.gateway_batch_slo_secs,
            trim_max_new_tokens=sv.gateway_trim_max_new_tokens,
            load_probe=load_probe,
            brownout=BrownoutLadder())
        self.gateway = GatewayServer(
            client_factory, policy=policy,
            port=sv.gateway_port, process_name=self.worker_name,
            stream_timeout=sv.gateway_stream_timeout_secs).start()
        name_resolve.add(
            gateway_http_key(spec.experiment_name, spec.trial_name,
                             self.worker_name),
            self.gateway.address, replace=True)
        self._drain_timeout = sv.drain_timeout_secs
        self._last_requests = 0
        logger.info("Gateway %s serving on %s (fleet=%s sharded=%s).",
                    self.worker_name, self.gateway.address, fleet,
                    sharded)
        return dict(address=self.gateway.address)

    def _poll(self) -> worker_base.PollResult:
        n = self.gateway.stats["http_requests"] - self._last_requests
        self._last_requests += n
        return worker_base.PollResult(sample_count=n,
                                      batch_count=1 if n else 0)

    def _handle_command(self, cmd: str, kwargs: Dict) -> Any:
        if cmd == "stats":
            return dict(gateway=dict(self.gateway.stats),
                        policy=dict(self.gateway.policy.stats),
                        brownout_level=self.gateway.policy.brownout
                        .level)
        if cmd == "drain":
            self.gateway.start_drain()
            return dict(self.gateway.stats)
        return super()._handle_command(cmd, kwargs)

    def _health_extra(self) -> Dict:
        gw = getattr(self, "gateway", None)
        if gw is None:
            return {}
        return dict(draining=bool(gw._draining),
                    http_requests=gw.stats["http_requests"],
                    streams=gw.stats["streams"],
                    brownout_level=gw.policy.brownout.level)

    def _preempt_hook(self, grace: float):
        logger.warning("Gateway %s preempted: refusing new "
                       "admissions.", self.worker_name)
        self.gateway.start_drain()

    def _exit_hook(self):
        if getattr(self, "gateway", None) is not None:
            self.gateway.start_drain()
            self.gateway.stop()
