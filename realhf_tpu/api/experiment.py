"""Experiment specification: what the launcher materializes.

Parity with the reference's two-level config system
(``realhf/api/core/system_api.py`` ExperimentConfig +
``api/quickstart/model.py`` ModelTrainEvalConfig): an experiment names
its models (role -> spec), the dataflow graph of MFCs, the dataset,
and run control (epochs, save/eval frequency, seed).
"""

import dataclasses
from typing import Dict, List, Optional

from realhf_tpu.api.config import DatasetAbstraction
from realhf_tpu.api.dfg import MFCDef
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.parallel.mesh import ParallelismConfig


@dataclasses.dataclass
class ModelSpec:
    """One model role (reference ModelTrainEvalConfig,
    quickstart/model.py:114)."""
    hf_family: str = "llama"
    path: Optional[str] = None  # HF checkpoint dir; None = random init
    # Used when path is None (testing / benchmarking):
    random_init_config: Optional[dict] = None
    is_critic: bool = False
    init_critic_from_actor: bool = False
    optimizer: Optional[OptimizerConfig] = None
    parallel: ParallelismConfig = dataclasses.field(
        default_factory=ParallelismConfig)
    gradient_checkpointing: bool = True
    bf16: bool = True
    # Host-RAM-bounded checkpoint load (hf/registry.py
    # load_hf_checkpoint_streamed): place weights layer-by-layer
    # directly onto the mesh; peak host memory = one transformer layer
    # + embeddings instead of the full model. Required for >host-RAM
    # models (70B). None (default) = automatic: stream when the
    # checkpoint's safetensors total exceeds 16 GB (single-process
    # meshes only -- process-spanning meshes need the explicit flag so
    # every member takes the same collective path); True/False force.
    streamed_load: Optional[bool] = None
    # Set by the RECOVERY path when `path` was redirected to a recover
    # checkpoint: restore saved Adam moments/master alongside the
    # weights. Never set for ordinary warm-starts from a checkpoint
    # dir -- a new run must begin with fresh optimizer state even if
    # the dir carries an optimizer_state.npz.
    restore_optimizer_state: bool = False
    # pp/ctx meshes generate through a decode view -- a SECOND full
    # weight copy on a collapsed dp x tp mesh (Engine.decode_engine).
    # True frees that copy after every generate MFC (steady-state HBM
    # back to one copy, the 70B OOM frontier) at the price of one
    # cross-mesh reshard per rollout; False keeps it resident so only
    # weight changes pay the reshard.
    drop_decode_view_after_rollout: bool = False


@dataclasses.dataclass
class MFCAllocation:
    """Per-MFC placement: layout + (optionally) its own worker group
    and per-worker device subset.

    The reference's RPCAllocation (quickstart/device_mesh.py:269):
    every MFC may run on its own device subset of the cluster with its
    own 3D-parallel strategy. ``workers=None`` keeps the MFC on its
    role's primary worker group (same devices, different layout =>
    same-group replica). ``workers`` different from the role's group
    puts the MFC on OTHER processes/devices entirely; the role's
    weights then flow to it through the host data plane after every
    train step (same-role cross-group reallocation -- the reference's
    param_realloc NCCL broadcast, comm/param_realloc.py:312, as a
    DCN-class host relay per SURVEY §5.8).

    ``device_ids``: local device indices each exec worker contributes
    to this MFC's mesh (reference per-worker GPU isolation,
    base/gpu_utils.py:64); None = the worker's default slice.
    """
    parallel: ParallelismConfig
    workers: Optional[List[int]] = None
    device_ids: Optional[List[int]] = None


@dataclasses.dataclass
class SaveEvalControl:
    """Reference ExperimentSaveEvalControl (system_api.py:157)."""
    save_freq_epochs: Optional[int] = None
    save_freq_steps: Optional[int] = None
    save_freq_secs: Optional[float] = None
    eval_freq_epochs: Optional[int] = None
    eval_freq_steps: Optional[int] = None
    benchmark_steps: Optional[int] = None  # stop early after N steps


@dataclasses.dataclass
class FaultToleranceConfig:
    """Knobs for the fault-tolerant runtime (heartbeats, watchdog,
    retry/backoff, requeue); see docs/distributed.md "Fault tolerance
    & recovery"."""
    # liveness: WorkerServer beats every interval; a beat older than
    # heartbeat_timeout marks the worker LOST
    heartbeat_interval: float = 2.0
    heartbeat_timeout: float = 20.0
    watchdog_poll_secs: float = 1.0
    # allowance for process spawn + jax import before the first beat
    startup_grace_secs: float = 120.0
    # requeue: how often one MFC may be requeued after worker loss
    # before the trial fails (relaunch-level recovery takes over)
    max_mfc_retries: int = 1
    # a worker continuously LOST this long fails the trial even if
    # nothing was in flight on it (it will be needed eventually)
    worker_lost_fatal_secs: float = 60.0
    # excluded_workers backoff: a lost worker is kept out of dispatch
    # for base * 2**(losses-1) seconds (capped, jittered)
    exclude_base_secs: float = 5.0
    exclude_max_secs: float = 120.0
    # save/eval dispatch+gather: attempts and per-attempt timeout.
    # The retry stack's TOTAL wall clock is additionally capped by
    # gather_max_elapsed_secs (RetryPolicy.max_elapsed) so stacked
    # backoffs during a degradation event cannot outlive the watchdog
    # grace window and mask a real worker loss.
    gather_retries: int = 2
    gather_timeout_secs: float = 600.0
    gather_max_elapsed_secs: Optional[float] = None
    # --- elastic degraded-mode training (system/elastic.py) ----------
    # re-plan MFCs of LOST/preempted workers onto survivors instead of
    # requeue-and-hope; re-expand when the worker rejoins
    elastic_degrade: bool = False
    # launcher resubmits a PREEMPTED worker's process once it exits
    # (the "replacement worker rejoins" path)
    elastic_rejoin: bool = False
    # grace window a preempted worker gets to drain + emergency-save
    preempt_grace_secs: float = 15.0
    # at most this many adopted (migrated) MFC replicas per survivor:
    # each adoption is a full extra weight copy in HBM
    max_adopted_per_worker: int = 2
    # --- host failure domains (system/pod.py) ------------------------
    # workers of one host whose heartbeats go stale within this many
    # seconds of each other are attributed as ONE HOST_LOST (one
    # flight event, one backoff entry) instead of N independent
    # losses. None -> the watchdog defaults to heartbeat_timeout.
    host_lost_window_secs: Optional[float] = None
    # --- durable checkpoints (system/ckpt_manager.py) ----------------
    # route model-worker saves through the sharded-manifest manager
    # (per-shard checksums, atomic COMMITTED marker, verified load
    # with fallback, GC); the HF layout is preserved via a `latest`
    # symlink so external consumers keep working
    durable_ckpt: bool = True
    # committed checkpoints retained per role (older ones are GCed)
    ckpt_keep: int = 2


@dataclasses.dataclass
class ServingSpec:
    """A standalone rollout/serving deployment (docs/serving.md): one
    or more ``GenServerWorker`` processes, each running a
    continuous-batching ``RolloutServer`` over the named model role.
    Launched by ``apps.main.run_serve`` -- standalone or alongside a
    training trial as its asynchronous rollout producer."""
    model_role: str = "default"
    n_servers: int = 1
    #: decode slots per server (concurrent sequences in the batch)
    n_slots: int = 4
    #: decode steps per host<->device sync
    chunk_size: int = 8
    max_prompt_len: int = 512
    #: admission control: queue entries beyond this are rejected with
    #: a retry_after hint (backpressure) instead of growing unbounded
    max_queue_depth: int = 256
    #: reject/evict sequences whose start weight version lags the
    #: installed version by more than this; None disables the bound
    max_staleness: Optional[int] = None
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0
    #: GenerationHyperparameters kwargs (max_new_tokens, greedy, ...);
    #: force_no_logits_mask is always set -- inflight serving never
    #: produces the PPO logits mask
    gconfig: dict = dataclasses.field(default_factory=dict)
    #: send incremental token deltas after every decode chunk
    stream_tokens: bool = True
    # -- paged KV pool (docs/perf.md "Paged KV & quantization"):
    # replace the dense per-slot [cache_len] KV windows with one
    # block-granular device pool (engine/kv_pool.py) shared with the
    # radix prefix cache. Decode memory then tracks ACTUAL tokens, so
    # concurrency is bounded by blocks, not worst-case windows, and
    # admission backpressure rides pool free blocks.
    paged_kv: bool = False
    #: KV storage dtype: None = the model's compute dtype (dense
    #: semantics); "fp32"/"bf16" set the storage dtype; "int8" stores
    #: quantized rows + per-row scales (requires/implies paged_kv --
    #: dequant-on-read lives in the pool gather path).
    kv_cache_dtype: Optional[str] = None
    #: tokens per pool block (the allocation granule; internal
    #: fragmentation is < 1 block per sequence)
    kv_block_len: int = 16
    #: total pool blocks; None sizes the pool at dense parity
    #: (n_slots * ceil(cache_len / kv_block_len)) -- shrink it to
    #: trade worst-case headroom for more decode slots per byte
    kv_pool_blocks: Optional[int] = None
    # -- serving hot path (docs/serving.md "Prefix cache &
    # speculative decoding") --------------------------------------
    #: byte budget for the radix prefix/KV cache (host memory):
    #: requests sharing a cached prefix skip its prefill and only run
    #: the uncached suffix. 0 disables reuse entirely (behaviorally
    #: identical to a cache-less server).
    prefix_cache_bytes: int = 64 * 1024 * 1024
    #: prompt-lookup speculative decoding: draft k tokens per round
    #: from the request's own history and verify them in one forward
    #: (greedy-exact; ignored unless gconfig is greedy). 0 disables.
    spec_decode_k: int = 0
    #: seconds drain() waits for in-flight sequences at shutdown
    drain_timeout_secs: float = 30.0
    #: HARD deadline on any drain: in-flight sequences still running
    #: past it are force-fenced with explicit
    #: ``cancelled(reason=drain_deadline)`` terminals (never silent
    #: loss) and a flight event names the abandoned rids. None = the
    #: drain timeout itself is the deadline.
    drain_deadline_secs: Optional[float] = None
    #: log-only autoscaling advisory (superseded by the closed loop
    #: below, kept for single-server deployments): when a server's
    #: queue depth stays above this threshold, an ElasticPlanner GROW
    #: suggestion is emitted (counter + flight event + warning log --
    #: no fleet change). 0 disables.
    autoscale_queue_threshold: int = 0
    # -- closed-loop autoscaling (docs/serving.md "Autoscaling"):
    # run_serve supervises an AutoscaleController that spawns/retires
    # GenServer replicas from live router signals. Requires
    # fleet_router (the router is both the signal source and the
    # discovery path for new replicas).
    autoscale: bool = False
    #: replica-count bounds; scale-down never goes below the floor
    #: (and never takes the last healthy replica while traffic is in
    #: flight, even with floor 0)
    autoscale_min_replicas: int = 1
    autoscale_max_replicas: int = 4
    #: seconds between policy observations in the launcher loop
    autoscale_interval_secs: float = 2.0
    #: scale-up pressure: queued requests per live replica above this
    autoscale_up_queue_per_replica: int = 8
    #: scale-up pressure: response-latency EWMA above this (None off)
    autoscale_up_latency_secs: Optional[float] = None
    #: consecutive pressured/idle observations before acting
    autoscale_consecutive_up: int = 3
    autoscale_consecutive_down: int = 10
    #: scale-down idle bound: in-flight per REMAINING replica
    autoscale_down_idle_per_replica: float = 1.0
    #: same-direction re-arm time between actions
    autoscale_cooldown_secs: float = 30.0
    #: seconds a spawned replica gets to register before the spawn is
    #: written off as failed
    autoscale_spawn_deadline_secs: float = 180.0
    #: where run_serve reads the router's autoscale signals:
    #: "zmq" (default; the router's stats worker command) or "http"
    #: (GET the router's /metrics telemetry endpoint -- the same
    #: Prometheus text a real scraper sees, resolved through
    #: names.telemetry; falls back to zmq when unreachable)
    autoscale_signal_source: str = "zmq"
    #: which latency figure feeds the scale-up policy: "ewma"
    #: (default), or "p50"/"p95" from the router_latency_seconds
    #: histogram (tail latency reacts to stragglers the EWMA smooths
    #: over)
    autoscale_latency_signal: str = "ewma"
    # -- resilient fleet mode (docs/serving.md "Fleet, failover &
    # circuit breakers"): a FleetRouter fronts the n_servers replicas;
    # replicas register leases in the fleet registry and clients talk
    # to the router (server_name="router") instead of a replica.
    fleet_router: bool = False
    #: replica lease TTL; a replica silent for this long vanishes from
    #: the registry and its in-flight work fails over
    lease_ttl_secs: float = 5.0
    #: dispatch a speculative duplicate when a request has not started
    #: within this many seconds (None disables hedging)
    router_hedge_delay_secs: Optional[float] = None
    router_max_hedges: int = 1
    #: consecutive failures that open a replica's circuit breaker
    router_breaker_failures: int = 3
    #: seconds an open breaker waits before the half-open probe
    router_breaker_cooldown_secs: float = 5.0
    #: no reply at all to a dispatched request within this -> failover
    router_dispatch_timeout_secs: float = 10.0
    #: an accepted request silent for this long -> failover (None
    #: disables; covers a dropped terminal-event send)
    router_response_timeout_secs: Optional[float] = 60.0
    #: cap on router-tracked in-flight requests (backpressure beyond)
    router_max_pending: int = 1024
    #: prefix-affinity dispatch: hash a request's first N tokens and
    #: prefer the replica that last served that hash, so fleet traffic
    #: concentrates prefix-cache hits instead of spraying a shared
    #: system prompt across every replica. 0 disables (pure
    #: least-loaded). Health/breaker/fencing gates always win.
    router_affinity_prefix_len: int = 16
    # -- sharded router plane (docs/serving.md "Sharded router
    # plane"): with n_routers > 1, that many RouterWorker shards
    # split rid space by consistent hash; each holds its own
    # lease/epoch and a shard death re-homes its range to survivors.
    n_routers: int = 1
    # -- chunked weight distribution (docs/serving.md "Chunked weight
    # distribution"): content-hashed chunk pushes over a relay tree
    # instead of full-copy unicast per replica.
    #: max raw bytes packed per chunk
    weight_push_chunk_bytes: int = 4 << 20
    #: wire encoding for pushed chunks: "raw" or "int8" (per-row
    #: symmetric quantization, reusing the paged-KV helpers)
    weight_push_encoding: str = "raw"
    #: relay-tree fanout; 0 = unicast (root pushes to every replica)
    weight_push_fanout: int = 2
    # -- HTTP front door (docs/serving.md "Front door"): a
    # GatewayWorker serving OpenAI-compatible streaming
    # ``/v1/completions`` over SSE, fronting the router plane with
    # per-tenant quotas, SLO classes, and deadline-aware shedding.
    gateway: bool = False
    #: TCP port for the gateway's HTTP listener; 0 = OS-assigned (the
    #: bound address is published via name_resolve either way)
    gateway_port: int = 0
    #: default per-tenant token-bucket refill rate (requests/second)
    #: and burst capacity; tenants absent from ``gateway_tenants``
    #: get these
    gateway_tenant_rate: float = 50.0
    gateway_tenant_burst: float = 100.0
    #: per-tenant overrides: ``{tenant: {"rate": .., "burst": ..}}``
    gateway_tenants: Dict[str, dict] = dataclasses.field(
        default_factory=dict)
    #: SLO budgets (seconds): a request without an explicit
    #: ``deadline_secs`` gets its class's budget as the deadline the
    #: shed decision evaluates against
    gateway_interactive_slo_secs: float = 2.0
    gateway_batch_slo_secs: float = 30.0
    #: brownout level 2+ trims ``max_tokens`` down to this
    gateway_trim_max_new_tokens: int = 32
    #: seconds the gateway waits on a wire stream before closing the
    #: HTTP request with an ``expired`` terminal
    gateway_stream_timeout_secs: float = 120.0


@dataclasses.dataclass
class ExperimentSpec:
    experiment_name: str
    trial_name: str
    models: Dict[str, ModelSpec]
    mfcs: List[MFCDef]
    dataset: DatasetAbstraction
    # Per-MFC placement overrides (MFC name -> layout or full
    # MFCAllocation). An MFC whose layout differs from its role's
    # primary creates a weight replica kept fresh by parameter
    # reallocation; an MFCAllocation with its own ``workers`` puts the
    # replica on a different worker group / device subset entirely
    # (the reference's RPCAllocation, quickstart/device_mesh.py:269).
    allocations: Dict[str, object] = dataclasses.field(
        default_factory=dict)
    tokenizer_path: Optional[str] = None
    tokenizer: Optional[object] = None  # direct object (tests)
    total_train_epochs: int = 1
    seed: int = 1
    ctl: SaveEvalControl = dataclasses.field(default_factory=SaveEvalControl)
    ft: FaultToleranceConfig = dataclasses.field(
        default_factory=FaultToleranceConfig)
    eval_dataset: Optional[DatasetAbstraction] = None
    # --- distributed runtime (mode=distributed) -----------------------
    # Number of model-worker processes; each owns its own device set
    # and the roles assigned to it (reference: ModelWorker per GPU;
    # on TPU one worker per host-slice).
    n_model_workers: int = 1
    # role -> model worker index OR list of indices (a worker GROUP:
    # the role's mesh spans every group member's devices, and the
    # members form one jax.distributed world -- the reference's
    # multi-node model spanning multiple ModelWorkers). Unassigned
    # roles land on worker 0. The first index is the group LEADER: it
    # owns the dataset/reply protocol for the role.
    worker_assignment: Dict[str, object] = dataclasses.field(
        default_factory=dict)
    # Buffer capacity: how many dataset batches may be in flight at
    # once (>=2 lets MFCs of consecutive steps overlap on disjoint
    # meshes; reference AsyncIOSequenceBuffer pipelining). With the
    # per-sample buffer this also bounds the largest per-MFC n_seqs an
    # assembly can ever satisfy: capacity * source n_seqs samples.
    max_concurrent_batches: int = 2
    # How many of its OWN batches a non-train MFC may run ahead of its
    # role's train MFCs, measured on per-sample consumption watermarks
    # (reference master_worker.py:503-509 staleness guard; with
    # uniform n_seqs this is exactly "k-1-offpolicyness batches").
    max_head_offpolicyness: int = 0
    # Auto-resolve OffloadHooks: non-trainable roles (ref/reward) move
    # their weights to host after their last MFC of a step, freeing
    # HBM for the train MFCs, and reload on next use (reference
    # resolve_rpc_hooks, experiments/common/utils.py:143 +
    # model_worker.py:542-552).
    auto_offload: bool = False
    # Rollout/serving deployment (apps.main.run_serve spawns
    # ``serving.n_servers`` GenServerWorker processes); None for
    # ordinary training trials.
    serving: Optional[ServingSpec] = None

    def workers_of_role(self, role: str) -> List[int]:
        """Worker group of a role (leader first). Single-int
        assignments are one-member groups."""
        v = self.worker_assignment.get(role, 0)
        if isinstance(v, int):
            return [v]
        out = list(v)
        if len(out) != len(set(out)):
            raise ValueError(f"duplicate workers in group of {role}: {v}")
        return out

    def worker_of_role(self, role: str) -> int:
        """The role's group leader (single worker in the common case)."""
        return self.workers_of_role(role)[0]

    def alloc_of(self, node_name: str) -> Optional[MFCAllocation]:
        """The MFC's allocation, normalized to MFCAllocation (bare
        ParallelismConfig values keep the role's worker group)."""
        v = self.allocations.get(node_name)
        if v is None:
            return None
        if isinstance(v, MFCAllocation):
            return v
        return MFCAllocation(parallel=v)

    def workers_of_node(self, node_name: str, role: str) -> List[int]:
        """The worker group an MFC EXECUTES on (leader first): its
        allocation's own group when set, else its role's group."""
        alloc = self.alloc_of(node_name)
        if alloc is not None and alloc.workers is not None:
            out = list(alloc.workers)
            if not out:
                raise ValueError(
                    f"MFCAllocation for {node_name} has an empty "
                    "workers list; use workers=None for the role's "
                    "own group.")
            if len(out) != len(set(out)):
                raise ValueError(
                    f"duplicate workers in group of {node_name}: {out}")
            return out
        return self.workers_of_role(role)

    def is_cross_group(self, node_name: str, role: str) -> bool:
        """True when the MFC executes on a different worker group than
        its role's primary -- weights then flow via the host data
        plane (same-role cross-group reallocation)."""
        return (set(self.workers_of_node(node_name, role))
                != set(self.workers_of_role(role)))

    @property
    def multihost(self) -> bool:
        """True when any role's (or MFC allocation's) mesh spans more
        than one worker process -- all model workers then join one
        jax.distributed world (the reference's single NCCL world,
        global_comm.py:44). Cross-group single-worker placements do
        NOT need a shared world: each group's mesh is process-local
        and weights move over the host data plane."""
        if any(len(self.workers_of_role(r)) > 1 for r in self.models):
            return True
        return any(
            a is not None and a.workers is not None and len(a.workers) > 1
            for a in (self.alloc_of(n) for n in self.allocations))
