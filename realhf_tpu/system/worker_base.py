"""Worker process skeleton + the controller's control panel.

Parity with reference ``realhf/system/worker_base.py``: a Worker runs a
poll loop, obeys configure/start/pause/exit commands, and publishes its
status through name_resolve; the controller's WorkerControlPanel issues
group commands over per-worker ZMQ REQ/REP sockets and monitors
statuses for failure detection (reference controller ``wait:275``).
"""

import dataclasses
import enum
import os
import pickle
import signal
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

import zmq

from realhf_tpu import obs
from realhf_tpu.base import cluster, logging, name_resolve, names, network
from realhf_tpu.obs import flight, metrics, tracing

logger = logging.getLogger("worker_base")

#: Heartbeat cadence knobs. Workers read the env (the launcher exports
#: the experiment's FaultToleranceConfig values before spawning); the
#: TTL handed to TTL-capable name_resolve backends (redis) is a
#: multiple of the interval so one missed beat never expires an entry.
HEARTBEAT_INTERVAL_ENV = "REALHF_TPU_HEARTBEAT_INTERVAL"
DEFAULT_HEARTBEAT_INTERVAL = 2.0
HEARTBEAT_TTL_FACTOR = 5.0

#: Preemption-notice knobs. A preempted worker (cluster SIGTERM,
#: SIGUSR1, injected `preempt` fault, or `preempt` control command)
#: publishes a notice under ``names.worker_preempt``, runs its
#: ``_preempt_hook`` (emergency checkpoint / serving drain), keeps
#: serving in-flight work for the grace window, then exits with
#: status PREEMPTED. SIGTERM handling is opt-in via
#: ``REALHF_TPU_PREEMPT_SIGTERM=1`` -- schedulers that SIGTERM for
#: plain teardown must keep getting prompt exits.
PREEMPT_GRACE_ENV = "REALHF_TPU_PREEMPT_GRACE"
PREEMPT_SIGTERM_ENV = "REALHF_TPU_PREEMPT_SIGTERM"
DEFAULT_PREEMPT_GRACE = 15.0


class WorkerServerStatus(str, enum.Enum):
    READY = "READY"
    RUNNING = "RUNNING"
    PAUSED = "PAUSED"
    COMPLETED = "COMPLETED"
    ERROR = "ERROR"
    LOST = "LOST"
    # preemption notice received: draining within the grace window
    # (while alive), terminal after the graceful exit. Accounted-for
    # in liveness terms -- never LOST.
    PREEMPTED = "PREEMPTED"


@dataclasses.dataclass
class PollResult:
    sample_count: int = 0
    batch_count: int = 0


class WorkerServer:
    """Per-worker command endpoint (REP socket registered in
    name_resolve; reference WorkerServer:77)."""

    def __init__(self, experiment_name: str, trial_name: str,
                 worker_name: str,
                 heartbeat_interval: Optional[float] = None):
        self.worker_name = worker_name
        self._exp, self._trial = experiment_name, trial_name
        ctx = zmq.Context.instance()
        self._sock = ctx.socket(zmq.REP)
        port = self._sock.bind_to_random_port("tcp://*")
        host = network.gethostip()
        name_resolve.add(
            names.worker_key(experiment_name, trial_name, worker_name),
            f"tcp://{host}:{port}", replace=True)
        #: last published status (the /healthz surface reads it
        #: without a name_resolve round-trip)
        self.status: Optional[WorkerServerStatus] = None
        # host failure domain (system/pod.py): a pod launch injects
        # REALHF_TPU_HOST_ID per host; republish it so the master-side
        # watchdog can attribute whole-host losses as ONE HOST_LOST
        self.host_id = cluster.current_host_id()
        if self.host_id:
            name_resolve.add(
                names.worker_host(experiment_name, trial_name,
                                  worker_name),
                self.host_id, replace=True, delete_on_exit=False)
        self.set_status(WorkerServerStatus.READY)
        # liveness beacon: a daemon thread re-publishes a wall-clock
        # timestamp so the controller-side watchdog (system/watchdog.py)
        # can attribute silence to a dead/hung worker. A thread (not
        # the poll loop) keeps beating through long jit compiles and
        # multi-minute MFC executions.
        if heartbeat_interval is None:
            heartbeat_interval = float(os.environ.get(
                HEARTBEAT_INTERVAL_ENV, DEFAULT_HEARTBEAT_INTERVAL))
        self._hb_interval = heartbeat_interval
        # incarnation fencing: every beat carries this process's boot
        # id. A worker that dies and is relaunched FASTER than the
        # watchdog's staleness timeout would otherwise be a silent
        # message blackhole -- in-flight PUB'd requests died with the
        # old process, yet the fresh beat hides the death. The
        # watchdog treats a boot-id change as a loss edge
        # (system/watchdog.py) so the master requeues and re-routes.
        self.boot_id = uuid.uuid4().hex[:12]
        self._hb_key = names.worker_heartbeat(experiment_name, trial_name,
                                              worker_name)
        self._preempt_key = names.worker_preempt(
            experiment_name, trial_name, worker_name)
        # a RELAUNCHED worker must not inherit its previous
        # incarnation's preemption notice -- the master reads notice
        # presence as "this worker is retiring"
        self.clear_preempt_notice()
        self._hb_stop = threading.Event()
        # extra per-beat callbacks (e.g. a rollout server's fleet
        # lease renewal, serving/fleet.py): liveness signals that must
        # keep beating while the poll loop is stuck in a long jit
        # compile or a multi-minute MFC execution ride the SAME
        # beacon thread as the heartbeat
        self._beat_hooks = []
        self.beat()  # visible before the first interval elapses
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop,
            name=f"heartbeat[{worker_name}]", daemon=True)
        self._hb_thread.start()

    def add_beat_hook(self, fn):
        """Invoke ``fn()`` on every heartbeat (beacon thread!). The
        hook must be thread-safe and non-blocking; exceptions are
        swallowed (the next beat retries)."""
        self._beat_hooks.append(fn)

    def heartbeat_age(self) -> Optional[float]:
        """Seconds since the last beat this process published (the
        /healthz liveness figure; None before the first beat)."""
        last = getattr(self, "_last_beat_at", None)
        return None if last is None else time.monotonic() - last

    def beat(self):
        """Publish one heartbeat: ``"<wall-ts>:<boot-id>"`` (wall
        clock, not monotonic: the watchdog lives in another process;
        the boot id fences incarnations)."""
        self._last_beat_at = time.monotonic()
        try:
            name_resolve.add(
                self._hb_key, f"{time.time():.3f}:{self.boot_id}",
                replace=True, delete_on_exit=False,
                keepalive_ttl=self._hb_interval * HEARTBEAT_TTL_FACTOR)
        except Exception as e:  # noqa: BLE001 - next beat retries
            logger.warning("Heartbeat publish failed for %s: %s",
                           self.worker_name, e)
        for hook in list(self._beat_hooks):
            try:
                hook()
            except Exception as e:  # noqa: BLE001 - next beat retries
                logger.warning("Beat hook %r failed for %s: %s",
                               getattr(hook, "__name__", hook),
                               self.worker_name, e)

    def _heartbeat_loop(self):
        while not self._hb_stop.wait(self._hb_interval):
            self.beat()

    def stop_heartbeat(self):
        """Stop the beacon (clean exit; terminal status takes over as
        the liveness signal)."""
        self._hb_stop.set()

    def publish_preempt_notice(self, grace: float):
        """Announce preemption: ``"<wall-ts>:<grace-secs>"`` under the
        worker's preempt key. The master reacts to the notice (elastic
        degrade + drain) BEFORE the heartbeat ever goes stale."""
        try:
            name_resolve.add(
                self._preempt_key, f"{time.time():.3f}:{grace:.3f}",
                replace=True, delete_on_exit=False)
        except Exception as e:  # noqa: BLE001 - notice is best-effort
            logger.warning("Preempt notice publish failed for %s: %s",
                           self.worker_name, e)

    def clear_preempt_notice(self):
        try:
            name_resolve.delete(self._preempt_key)
        except name_resolve.NameEntryNotFoundError:
            pass
        except Exception as e:  # noqa: BLE001 - best-effort cleanup
            logger.warning("Preempt notice clear failed for %s: %s",
                           self.worker_name, e)

    def set_status(self, status: WorkerServerStatus):
        self.status = status
        name_resolve.add(
            names.worker_status(self._exp, self._trial, self.worker_name),
            status.value, replace=True, delete_on_exit=False)

    def poll_command(self, timeout: float = 0.0):
        """Returns (command, kwargs) or None; caller must respond via
        the returned responder before polling again."""
        if not self._sock.poll(timeout * 1000):
            return None
        cmd, kwargs = pickle.loads(self._sock.recv())
        return cmd, kwargs

    def respond(self, data: Any = None):
        self._sock.send(pickle.dumps(data))


class WorkerControlPanel:
    """Controller side: group commands + status monitoring
    (reference WorkerControlPanel:217)."""

    def __init__(self, experiment_name: str, trial_name: str):
        self._exp, self._trial = experiment_name, trial_name
        self._ctx = zmq.Context.instance()
        self._socks: Dict[str, zmq.Socket] = {}

    def connect(self, worker_names: List[str], timeout: float = 120.0):
        for w in worker_names:
            addr = name_resolve.wait(
                names.worker_key(self._exp, self._trial, w), timeout=timeout)
            s = self._ctx.socket(zmq.REQ)
            try:
                s.connect(addr)
            except BaseException:
                # a bad resolved address must not leak the socket
                # (graft-lint lifecycle-leak-on-raise)
                s.close(0)
                raise
            self._socks[w] = s

    def group_request(self, command: str,
                      worker_names: Optional[List[str]] = None,
                      kwargs: Optional[Dict] = None,
                      timeout: float = 600.0) -> Dict[str, Any]:
        targets = worker_names or list(self._socks)
        return self.group_request_varied(
            command, {w: kwargs or {} for w in targets}, timeout=timeout)

    def group_request_varied(self, command: str,
                             kwargs_by_worker: Dict[str, Dict],
                             timeout: float = 600.0) -> Dict[str, Any]:
        """group_request with per-worker kwargs. All requests go out
        before any reply is awaited, so command handlers that form a
        cross-worker barrier (e.g. configure joining a jax.distributed
        world) complete even when each worker needs different kwargs.

        Failure-aware: a worker whose handler raised replies the
        exception -- re-raised here with attribution -- and a worker
        that DIED mid-command (status ERROR) fails the wait promptly
        instead of hanging out the full timeout."""
        for w, kw in kwargs_by_worker.items():
            self._socks[w].send(pickle.dumps((command, kw or {})))
        out = {}
        for w in kwargs_by_worker:
            deadline = time.monotonic() + timeout
            while not self._socks[w].poll(1000):
                if self.get_worker_status(w) == WorkerServerStatus.ERROR:
                    raise RuntimeError(
                        f"Worker {w} died (status ERROR) during "
                        f"`{command}`.")
                if time.monotonic() > deadline:
                    raise TimeoutError(f"Worker {w} did not respond "
                                       f"to `{command}`.")
            out[w] = pickle.loads(self._socks[w].recv())
            if isinstance(out[w], Exception):
                raise RuntimeError(
                    f"Worker {w} failed handling `{command}`: "
                    f"{out[w]!r}") from out[w]
        return out

    def get_worker_status(self, worker_name: str) -> WorkerServerStatus:
        try:
            return WorkerServerStatus(name_resolve.get(
                names.worker_status(self._exp, self._trial, worker_name)))
        except name_resolve.NameEntryNotFoundError:
            return WorkerServerStatus.LOST

    def all_statuses(self, worker_names: List[str]
                     ) -> Dict[str, WorkerServerStatus]:
        return {w: self.get_worker_status(w) for w in worker_names}


class Worker:
    """Poll-loop worker (reference Worker:468). Subclasses implement
    `_configure(config)` and `_poll() -> PollResult`; `run()` drives the
    state machine until exit."""

    def __init__(self, experiment_name: str, trial_name: str,
                 worker_name: str):
        self.worker_name = worker_name
        # observability (realhf_tpu/obs/): label this process's
        # tracer/metrics/flight recorder; REALHF_TPU_TRACE=1 turns on
        # span export for every worker type uniformly
        obs.configure_from_env(worker_name, experiment=experiment_name,
                               trial=trial_name)
        self.server = WorkerServer(experiment_name, trial_name, worker_name)
        self._running = False
        self._exiting = False
        self.config = None
        # preemption state machine: a signal handler only flips
        # _preempt_signaled (async-signal-safe); the run loop converts
        # it into a published notice + hook + graceful deadline.
        self._preempt_signaled = False
        self._preempt_deadline: Optional[float] = None
        self._preempt_grace: Optional[float] = None
        self._preempt_hook_ran = False
        # live HTTP telemetry endpoints (obs/http.py): /metrics,
        # /healthz, /flight, /statusz on an ephemeral port, published
        # under names.telemetry so the pod controller resolves real
        # per-worker Prometheus scrape targets (started LAST: the
        # health provider reads the state initialized above). Opt-out:
        # REALHF_TPU_TELEMETRY=0. Never fatal.
        from realhf_tpu.obs import http as obs_http
        self.telemetry = obs_http.start_from_env(
            worker_name, health=self._telemetry_health)
        if self.telemetry is not None:
            try:
                name_resolve.add(
                    names.telemetry(experiment_name, trial_name,
                                    worker_name),
                    self.telemetry.address, replace=True)
            except Exception as e:  # noqa: BLE001 - scrape discovery
                # is advisory; the endpoints still answer directly
                logger.warning("Telemetry publish failed for %s: %s",
                               worker_name, e)

    # -- subclass API ---------------------------------------------------
    def _configure(self, config: Any):
        raise NotImplementedError()

    def _poll(self) -> PollResult:
        raise NotImplementedError()

    def _exit_hook(self):
        """Last-chance cleanup/checkpoint on exit (reference
        model_worker.py:953 recover save)."""

    def _preempt_hook(self, grace: float):
        """Emergency work on a preemption notice, run ONCE from the
        poll loop (never the signal handler) with ``grace`` seconds
        left: model workers emergency-save a durable checkpoint,
        serving workers drain (docs/serving.md)."""

    def _health_extra(self) -> Dict:
        """Subclass hook: extra /healthz fields. A truthy
        ``draining`` key flips the reported state to DRAINING (-> HTTP
        503) while the worker is otherwise RUNNING, so probers stop
        sending traffic the moment a serving drain starts."""
        return {}

    def _telemetry_health(self) -> Dict:
        """The /healthz payload (obs/http.py): worker status,
        heartbeat age, incarnation/host identity, plus whatever the
        subclass adds (lease/epoch state for serving workers)."""
        status = self.server.status
        state = status.value if status is not None else "UNKNOWN"
        if self.preempted:
            state = WorkerServerStatus.PREEMPTED.value
        try:
            extra = dict(self._health_extra() or {})
        except Exception as e:  # noqa: BLE001 - a subclass bug must
            # degrade the answer, not kill the endpoint
            extra = dict(health_extra_error=repr(e))
        if extra.pop("draining", False) and state == "RUNNING":
            state = "DRAINING"
        return dict(
            worker=self.worker_name, state=state,
            status=status.value if status is not None else None,
            running=self._running,
            preempted=self.preempted,
            heartbeat_age_secs=self.server.heartbeat_age(),
            boot_id=self.server.boot_id,
            host_id=self.server.host_id, **extra)

    # -- preemption -----------------------------------------------------
    @property
    def preempted(self) -> bool:
        return self._preempt_deadline is not None

    def notice_preemption(self, grace: Optional[float] = None,
                          reason: str = "signal"):
        """Enter the preemption grace window: publish the notice and
        status PREEMPTED (the master stops dispatching new work here
        and starts elastic degradation), keep serving in-flight work,
        and exit gracefully when the window closes. Idempotent."""
        if self._preempt_deadline is not None:
            return
        if grace is None:
            grace = float(os.environ.get(PREEMPT_GRACE_ENV,
                                         DEFAULT_PREEMPT_GRACE))
        grace = max(0.0, float(grace))
        self._preempt_grace = grace
        self._preempt_deadline = time.monotonic() + grace
        logger.warning(
            "Worker %s PREEMPTED (%s): %.1fs grace window; draining.",
            self.worker_name, reason, grace)
        self.server.publish_preempt_notice(grace)
        self.server.set_status(WorkerServerStatus.PREEMPTED)
        # postmortem trail: record AND dump now -- the process may be
        # SIGKILLed before the grace window closes
        flight.record("preempted", reason=reason, grace=grace)
        metrics.inc("worker_preempted_total")
        flight.dump(reason=f"preempted ({reason})")

    def _install_signal_handlers(self):
        """SIGUSR1 always means preemption notice; SIGTERM only when
        ``REALHF_TPU_PREEMPT_SIGTERM=1`` (schedulers that terminate
        with SIGTERM for teardown must keep prompt exits)."""

        def _handler(signum, _frame):
            # flag only -- the run loop publishes the notice (file IO
            # in a signal handler could reenter mid-operation)
            self._preempt_signaled = True

        try:
            signal.signal(signal.SIGUSR1, _handler)
            if os.environ.get(PREEMPT_SIGTERM_ENV) == "1":
                signal.signal(signal.SIGTERM, _handler)
        except ValueError:
            # not the main thread (in-process test harness): the
            # command/fault paths still deliver notices
            pass

    def _step_preemption(self) -> bool:
        """Advance the preemption state machine once per loop
        iteration; True when the grace window has closed and the
        worker should exit."""
        if self._preempt_signaled and self._preempt_deadline is None:
            self.notice_preemption(reason="signal")
        if self._preempt_deadline is None:
            return False
        if not self._preempt_hook_ran:
            self._preempt_hook_ran = True
            try:
                self._preempt_hook(max(
                    0.0, self._preempt_deadline - time.monotonic()))
            except Exception:  # noqa: BLE001 - still exit PREEMPTED
                logger.error("Preempt hook of %s failed.",
                             self.worker_name, exc_info=True)
        return time.monotonic() >= self._preempt_deadline

    # -------------------------------------------------------------------
    def _handle_command(self, cmd: str, kwargs: Dict) -> Any:
        if cmd == "configure":
            self.config = kwargs.get("config")
            result = self._configure(self.config)
            self.server.set_status(WorkerServerStatus.READY)
            return result
        if cmd == "start":
            self._running = True
            self.server.set_status(WorkerServerStatus.RUNNING)
            return "ok"
        if cmd == "pause":
            self._running = False
            self.server.set_status(WorkerServerStatus.PAUSED)
            return "ok"
        if cmd == "exit":
            self._exiting = True
            return "ok"
        if cmd == "ping":
            return "pong"
        if cmd == "preempt":
            # controller-initiated preemption drill (tests / manual
            # degrade rehearsals): same path as a cluster signal
            self.notice_preemption(grace=(kwargs or {}).get("grace"),
                                   reason="command")
            return "ok"
        if cmd == "metrics":
            # the worker health surface's metrics export
            # (docs/observability.md): Prometheus text + raw snapshot
            return dict(
                prometheus=metrics.to_prometheus(),
                snapshot=metrics.snapshot(),
                flight_events=len(flight.default_recorder()))
        if cmd == "profiler":
            # tracing.start(path) / stop() on THIS process (the master
            # overrides this to broadcast to its model workers)
            return self._handle_profiler(**(kwargs or {}))
        raise ValueError(f"Unknown worker command {cmd}")

    def _handle_profiler(self, action: str = "start",
                         path: Optional[str] = None) -> Dict:
        """Start or stop a profile of this process through the one
        control, ``obs.tracing.start(path)`` / ``stop()``: spans on,
        ``jax.profiler`` recording into ``{run_log_path}/trace/jax``
        unless ``path`` overrides, every scoped span in the profile
        beside the device's operations, and ``programs.json`` (what
        the compiled programs say of themselves) written beside it at
        the stop. A start while a capture is running stops that one
        first (``tracing.start``)."""
        from realhf_tpu.base import monitor
        if action == "start":
            target = path or monitor.trace_dir("jax")
            tracing.start(target)
            flight.record("profiler_start", path=target)
            return dict(ok=True, path=target)
        if action == "stop":
            capture = tracing.stop()
            if capture is None:
                return dict(ok=False, error="no capture is running")
            flight.record("profiler_stop")
            return dict(ok=True, path=capture.profile_dir,
                        spans=len(capture.spans),
                        programs=len(capture.programs))
        raise ValueError(f"Unknown profiler action {action!r}")

    def run(self):
        logger.info("Worker %s starting poll loop.", self.worker_name)
        self._install_signal_handlers()
        try:
            while not self._exiting:
                cmd = self.server.poll_command(
                    timeout=0.05 if not self._running else 0.0)
                if cmd is not None:
                    try:
                        self.server.respond(self._handle_command(*cmd))
                    except Exception as e:  # noqa: BLE001
                        self.server.respond(e)
                        raise
                if self._step_preemption():
                    logger.warning(
                        "Worker %s: preemption grace window closed; "
                        "exiting PREEMPTED.", self.worker_name)
                    break
                if self._running:
                    self._poll()
                # periodic observability housekeeping: metrics JSONL
                # snapshot + span-buffer flush (both cheap no-ops when
                # no sink/trace file is configured)
                metrics.maybe_flush()
                tracing.flush()
            self._exit_hook()
            tracing.flush()
            # final snapshot: maybe_flush is interval-gated, so a
            # short-lived worker would exit with its last gauge
            # values never persisted
            metrics.flush_final()
            self.server.stop_heartbeat()
            self.server.set_status(
                WorkerServerStatus.PREEMPTED if self.preempted
                else WorkerServerStatus.COMPLETED)
            if self.telemetry is not None:
                self.telemetry.stop()
        except Exception as e:
            # terminal status (not the beacon) is the liveness signal
            # from here on; the watchdog treats ERROR/COMPLETED as
            # "accounted for", never LOST. The flight recorder dumps
            # FIRST: the ring of recent events is the postmortem.
            flight.dump(reason=f"worker ERROR exit: {e!r}")
            tracing.flush()
            metrics.flush_final()
            self.server.stop_heartbeat()
            self.server.set_status(WorkerServerStatus.ERROR)
            raise
