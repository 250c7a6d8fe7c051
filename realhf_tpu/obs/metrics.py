"""Metrics registry: counters, gauges, summaries, histograms.

The numbers half of the observability layer (docs/observability.md).
One :class:`MetricsRegistry` per process absorbs what used to be
scattered -- ``base/stats.py`` scalar side-channels, watchdog
liveness, serving queue depth/rejections, scheduler decode/evict/
hot-swap counters, checkpoint save/verify durations, elastic
degrade/rejoin events -- behind four metric types:

- ``Counter``: monotone totals (``..._total``).
- ``Gauge``: last-write-wins levels (queue depth, live workers).
- ``Summary``: count/sum/min/max/mean accumulation per label set
  (exec durations; the :class:`Accum` it is built on also backs the
  fixed ``base/stats.py`` export).
- ``Histogram``: bucketed observations in Prometheus ``le`` form.

Exports: :meth:`MetricsRegistry.to_prometheus` renders the standard
text exposition format (served from the worker health surface via the
``metrics`` worker command); :meth:`snapshot` returns a plain dict;
an attached JSONL sink (:meth:`attach_jsonl`) periodically persists
snapshots and immediately persists one-off structured records emitted
through :meth:`event` -- the structured replacement for the master's
free-form stats tables.

Label-aware convenience module functions (``inc``, ``set_gauge``,
``observe``, ``event``) operate on the process-default registry so
instrumentation call sites stay one line. All operations are cheap
and in-memory; file IO happens only in ``event``/``maybe_flush`` and
always outside the registry lock.
"""

import dataclasses
import json
import math
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from realhf_tpu.base import logging

logger = logging.getLogger("obs.metrics")

METRICS_JSONL_ENV = "REALHF_TPU_METRICS_JSONL"
DEFAULT_SNAPSHOT_INTERVAL = 30.0


@dataclasses.dataclass
class Accum:
    """count/sum/min/max accumulator (mean derived). Also the engine
    behind the fixed ``base/stats.py`` export."""
    count: int = 0
    total: float = 0.0
    min: float = math.inf
    max: float = -math.inf

    def add(self, value: float):
        value = float(value)
        self.count += 1
        self.total += value
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def as_dict(self) -> Dict[str, float]:
        if not self.count:
            return dict(count=0, sum=0.0, min=0.0, max=0.0, mean=0.0)
        return dict(count=self.count, sum=self.total, min=self.min,
                    max=self.max, mean=self.mean)


LabelKey = Tuple[Tuple[str, str], ...]


def _label_key(labels: Dict[str, Any]) -> LabelKey:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _prom_labels(key: LabelKey, extra: Sequence[Tuple[str, str]] = ()
                 ) -> str:
    items = list(key) + list(extra)
    if not items:
        return ""
    body = ",".join(f'{k}="{v}"' for k, v in items)
    return "{" + body + "}"


class _Metric:
    kind = ""

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()

    def prometheus_lines(self) -> List[str]:
        raise NotImplementedError

    def snapshot_value(self):
        raise NotImplementedError

    def _header(self) -> List[str]:
        out = []
        if self.help:
            out.append(f"# HELP {self.name} {self.help}")
        out.append(f"# TYPE {self.name} {self.kind}")
        return out


class Counter(_Metric):
    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        super().__init__(name, help)
        self._values: Dict[LabelKey, float] = {}

    def inc(self, amount: float = 1.0, **labels):
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, **labels) -> float:
        with self._lock:
            return self._values.get(_label_key(labels), 0.0)

    def prometheus_lines(self) -> List[str]:
        with self._lock:
            values = dict(self._values)
        out = self._header()
        for key in sorted(values):
            out.append(f"{self.name}{_prom_labels(key)} "
                       f"{values[key]:g}")
        return out

    def snapshot_value(self):
        with self._lock:
            return {json.dumps(dict(k)) if k else "": v
                    for k, v in self._values.items()}


class Gauge(_Metric):
    kind = "gauge"

    def __init__(self, name: str, help: str = ""):
        super().__init__(name, help)
        self._values: Dict[LabelKey, float] = {}
        # how often each series was written: a value alone cannot say
        # whether it is new (``writes``)
        self._writes: Dict[LabelKey, int] = {}

    def set(self, value: float, **labels):
        key = _label_key(labels)
        with self._lock:
            self._values[key] = float(value)
            self._writes[key] = self._writes.get(key, 0) + 1

    def inc(self, amount: float = 1.0, **labels):
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount
            self._writes[key] = self._writes.get(key, 0) + 1

    def value(self, **labels) -> float:
        with self._lock:
            return self._values.get(_label_key(labels), 0.0)

    def writes(self) -> Dict[str, int]:
        """Writes so far of every series, keyed as ``snapshot_value``
        keys them: two readings tell which series were written in
        between, whatever their values."""
        with self._lock:
            return {json.dumps(dict(k)) if k else "": n
                    for k, n in self._writes.items()}

    def prometheus_lines(self) -> List[str]:
        with self._lock:
            values = dict(self._values)
        out = self._header()
        for key in sorted(values):
            out.append(f"{self.name}{_prom_labels(key)} "
                       f"{values[key]:g}")
        return out

    def snapshot_value(self):
        with self._lock:
            return {json.dumps(dict(k)) if k else "": v
                    for k, v in self._values.items()}


class Summary(_Metric):
    kind = "summary"

    def __init__(self, name: str, help: str = ""):
        super().__init__(name, help)
        self._values: Dict[LabelKey, Accum] = {}

    def observe(self, value: float, **labels):
        key = _label_key(labels)
        with self._lock:
            acc = self._values.get(key)
            if acc is None:
                acc = self._values[key] = Accum()
            acc.add(value)

    def accum(self, **labels) -> Accum:
        with self._lock:
            return dataclasses.replace(
                self._values.get(_label_key(labels), Accum()))

    def prometheus_lines(self) -> List[str]:
        with self._lock:
            values = {k: v.as_dict() for k, v in self._values.items()}
        out = self._header()
        for key in sorted(values):
            d = values[key]
            lbl = _prom_labels(key)
            out.append(f"{self.name}_count{lbl} {d['count']:g}")
            out.append(f"{self.name}_sum{lbl} {d['sum']:g}")
            out.append(f"{self.name}_min{lbl} {d['min']:g}")
            out.append(f"{self.name}_max{lbl} {d['max']:g}")
        return out

    def snapshot_value(self):
        with self._lock:
            return {json.dumps(dict(k)) if k else "": v.as_dict()
                    for k, v in self._values.items()}


#: default histogram buckets: wall-clock seconds from 1 ms to ~17 min
DEFAULT_BUCKETS = (0.001, 0.005, 0.025, 0.1, 0.5, 2.5, 10.0, 60.0,
                   300.0, 1000.0)


def quantile_from_buckets(buckets: Sequence[float],
                          counts: Sequence[int], q: float,
                          observed_max: Optional[float] = None
                          ) -> Optional[float]:
    """Prometheus-style ``histogram_quantile``: linear interpolation
    inside the bucket the q-th observation falls into. ``counts`` is
    per-bucket (NOT cumulative), with the trailing overflow bucket --
    ``len(counts) == len(buckets) + 1``. A quantile landing in the
    overflow bucket returns ``observed_max`` when known, else the last
    finite bound (exactly Prometheus' behavior). None when empty."""
    total = sum(counts)
    if total <= 0:
        return None
    q = min(1.0, max(0.0, q))
    target = q * total
    cum = 0.0
    for i, le in enumerate(buckets):
        prev_cum = cum
        cum += counts[i]
        if cum >= target:
            lo = buckets[i - 1] if i > 0 else 0.0
            est = le if counts[i] == 0 \
                else lo + (le - lo) * (target - prev_cum) / counts[i]
            # interpolation can overshoot the largest observation
            # (the within-bucket distribution is unknown); when the
            # true max is known, no quantile can exceed it
            return min(est, observed_max) \
                if observed_max is not None else est
    return observed_max if observed_max is not None \
        else (buckets[-1] if buckets else None)


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        super().__init__(name, help)
        self.buckets = tuple(sorted(buckets))
        self._counts: Dict[LabelKey, List[int]] = {}
        self._accum: Dict[LabelKey, Accum] = {}

    def observe(self, value: float, **labels):
        value = float(value)
        key = _label_key(labels)
        with self._lock:
            counts = self._counts.get(key)
            if counts is None:
                counts = self._counts[key] = [0] * (len(self.buckets) + 1)
                self._accum[key] = Accum()
            for i, le in enumerate(self.buckets):
                if value <= le:
                    counts[i] += 1
                    break
            else:
                counts[-1] += 1
            self._accum[key].add(value)

    def prometheus_lines(self) -> List[str]:
        with self._lock:
            counts = {k: list(v) for k, v in self._counts.items()}
            accum = {k: v.as_dict() for k, v in self._accum.items()}
        out = self._header()
        for key in sorted(counts):
            cum = 0
            for i, le in enumerate(self.buckets):
                cum += counts[key][i]
                out.append(
                    f"{self.name}_bucket"
                    f"{_prom_labels(key, [('le', f'{le:g}')])} {cum}")
            cum += counts[key][-1]
            out.append(f"{self.name}_bucket"
                       f"{_prom_labels(key, [('le', '+Inf')])} {cum}")
            out.append(f"{self.name}_count{_prom_labels(key)} {cum}")
            out.append(f"{self.name}_sum{_prom_labels(key)} "
                       f"{accum[key]['sum']:g}")
        return out

    def quantile(self, q: float, **labels) -> Optional[float]:
        """Estimated q-quantile for one label set (all observations
        when ``labels`` is empty and only one set exists -- otherwise
        the counts of every label set are merged)."""
        with self._lock:
            if labels:
                counts = self._counts.get(_label_key(labels))
                acc = self._accum.get(_label_key(labels))
                if counts is None:
                    return None
                counts = list(counts)
                observed_max = acc.max if acc and acc.count else None
            else:
                if not self._counts:
                    return None
                counts = [0] * (len(self.buckets) + 1)
                observed_max = None
                for k, v in self._counts.items():
                    for i, c in enumerate(v):
                        counts[i] += c
                    acc = self._accum[k]
                    if acc.count:
                        observed_max = acc.max \
                            if observed_max is None \
                            else max(observed_max, acc.max)
        return quantile_from_buckets(self.buckets, counts, q,
                                     observed_max=observed_max)

    def snapshot_value(self):
        with self._lock:
            return {json.dumps(dict(k)) if k else "": dict(
                        buckets=list(self.buckets), counts=list(v),
                        **self._accum[k].as_dict())
                    for k, v in self._counts.items()}


class MetricsRegistry:
    """Get-or-create metric store + exporters for one process."""

    def __init__(self, process_name: str = "proc"):
        self.process_name = process_name
        self._metrics: Dict[str, _Metric] = {}
        self._lock = threading.Lock()
        self._jsonl_path: Optional[str] = None
        self._jsonl_interval = DEFAULT_SNAPSHOT_INTERVAL
        self._last_snapshot = 0.0
        self._io_lock = threading.Lock()

    # -- metric construction --------------------------------------------
    def _get(self, name: str, cls, **kwargs) -> _Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, **kwargs)
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}, requested {cls.__name__}")
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(name, Counter, help=help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(name, Gauge, help=help)

    def summary(self, name: str, help: str = "") -> Summary:
        return self._get(name, Summary, help=help)

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = DEFAULT_BUCKETS
                  ) -> Histogram:
        return self._get(name, Histogram, help=help, buckets=buckets)

    # -- one-line instrumentation ---------------------------------------
    def inc(self, name: str, amount: float = 1.0, **labels):
        self.counter(name).inc(amount, **labels)

    def set_gauge(self, name: str, value: float, **labels):
        self.gauge(name).set(value, **labels)

    def observe(self, name: str, value: float, **labels):
        self.summary(name).observe(value, **labels)

    def observe_hist(self, name: str, value: float, **labels):
        """Bucketed observation (quantile-capable; ``observe`` is the
        count/sum/min/max summary)."""
        self.histogram(name).observe(value, **labels)

    # -- exports ---------------------------------------------------------
    def snapshot(self) -> Dict[str, Dict]:
        with self._lock:
            metrics = dict(self._metrics)
        return {name: dict(type=m.kind, values=m.snapshot_value())
                for name, m in sorted(metrics.items())}

    def gauge_writes(self) -> Dict[str, Dict[str, int]]:
        """``Gauge.writes`` of every gauge, by name."""
        with self._lock:
            metrics = dict(self._metrics)
        return {name: m.writes() for name, m in metrics.items()
                if isinstance(m, Gauge)}

    def to_prometheus(self) -> str:
        with self._lock:
            metrics = [m for _, m in sorted(self._metrics.items())]
        lines: List[str] = []
        for m in metrics:
            lines.extend(m.prometheus_lines())
        return "\n".join(lines) + ("\n" if lines else "")

    # -- JSONL sink ------------------------------------------------------
    def attach_jsonl(self, path: str,
                     interval: float = DEFAULT_SNAPSHOT_INTERVAL):
        """Periodic snapshot + immediate event persistence to ``path``
        (one JSON object per line). ``maybe_flush`` must be called
        from a poll loop for the periodic part."""
        self._jsonl_path = path
        self._jsonl_interval = interval
        self._last_snapshot = time.monotonic()

    def _write_line(self, record: Dict):
        path = self._jsonl_path
        if path is None:
            return
        line = json.dumps(record, default=str)
        with self._io_lock:
            try:
                os.makedirs(os.path.dirname(path), exist_ok=True)
                with open(path, "a") as f:
                    f.write(line + "\n")
            except OSError as e:  # metrics must never kill the run
                logger.warning("Metrics JSONL write to %s failed: %s",
                               path, e)

    def event(self, name: str, **fields) -> Dict:
        """Structured one-off record (the JSONL replacement for
        free-form log tables). Always returns the record; persists it
        when a JSONL sink is attached."""
        record = dict(ts=time.time(), kind="event", event=name,
                      process=self.process_name, **fields)
        self._write_line(record)
        return record

    def maybe_flush(self, now: Optional[float] = None):
        """Persist a snapshot when the interval elapsed (cheap no-op
        otherwise); call from worker poll loops."""
        if self._jsonl_path is None:
            return
        now = time.monotonic() if now is None else now
        if now - self._last_snapshot < self._jsonl_interval:
            return
        self._last_snapshot = now
        self._write_line(dict(ts=time.time(), kind="snapshot",
                              process=self.process_name,
                              metrics=self.snapshot()))

    def flush_final(self):
        """Unconditional final snapshot (marked ``final``) for clean
        exits: ``maybe_flush`` only fires on the interval, so a short
        run -- the inline runner, quickstart, a worker exiting between
        intervals -- would otherwise end with its last gauge values
        never persisted. Cheap no-op without a JSONL sink."""
        if self._jsonl_path is None:
            return
        self._last_snapshot = time.monotonic()
        self._write_line(dict(ts=time.time(), kind="snapshot",
                              final=True, process=self.process_name,
                              metrics=self.snapshot()))


# ----------------------------------------------------------------------
# Module-level default registry + convenience API.
# ----------------------------------------------------------------------
_default = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    return _default


def reset_default():
    """Fresh default registry (test isolation)."""
    global _default
    _default = MetricsRegistry()


def inc(name: str, amount: float = 1.0, **labels):
    _default.inc(name, amount, **labels)


def set_gauge(name: str, value: float, **labels):
    _default.set_gauge(name, value, **labels)


def observe(name: str, value: float, **labels):
    _default.observe(name, value, **labels)


def observe_hist(name: str, value: float, **labels):
    _default.observe_hist(name, value, **labels)


def event(name: str, **fields) -> Dict:
    return _default.event(name, **fields)


def snapshot() -> Dict[str, Dict]:
    return _default.snapshot()


def gauge_writes() -> Dict[str, Dict[str, int]]:
    return _default.gauge_writes()


def to_prometheus() -> str:
    return _default.to_prometheus()


def maybe_flush():
    _default.maybe_flush()


def flush_final():
    _default.flush_final()


_watching_compiles = False

_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
#: jax.monitoring's other duration events of a lowering -> stage of
#: ``engine_stage_secs_total`` (and ``<stage>_s`` on the span)
_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
#: jax.monitoring's events of the persistent cache -> result of
#: ``engine_cache_total`` (a miss is a program compiled AND written:
#: none with the cache off)
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


class _Traces(threading.local):
    """(start, seconds) of the calling thread's trace events that no
    later one has been found to hold, in order."""

    def __init__(self):
        self.seen: List[Tuple[float, float]] = []


_traces = _Traces()


def _outermost_trace_secs(secs: float) -> float:
    """jax fires the trace event of a jit traced inside another's
    trace too (hundreds in one train step's), the inner seconds lying
    inside the outer's, which ends later: of an event's seconds only
    those that no earlier event of this thread has counted."""
    start = time.monotonic() - secs
    seen, inside = _traces.seen, 0.0
    while seen and seen[-1][0] >= start:
        inside += seen.pop()[1]
    seen.append((start, secs))
    if len(seen) > 1 << 16:  # a process's eager ops, one after another
        del seen[:1 << 15]
    return max(0.0, secs - inside)


def watch_compiles():
    """Count, from now to the end of the process, every program the
    backend compiles or loads from the persistent cache
    (``engine_compiles_total``) and the seconds that took
    (``engine_compile_secs_total``), the seconds of the other stages
    of a lowering (``engine_stage_secs_total{stage}``: ``trace``,
    ``lower``, ``cache_load``; ``facts`` is the engine's) and the
    persistent cache's answers (``engine_cache_total{result}``). While
    spans are on, each event is also added to the innermost open span
    of the thread that compiles (``trace_s``, ``lower_s``,
    ``backend_s``, ``cache_load_s``, ``programs``, ``cache_hits``,
    ``cache_misses``; a load's seconds lie inside ``backend_s``). JAX
    cannot drop a listener, so there is one a process, registered by
    ``quickstart.main`` or the first engine."""
    global _watching_compiles
    if _watching_compiles:
        return
    from jax import monitoring

    from realhf_tpu.obs import tracing

    def on_secs(event, secs, **_):
        if event == _BACKEND_EVENT:
            inc("engine_compiles_total")
            inc("engine_compile_secs_total", secs)
            tracing.add_to_current_span(backend_s=secs, programs=1)
            return
        stage = _STAGE_EVENTS.get(event)
        if stage is None:
            return
        if stage == "trace":
            secs = _outermost_trace_secs(secs)
        inc("engine_stage_secs_total", secs, stage=stage)
        tracing.add_to_current_span(**{f"{stage}_s": secs})

    def on_event(event, **_):
        result = _CACHE_EVENTS.get(event)
        if result is not None:
            inc("engine_cache_total", result=result)
            # cache_hits, cache_misses: the event's own last name
            tracing.add_to_current_span(**{event.rsplit("/", 1)[-1]: 1})

    monitoring.register_event_duration_secs_listener(on_secs)
    monitoring.register_event_listener(on_event)
    _watching_compiles = True


def metrics_file_path(process_name: str,
                      experiment: Optional[str] = None,
                      trial: Optional[str] = None) -> str:
    from realhf_tpu.base import constants
    safe = process_name.replace("/", "-").replace(" ", "_")
    return os.path.join(constants.run_log_path(experiment, trial),
                        "obs", "metrics", f"{safe}.metrics.jsonl")
