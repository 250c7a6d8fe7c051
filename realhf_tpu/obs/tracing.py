"""Structured span tracer with cross-process context propagation.

The timeline half of the observability layer (docs/observability.md):
nestable spans with trace/span ids and free-form attributes, buffered
lock-free per thread (each thread appends to its own list; drains
snapshot a length first so a racing append is never lost), and
exported as Chrome trace-event JSON that Perfetto / ``chrome://tracing``
load directly -- one PPO step renders as a single timeline across the
master, every model worker, and the serving fleet.

Propagation: a span's :class:`SpanContext` serializes to a plain dict
(``inject``) that rides in ``request_reply_stream.Payload.trace`` and
in the serving submit envelope; the receiving process ``extract``\\ s it
and parents its spans there, so causality survives process hops.

The tracer is OFF by default (every call is a cheap no-op). The
control is :func:`start` / :func:`stop`, callable any number of times
in a running process: ``start`` turns spans on and, given a directory,
starts ``jax.profiler`` too (the one way to start a profile); ``stop``
returns the :class:`Capture` (spans, counter deltas, and what the
compiled programs under the spans say of themselves), which stays
readable as
:func:`last_capture` (and the few before it as :func:`captures`).
The ``REALHF_TPU_TRACE=1`` env switch honored by every worker
process, the inline runner, and quickstart
(:func:`configure_from_env`) is a caller of ``start``. When a file path
is configured, finished spans stream to it as JSON lines (one Chrome
event per line); :func:`merge_traces` folds every per-process file of
a run into one ``merged_trace.json``. Without a path they stay in
memory until ``stop``.

Set-up: ``quickstart.main`` is a caller of the control too. Its first
statement, :func:`start_setup`, starts a capture iff none is running;
:func:`end_setup` stops it when the runner's first step has ended (or
the program hands over to a launcher, or raised) and tells the operator
what it held (``obs/setup.py``). A capture that a caller or
``REALHF_TPU_TRACE=1`` has running takes the ``setup:*`` spans instead
and is left alone.

Clocks: a span takes ``time.monotonic()`` (steady, and the clock the
benchmark's harness reads); :data:`EPOCH_OFFSET`, taken once a
process, turns it into wall-clock time at Chrome export only, so the
files of several processes still line up. While ``start`` has a
profile recording, entering a scoped span also enters a
``jax.profiler.TraceAnnotation`` of the same name: the span then lies
in the ``.xplane.pb`` beside the device's operations, on their clock.

Synced: a span may be handed what its work produced
(:meth:`Span.result`). Under ``start(sync=True)`` leaving the span
first waits for that value (``jax.block_until_ready``), so the span
holds the device work it caused; otherwise it ends at the enqueue and
the program keeps its own overlap. ``sync`` may also be a tuple of name
prefixes: only those spans wait (``("compute:", "realloc")`` ends every
MFC and reshard blocked and leaves the overlap inside an MFC alone;
waiting after each engine program can expose host work that the
program hides behind the device). ``REALHF_TPU_TRACE=1`` is unsynced.
"""

import collections
import contextlib
import dataclasses
import json
import os
import sys
import threading
import time
import uuid
import zlib
from typing import (
    Any,
    Deque,
    Dict,
    Iterator,
    List,
    Optional,
    Tuple,
    Union,
)

from realhf_tpu.base import logging

logger = logging.getLogger("obs.tracing")

TRACE_ENV = "REALHF_TPU_TRACE"

#: file name of the per-run merged Chrome trace (Perfetto-loadable)
MERGED_TRACE_NAME = "merged_trace.json"

#: wall-clock seconds since the epoch at this process's
#: ``time.monotonic() == 0``: added to a span's times where they leave
#: the process (Chrome export, ``to_epoch``), nowhere else
EPOCH_OFFSET = time.time() - time.monotonic()

#: how many captures stay readable after their ``stop`` (a traced
#: stretch may be several: profiled steps, then synced ones)
KEPT_CAPTURES = 8

#: counters whose deltas a capture reports (docs/observability.md),
#: beside the one a layer operator's record names for its tokens
#: (``models/operators.py:token_counters``)
CAPTURE_COUNTERS = ("realloc_bytes_total", "realloc_puts_total",
                    "engine_compiles_total", "engine_compile_secs_total",
                    "moe_routed_pairs_total", "flash_kv_blocks_total",
                    "moe_held_pairs_total", "moe_share_overflow_total",
                    "sparse_pairs_total", "index_tokens_total",
                    "index_blocks_total", "engine_stage_secs_total",
                    "engine_cache_total", "loop_token_passes_total",
                    "flash_stream_rows_total")
#: gauges whose last values a capture reports, where they were
#: written while it ran
CAPTURE_GAUGES = ("moe_load_max_over_mean",
                  "moe_held_load_max_over_mean",
                  "engine_program_bytes", "loop_expected_exit_pass",
                  "loop_exit_entropy", "loop_exit_mass", "loop_pass_nll")

#: ``(finished spans, profiled) -> {fingerprint: facts}``: who knows
#: the compiled programs that ran under the spans (the engines:
#: ``engine/engine.py`` registers the one provider); see Capture.programs
_program_provider = None


def set_program_provider(provider):
    global _program_provider
    _program_provider = provider


def to_epoch(monotonic_secs: float) -> float:
    """A reading of this process's ``time.monotonic()`` as wall-clock
    seconds, comparable across processes."""
    return monotonic_secs + EPOCH_OFFSET


@dataclasses.dataclass(frozen=True)
class SpanContext:
    """The propagatable identity of a span."""
    trace_id: str
    span_id: str

    def to_dict(self) -> Dict[str, str]:
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    @classmethod
    def from_dict(cls, d: Optional[Dict]) -> Optional["SpanContext"]:
        if not d or "trace_id" not in d or "span_id" not in d:
            return None
        return cls(trace_id=str(d["trace_id"]),
                   span_id=str(d["span_id"]))


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


class Span:
    """One timed operation. Create through :meth:`Tracer.span` (context
    manager, becomes the thread's current span) or
    :meth:`Tracer.start_span` (explicit lifetime for long-lived work
    like a serving request); ``finish()`` records it."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "start",
                 "end", "thread", "attributes", "_tracer", "_finished",
                 "_result")

    def __init__(self, tracer: "Tracer", name: str,
                 parent: Optional[SpanContext], attributes: Dict):
        self._tracer = tracer
        self.name = name
        self.trace_id = parent.trace_id if parent else _new_id()
        self.span_id = _new_id()
        self.parent_id = parent.span_id if parent else None
        self.start = time.monotonic()
        self.end: Optional[float] = None
        self.thread = threading.get_ident()
        self.attributes = dict(attributes)
        self._finished = False
        self._result = None

    @property
    def context(self) -> SpanContext:
        return SpanContext(trace_id=self.trace_id, span_id=self.span_id)

    def set_attribute(self, key: str, value: Any):
        self.attributes[key] = value

    def result(self, value):
        """Hand the span what its work produced and get it back. A
        synced tracer waits for it before the span ends."""
        self._result = value
        return value

    def finish(self, end_time: Optional[float] = None):
        """``end_time`` is a reading of ``time.monotonic()``."""
        if self._finished:
            return
        self._finished = True
        if self._result is not None:
            if self._tracer.waits_for(self.name):
                import jax
                jax.block_until_ready(self._result)
            self._result = None
        self.end = end_time if end_time is not None else time.monotonic()
        self._tracer._record(self)

    def as_dict(self) -> Dict[str, Any]:
        return dict(name=self.name, start=self.start, end=self.end,
                    span_id=self.span_id, parent_id=self.parent_id,
                    trace_id=self.trace_id, thread=self.thread,
                    attributes=dict(self.attributes))


class _NoopSpan:
    """Returned while the tracer is disabled: every operation is free."""

    __slots__ = ()
    name = ""
    trace_id = span_id = parent_id = None
    attributes: Dict = {}
    context = None

    def set_attribute(self, key, value):
        pass

    def result(self, value):
        return value

    def finish(self, end_time=None):
        pass


NOOP_SPAN = _NoopSpan()


class _ThreadBuffer(threading.local):
    """Per-thread finished-span buffer. Appends are thread-local (no
    lock); the drain snapshots a length first, so an append racing the
    drain lands past the snapshot and survives for the next drain."""

    def __init__(self, register):
        self.spans: List[Span] = []
        self.stack: List[Span] = []
        #: parent of this thread's top-level spans (Tracer.attach)
        self.inherited: Optional[SpanContext] = None
        register(self.spans)


@dataclasses.dataclass
class Capture:
    """What one ``start`` .. ``stop`` recorded. ``spans`` are dicts
    (``name``, ``start``, ``end`` on this process's
    ``time.monotonic()``, ``span_id``, ``parent_id``, ``trace_id``,
    ``thread``, ``attributes``) in order of their start; ``counters``
    maps ``name{label=value,...}`` of every :data:`CAPTURE_COUNTERS`
    series to its growth in between, ``gauges`` every
    :data:`CAPTURE_GAUGES` series WRITTEN in between to its value at
    ``stop`` (not what an earlier model of the process left).
    ``programs`` maps the ``program_fingerprint`` of every
    ``engine:*`` span to what that compiled program says of itself
    (``obs/parts.py:ProgramFacts`` as a plain dict: ``module``,
    ``ops``: instruction -> part, pass, opcode, phase; ``memory``);
    with a ``profile_dir`` it is also ``programs.json`` beside the
    profile. With a file path configured the spans already flushed to
    the file are not here as well."""
    spans: List[Dict[str, Any]]
    counters: Dict[str, float]
    start: float
    end: float
    sync: Union[bool, Tuple[str, ...]] = False
    profile_dir: Optional[str] = None
    gauges: Dict[str, float] = dataclasses.field(default_factory=dict)
    programs: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict)

    def named(self, prefix: str) -> List[Dict[str, Any]]:
        """Spans called ``prefix`` or ``prefix<something>`` where the
        prefix ends in a colon (``mfc:``)."""
        if prefix.endswith(":"):
            return [s for s in self.spans
                    if s["name"].startswith(prefix)]
        return [s for s in self.spans if s["name"] == prefix]

    def children(self, span: Dict[str, Any]) -> List[Dict[str, Any]]:
        return [s for s in self.spans
                if s["parent_id"] == span["span_id"]]

    def descendants(self, span: Dict[str, Any]) -> List[Dict[str, Any]]:
        out, level = [], self.children(span)
        while level:
            out.extend(level)
            level = [c for s in level for c in self.children(s)]
        return out

    def self_seconds(self, span: Dict[str, Any], cover=None) -> float:
        """The span's duration less the part of it that its children
        cover (their union: children in several threads overlap).
        With ``cover``, a predicate on a span, less the part that the
        DESCENDANTS it holds for cover: an ``mfc:*`` span's time
        outside every ``engine:*`` span beneath it."""
        from realhf_tpu.obs import analyze
        below = self.children(span) if cover is None else [
            s for s in self.descendants(span) if cover(s)]
        return span["end"] - span["start"] - analyze.covered_seconds(
            (span["start"], span["end"]),
            [(s["start"], s["end"]) for s in below])

    def counter(self, name: str, **labels) -> float:
        return self.counters.get(_series(name, labels), 0.0)


def _series(name: str, labels) -> str:
    inner = ",".join(f"{k}={v}" for k, v in sorted(dict(labels).items()))
    return f"{name}{{{inner}}}" if inner else name


def _metric_values(names=None) -> Dict[str, float]:
    from realhf_tpu.obs import metrics
    if names is None:
        # (a process that feeds an operator's counter holds a model,
        # so it has imported the table; no other need import JAX)
        table = sys.modules.get("realhf_tpu.models.operators")
        names = CAPTURE_COUNTERS + (
            table.token_counters() if table else ())
    out = {}
    for name, m in metrics.snapshot().items():
        if name in names:
            for labels, value in m["values"].items():
                out[_series(name, json.loads(labels) if labels
                            else {})] = value
    return out


def _gauge_writes() -> Dict[str, int]:
    """Series of ``CAPTURE_GAUGES`` -> how often written so far."""
    from realhf_tpu.obs import metrics
    return {_series(name, json.loads(labels) if labels else {}): n
            for name, series in metrics.gauge_writes().items()
            if name in CAPTURE_GAUGES for labels, n in series.items()}


class Tracer:
    """Span factory + buffer + exporter for one logical process."""

    def __init__(self, process_name: str = "proc",
                 enabled: bool = False, path: Optional[str] = None):
        self.process_name = process_name
        self.enabled = enabled
        self.path = path
        #: leaving a span first waits for its result: True for every
        #: span, or the name prefixes of those that wait (start(sync=))
        self.sync: Union[bool, Tuple[str, ...]] = False
        #: a profile that start() began is recording: scoped spans
        #: also enter this class (jax.profiler.TraceAnnotation)
        self._annotation = None
        self._profile_dir: Optional[str] = None
        self._started: Optional[float] = None
        #: ``_started`` of the capture that start_setup() began
        self._setup_started: Optional[float] = None
        self._counters_at_start: Dict[str, float] = {}
        self._gauge_writes_at_start: Dict[str, int] = {}
        #: what the last few start .. stop pairs recorded, newest last
        self._captures: Deque[Capture] = collections.deque(
            maxlen=KEPT_CAPTURES)
        self._buffers: List[List[Span]] = []
        self._buffers_lock = threading.Lock()
        self._file_lock = threading.Lock()
        self._wrote_meta = False
        self._tl = _ThreadBuffer(self._register_buffer)

    # -- configuration --------------------------------------------------
    def configure(self, process_name: Optional[str] = None,
                  enabled: Optional[bool] = None,
                  path: Optional[str] = None):
        if process_name is not None:
            self.process_name = process_name
            self._wrote_meta = False
        if enabled is not None:
            self.enabled = enabled
        if path is not None:
            self.path = path

    def _register_buffer(self, buf: List[Span]):
        with self._buffers_lock:
            self._buffers.append(buf)

    # -- the control ----------------------------------------------------
    def waits_for(self, name: str) -> bool:
        return self.sync is True or bool(
            self.sync and name.startswith(self.sync))

    def start(self, profile_dir: Optional[str] = None,
              sync: Union[bool, Tuple[str, ...]] = False):
        """Turn spans on, in a process that may have run for hours.
        With ``profile_dir``, also start ``jax.profiler`` into it
        (python tracer off: the spans carry the names) and write every
        scoped span into that profile as a ``TraceAnnotation``. With
        ``sync``, spans wait for their results before they end (True:
        all of them; a tuple of name prefixes: those). A ``start``
        while started stops the earlier capture first."""
        if self._started is not None:
            self.stop()
        self.drain()  # what an earlier configure(enabled=True) left
        self._counters_at_start = _metric_values()
        self._gauge_writes_at_start = _gauge_writes()
        self.sync = sync if isinstance(sync, bool) else tuple(sync)
        if profile_dir is not None:
            import jax
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(profile_dir,
                                     profiler_options=options)
            self._annotation = jax.profiler.TraceAnnotation
            self._profile_dir = profile_dir
        self._started = time.monotonic()
        self.enabled = True

    def stop(self) -> Optional[Capture]:
        """Turn spans (and the profile) off and return what was
        recorded since ``start``; None if not started. Spans still
        open are not in it."""
        if self._started is None:
            return None
        self.enabled = False
        profile_dir, self._profile_dir = self._profile_dir, None
        if self._annotation is not None:
            self._annotation = None
            import jax
            jax.profiler.stop_trace()
        spans = self.drain()
        end = time.monotonic()  # the capture's clock stops here
        now = _metric_values()
        deltas = {k: v - self._counters_at_start.get(k, 0.0)
                  for k, v in now.items()}
        # a gauge another model of this process left behind is not
        # this capture's: only what was written meanwhile (so not what
        # reading a program's facts, below, writes after the end)
        gauges = {k: v for k, v in _metric_values(CAPTURE_GAUGES).items()
                  if _gauge_writes().get(k)
                  != self._gauge_writes_at_start.get(k)}
        programs = self._programs(spans, profile_dir)
        self._write(spans)
        capture = Capture(
            spans=sorted((s.as_dict() for s in spans),
                         key=lambda s: s["start"]),
            counters={k: v for k, v in deltas.items() if v},
            start=self._started, end=end, sync=self.sync,
            profile_dir=profile_dir, programs=programs, gauges=gauges)
        self._started, self.sync = None, False
        self._captures.append(capture)
        return capture

    def start_setup(self):
        """The program records its own set-up (``quickstart.main``'s
        first statement): an unsynced capture with no profile, begun
        iff none is running, so a caller's ``start`` before it keeps
        the whole run in the caller's capture."""
        if self._started is None:
            self.start()
            self._setup_started = self._started

    def _owns_setup(self) -> bool:
        return self._setup_started is not None \
            and self._setup_started == self._started

    def release_setup(self) -> bool:
        """The set-up capture is no longer ``start_setup``'s to stop;
        True where it is the one still running (under
        ``REALHF_TPU_TRACE=1`` it then becomes the run's)."""
        mine, self._setup_started = self._owns_setup(), None
        return mine

    def setup_spans(self, on: bool):
        """Spans of the program's OWN set-up capture off or on again,
        the capture running on: the runner turns them off between the
        call of ``run_step`` and that method's body, so that what a
        caller has wrapped around it (a harness's reference comparison
        before the first step) is no part of the program's record of
        itself. Nothing where the running capture is a caller's."""
        if self._owns_setup():
            self.enabled = on

    def end_setup(self) -> Optional[Capture]:
        """Stop the capture ``start_setup`` began, iff it is the one
        still running (the first step has ended, or the program hands
        over or raised before it); None otherwise."""
        return self.stop() if self.release_setup() else None

    @staticmethod
    def _programs(spans: List[Span], profile_dir: Optional[str]
                  ) -> Dict[str, Dict[str, Any]]:
        """What the compiled programs under ``spans`` say of
        themselves (the registered provider; it may read a program's
        text now, where there was a profile, and set
        ``program_fingerprint`` on its spans), written as
        ``programs.json`` beside the profile. Never raises."""
        if _program_provider is None:
            return {}
        try:
            programs = _program_provider(spans, profile_dir is not None)
            if profile_dir is not None and programs:
                from realhf_tpu.obs import parts
                with open(parts.programs_path(profile_dir), "w") as f:
                    json.dump(programs, f)
            return programs
        except Exception as e:  # noqa: BLE001 - tracing must never
            # kill the run
            logger.warning("Reading the capture's programs failed: %s", e)
            return {}

    def captures(self) -> List[Capture]:
        """The last :data:`KEPT_CAPTURES` captures, newest last."""
        return list(self._captures)

    def last_capture(self) -> Optional[Capture]:
        return self._captures[-1] if self._captures else None

    @property
    def pid(self) -> int:
        """Stable integer process id for Chrome events: derived from
        the process NAME, so a merged multi-process trace keeps one
        lane per worker and an in-process test harness can emulate
        several 'processes' with several tracers."""
        return zlib.crc32(self.process_name.encode()) & 0x7FFFFFFF

    # -- span creation --------------------------------------------------
    def current_span(self) -> Optional[Span]:
        stack = self._tl.stack
        return stack[-1] if stack else None

    def current_context(self) -> Optional[SpanContext]:
        cur = self.current_span()
        return cur.context if cur is not None else self._tl.inherited

    @contextlib.contextmanager
    def attach(self, parent: Optional[SpanContext]):
        """Carry on a caller's work in another thread: top-level spans
        this thread opens inside become children of ``parent`` (what
        ``current_context()`` gave in the caller's thread)."""
        previous = self._tl.inherited
        self._tl.inherited = parent
        try:
            yield
        finally:
            self._tl.inherited = previous

    def inject(self) -> Optional[Dict[str, str]]:
        """Current span context as a payload-ready dict (None when no
        span is open or tracing is off)."""
        ctx = self.current_context() if self.enabled else None
        return ctx.to_dict() if ctx is not None else None

    @staticmethod
    def extract(carrier: Optional[Dict]) -> Optional[SpanContext]:
        return SpanContext.from_dict(carrier)

    def start_span(self, name: str,
                   parent: Optional[SpanContext] = None,
                   **attributes) -> Span:
        """Explicit-lifetime span (NOT pushed on the thread's current
        stack): caller owns ``finish()``. ``parent=None`` parents to
        the thread's current span."""
        if not self.enabled:
            return NOOP_SPAN
        if parent is None:
            parent = self.current_context()
        return Span(self, name, parent, attributes)

    @contextlib.contextmanager
    def span(self, name: str, parent: Optional[SpanContext] = None,
             **attributes) -> Iterator[Span]:
        """Scoped span: becomes the thread's current span, so nested
        ``span()`` calls and ``inject()`` see it; finishes on exit
        (exceptions are recorded as an ``error`` attribute)."""
        if not self.enabled:
            yield NOOP_SPAN
            return
        sp = self.start_span(name, parent=parent, **attributes)
        self._tl.stack.append(sp)
        annotation = self._annotation
        if annotation is not None:
            annotation = annotation(name)
            annotation.__enter__()
        try:
            yield sp
        except BaseException as e:
            sp.set_attribute("error", repr(e))
            sp._result = None  # nothing to wait for
            raise
        finally:
            stack = self._tl.stack
            if stack and stack[-1] is sp:
                stack.pop()
            try:
                sp.finish()
            finally:
                if annotation is not None:
                    annotation.__exit__(None, None, None)

    # -- recording / export ---------------------------------------------
    def _record(self, span: Span):
        self._tl.spans.append(span)

    def drain(self) -> List[Span]:
        """Remove and return every finished span across all threads."""
        out: List[Span] = []
        with self._buffers_lock:
            buffers = list(self._buffers)
        for buf in buffers:
            n = len(buf)  # snapshot BEFORE slicing: racing appends
            out.extend(buf[:n])  # land at >= n and survive
            del buf[:n]
        return out

    def _event(self, span: Span) -> Dict:
        args = {k: v for k, v in span.attributes.items()}
        args["trace_id"] = span.trace_id
        args["span_id"] = span.span_id
        if span.parent_id:
            args["parent_id"] = span.parent_id
        return {
            "name": span.name, "ph": "X", "cat": "span",
            "ts": to_epoch(span.start) * 1e6,
            "dur": max(0.0, (span.end or span.start) - span.start) * 1e6,
            "pid": self.pid, "tid": span.thread & 0x7FFFFFFF,
            "args": args,
        }

    def _meta_events(self) -> List[Dict]:
        return [{"name": "process_name", "ph": "M", "pid": self.pid,
                 "args": {"name": self.process_name}}]

    def to_events(self, spans: List[Span],
                  with_meta: bool = True) -> List[Dict]:
        events = self._meta_events() if with_meta else []
        events.extend(self._event(s) for s in spans)
        return events

    def flush(self):
        """When a file path is configured, drain buffered spans and
        append them to it as JSON lines; without one they stay in
        memory for ``stop``. Serialization happens outside any
        span-recording path, so instrumented code never blocks on
        file IO."""
        if self.path:
            self._write(self.drain())

    def _write(self, spans: List[Span]):
        if not spans or not self.path:
            return
        lines = [json.dumps(e, default=str)
                 for e in self.to_events(spans,
                                         with_meta=not self._wrote_meta)]
        payload = "\n".join(lines) + "\n"
        with self._file_lock:
            self._wrote_meta = True
            try:
                os.makedirs(os.path.dirname(self.path), exist_ok=True)
                with open(self.path, "a") as f:
                    f.write(payload)
            except OSError as e:  # tracing must never kill the run
                logger.warning("Trace flush to %s failed: %s",
                               self.path, e)


# ----------------------------------------------------------------------
# Module-level default tracer (one per process) + convenience API.
# ----------------------------------------------------------------------
_default = Tracer()


def default_tracer() -> Tracer:
    return _default


def configure(process_name: Optional[str] = None,
              enabled: Optional[bool] = None,
              path: Optional[str] = None):
    _default.configure(process_name=process_name, enabled=enabled,
                       path=path)


def reset_default():
    """Fresh default tracer (test isolation)."""
    global _default
    _default.stop()  # a profile left recording would outlive it
    _default = Tracer()


def start(profile_dir: Optional[str] = None,
          sync: Union[bool, Tuple[str, ...]] = False):
    _default.start(profile_dir=profile_dir, sync=sync)


def stop() -> Optional[Capture]:
    return _default.stop()


def start_setup():
    _default.start_setup()


def end_setup() -> Optional[Capture]:
    """Ends the program's own set-up capture and, where there was one,
    says what it held (``obs/setup.py``: gauge ``setup_seconds`` and
    one INFO line)."""
    capture = _default.end_setup()
    if capture is not None:
        from realhf_tpu.obs import setup
        setup.report(capture)
    return capture


def release_setup() -> bool:
    return _default.release_setup()


def setup_spans(on: bool):
    _default.setup_spans(on)


def last_capture() -> Optional[Capture]:
    return _default.last_capture()


def captures() -> List[Capture]:
    return _default.captures()


def enabled() -> bool:
    return _default.enabled


def span(name: str, parent: Optional[SpanContext] = None, **attributes):
    return _default.span(name, parent=parent, **attributes)


def start_span(name: str, parent: Optional[SpanContext] = None,
               **attributes) -> Span:
    return _default.start_span(name, parent=parent, **attributes)


def current_context() -> Optional[SpanContext]:
    return _default.current_context()


def current_span():
    """The calling thread's innermost open span; the no-op span where
    there is none or spans are off (a span left open over a stretch
    with spans off is not that stretch's caller), so a callee can
    annotate its caller's span."""
    return (_default.current_span() if _default.enabled else None) \
        or NOOP_SPAN


def add_to_current_span(**amounts: float):
    """Add numbers to attributes of the calling thread's innermost
    open span (``metrics.watch_compiles``: the stages of a lowering go
    to the span that caused it); nothing where none is open or spans
    are off."""
    sp = current_span()
    if sp is not NOOP_SPAN:
        for key, amount in amounts.items():
            sp.attributes[key] = sp.attributes.get(key, 0) + amount


def attach(parent: Optional[SpanContext]):
    return _default.attach(parent)


def inject() -> Optional[Dict[str, str]]:
    return _default.inject()


def extract(carrier: Optional[Dict]) -> Optional[SpanContext]:
    return Tracer.extract(carrier)


def flush():
    _default.flush()


def trace_env_enabled(env=None) -> bool:
    env = os.environ if env is None else env
    return env.get(TRACE_ENV, "") not in ("", "0")


def trace_dir(experiment: Optional[str] = None,
              trial: Optional[str] = None) -> str:
    from realhf_tpu.base import constants
    return os.path.join(constants.run_log_path(experiment, trial),
                        "obs", "trace")


def trace_file_path(process_name: str,
                    experiment: Optional[str] = None,
                    trial: Optional[str] = None) -> str:
    safe = process_name.replace("/", "-").replace(" ", "_")
    return os.path.join(trace_dir(experiment, trial),
                        f"{safe}.trace.jsonl")


def merge_traces(directory: Optional[str] = None,
                 out_path: Optional[str] = None,
                 experiment: Optional[str] = None,
                 trial: Optional[str] = None) -> Optional[str]:
    """Fold every per-process ``*.trace.jsonl`` under ``directory``
    (default: this run's trace dir) into one Chrome trace-event JSON
    (``merged_trace.json``). Returns the merged path, or None when
    there was nothing to merge. Unparseable lines are skipped -- a
    worker killed mid-write must not void everyone else's timeline."""
    directory = directory or trace_dir(experiment, trial)
    if not os.path.isdir(directory):
        return None
    events: List[Dict] = []
    for fn in sorted(os.listdir(directory)):
        if not fn.endswith(".trace.jsonl"):
            continue
        try:
            with open(os.path.join(directory, fn)) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        events.append(json.loads(line))
                    except ValueError:
                        continue
        except OSError:
            continue
    if not events:
        return None
    out_path = out_path or os.path.join(directory, MERGED_TRACE_NAME)
    merged = {"traceEvents": events, "displayTimeUnit": "ms"}
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(merged, f)
    os.replace(tmp, out_path)
    logger.info("Merged %d trace events from %s into %s.",
                len(events), directory, out_path)
    return out_path
