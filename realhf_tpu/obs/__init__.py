"""Unified observability layer: tracing, metrics, flight recorder.

Three cooperating pieces (docs/observability.md):

- :mod:`realhf_tpu.obs.tracing` -- structured spans with trace/span
  ids, propagated across processes through ``request_reply_stream``
  payloads and the serving ZMQ envelope, exported as Chrome
  trace-event JSON (Perfetto-loadable).
- :mod:`realhf_tpu.obs.metrics` -- a counter/gauge/summary/histogram
  registry snapshotted periodically to JSONL and served as Prometheus
  text from the worker health surface (the ``metrics`` worker
  command).
- :mod:`realhf_tpu.obs.flight` -- a bounded ring of recent events per
  worker, dumped to disk on crashes, preemptions, and worker-lost
  paths for postmortems.
- :mod:`realhf_tpu.obs.http` -- live HTTP telemetry endpoints
  (/metrics, /healthz, /flight, /statusz) every worker and the inline
  runner serve on an ephemeral port published under
  ``names.telemetry`` (the Prometheus scrape surface).
- :mod:`realhf_tpu.obs.analyze` -- trace analytics: per-step
  wall-time attribution, critical-path/bottleneck-MFC, straggler
  skew, and goodput computed from the merged Chrome trace
  (``scripts/analyze_trace.py`` is the CLI).

:func:`configure_from_env` is the one call every process entry point
makes (``worker_base.Worker``, the inline runner, quickstart): it
labels the default tracer/registry/recorder with the process name and
turns file export on when ``REALHF_TPU_TRACE=1``. In a live process
the control is ``tracing.start()`` / ``tracing.stop()``.
"""

from typing import Optional

from realhf_tpu.obs import flight, metrics, tracing  # noqa: F401


def configure_from_env(process_name: str,
                       experiment: Optional[str] = None,
                       trial: Optional[str] = None):
    """Label the process-default tracer, metrics registry, and flight
    recorder, and enable trace/metrics file export per the env:

    - ``REALHF_TPU_TRACE=1``: span tracing ON, streamed to
      ``{run_log_path}/obs/trace/{process}.trace.jsonl`` (merged into
      one Chrome trace at trial teardown) and metrics snapshots to
      ``{run_log_path}/obs/metrics/{process}.metrics.jsonl``.
    - ``REALHF_TPU_METRICS_JSONL=<path-or-1>``: metrics JSONL sink
      alone (``1`` uses the default per-run path).

    Needs ``experiment``/``trial`` (or previously set run constants)
    to resolve file paths; with neither, export is skipped and only
    the labels apply. Never raises: observability setup must not take
    a worker down."""
    tracing.configure(process_name=process_name)
    metrics.default_registry().process_name = process_name
    flight.configure(process_name)
    import os

    trace_on = tracing.trace_env_enabled()
    metrics_env = os.environ.get(metrics.METRICS_JSONL_ENV, "")
    if not trace_on and not metrics_env:
        return
    try:
        if trace_on:
            tracing.configure(
                path=tracing.trace_file_path(process_name, experiment,
                                             trial))
            # unsynced: the run keeps its overlap. The set-up capture
            # that quickstart began, where there is one, becomes the
            # run's: its spans so far go to the file with the rest
            if not tracing.release_setup():
                tracing.start()
        if metrics_env not in ("", "0") and metrics_env != "1":
            metrics.default_registry().attach_jsonl(metrics_env)
        elif trace_on or metrics_env == "1":
            metrics.default_registry().attach_jsonl(
                metrics.metrics_file_path(process_name, experiment,
                                          trial))
    except Exception as e:  # noqa: BLE001 - observability must never
        # prevent a worker from starting
        tracing.logger.warning(
            "Observability file export disabled for %s: %s",
            process_name, e)
