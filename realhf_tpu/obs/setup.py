"""What a capture says of the program's set-up: from the call of
``quickstart.main`` to the end of its first step.

``tracing.start_setup()`` .. ``tracing.end_setup()`` record the stretch
(docs/observability.md, "The spans of set-up"); :func:`split` is the
ONE arithmetic over its spans, for the operator's gauge and INFO line
(:func:`report`) and for the benchmark's ``setup.*`` readers alike.
"""

from typing import Dict, Optional

from realhf_tpu.base import logging
from realhf_tpu.obs import analyze, metrics

logger = logging.getLogger("obs.setup")

#: phases of gauge ``setup_seconds`` <- keys of :func:`split`
PHASES = dict(program="program_s", imports="import_s", data="data_s",
              weights="weights_s", trace_lower="trace_lower_s",
              cache_load="cache_load_s", facts="facts_s",
              first_step="first_step_s", unattributed="unattributed_s")


def split(capture) -> Optional[Dict[str, float]]:
    """The seconds of a set-up capture by what they went to; None
    where ``capture`` holds no ``setup:*`` span.

    ``import_s`` (``setup:imports`` and ``setup:spec``), ``data_s``
    (``setup:data``), ``weights_s`` (the union of the ``setup:model``
    spans) and ``unattributed_s`` (the rest: what no ``setup:*`` span
    covers) make up the capture from its start to the start of its
    first ``step`` span, or to its end where no step began.
    ``first_step_s`` runs from that step's first ``mfc:*`` child to
    its end; the step's head before that child is a harness's (a
    reference comparison inside a patched ``run_step``; empty in a
    user's run) and is no part of ``program_s``, the capture's length,
    nor are the spans that start in it of any sum below.

    ``trace_lower_s``, ``cache_load_s``, ``programs``, ``cache_hits``
    and ``cache_misses`` sum what ``metrics.watch_compiles`` put on the
    spans; ``facts_s`` is the ``engine:facts`` spans' seconds, and what
    lowering the program a second time costs there is left out of
    ``trace_lower_s``: the two lie side by side in ``first_step_s``.
    ``roles`` counts the ``setup:model`` spans and ``weight_bytes``
    sums their ``bytes``."""
    setup = capture.named("setup:")
    if not setup:
        return None
    step = next(iter(capture.named("step")), None)
    if step is None:  # raised, or handed over, before its first step
        step_start = first_mfc = step_end = capture.end
    else:
        step_start, step_end = step["start"], step["end"]
        first_mfc = min((s["start"] for s in capture.children(step)
                         if s["name"].startswith("mfc:")),
                        default=step_start)
    before = (capture.start, step_start)
    mine = [s for s in capture.spans
            if not step_start <= s["start"] < first_mfc]
    models = [s for s in setup if s["name"] == "setup:model"]
    lowered = [s for s in mine if s["name"] != "engine:facts"]

    def seconds(*names):
        return analyze.covered_seconds(before, [
            (s["start"], s["end"]) for s in setup
            if not names or s["name"] in names])

    def total(attribute, spans=mine):
        return sum(s["attributes"].get(attribute, 0) for s in spans)

    return dict(
        program_s=capture.end - capture.start - (first_mfc - step_start),
        import_s=seconds("setup:imports", "setup:spec"),
        data_s=seconds("setup:data"),
        weights_s=seconds("setup:model"),
        unattributed_s=step_start - capture.start - seconds(),
        first_step_s=step_end - first_mfc,
        trace_lower_s=total("trace_s", lowered) + total("lower_s", lowered),
        cache_load_s=total("cache_load_s"),
        facts_s=sum(s["end"] - s["start"] for s in mine
                    if s["name"] == "engine:facts"),
        programs=total("programs"), cache_hits=total("cache_hits"),
        cache_misses=total("cache_misses"), roles=len(models),
        weight_bytes=total("bytes", models))


def report(capture):
    """``tracing.end_setup()``'s word to the operator: gauge
    ``setup_seconds{phase}`` and one INFO line, from :func:`split`.
    Never raises."""
    try:
        s = split(capture)
        if s is None:
            return
        for phase, key in PHASES.items():
            metrics.set_gauge("setup_seconds", s[key], phase=phase)
        logger.info(
            "Set-up %.1f s: imports %.1f, data %.1f, weights %.1f "
            "(%d roles, %.1f GB), first step %.1f (trace+lower %.1f, "
            "%d programs: %d loaded %d compiled, facts %.1f), other %.1f",
            s["program_s"], s["import_s"], s["data_s"], s["weights_s"],
            s["roles"], s["weight_bytes"] / 1e9, s["first_step_s"],
            s["trace_lower_s"], s["programs"], s["cache_hits"],
            s["programs"] - s["cache_hits"], s["facts_s"],
            s["unattributed_s"])
    except Exception as e:  # noqa: BLE001 - tracing must never kill
        # the run
        logger.warning("Reading the set-up capture failed: %s", e)
