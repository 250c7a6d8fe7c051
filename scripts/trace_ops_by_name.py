#!/usr/bin/env python3
"""A builder's tool: one ``benchmark/run.py`` run that also keeps what
the benchmark's reducer drops, for reading a cell's device time by
operation and by what the PROGRAM says each operation is.

    chiprun --chips 1 -- python3 scripts/trace_ops_by_name.py \
        --workload <cell> --seed <n> --seconds 45 --trace 2
    python3 -m realhf_tpu.obs.parts chiprun_out/profile_<cell>

Same arguments and same last line as ``benchmark/run.py``, which it
calls. Beside that it writes under ``chiprun_out/``:

- ``ops_<cell>.json``: EVERY device operation of the traced window
  with its own seconds (``trace_reduce.reduce`` keeps ten), by the
  names the ledger's ``breakdown.device_ops`` uses;
- ``profile_<cell>/``: the profiled capture's ``.xplane.pb`` and the
  ``programs.json`` that ``tracing.stop()`` wrote beside it (every
  program's operations by part, pass and opcode and the compiler's
  count of its memory): what ``python3 -m realhf_tpu.obs.parts`` reads,
  here, without the chip;
- ``spans_<cell>.json``: the counters and the ``engine:*`` spans, with
  their attributes, of every capture the run made;
- ``steps_<cell>.json``: every optimizer step's statistics as
  ``Engine.train_batch`` returned them (loss, ``grad_norm``; warm-up
  and traced steps included), every digit: two commits at one seed
  are compared step by step with them.
"""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out")

#: what every ``Engine.train_batch`` call of the run returned, in order
OPT_STEPS = []


def workload_of(argv):
    return argv[argv.index("--workload") + 1]


def keep_profile(cell):
    """Copy the profiled capture's trace file and ``programs.json``
    (the run deletes its work directory when it ends)."""
    from realhf_tpu.obs import parts, tracing
    for capture in reversed(tracing.captures()):
        if capture.profile_dir is None:
            continue
        kept = os.path.join(OUT, f"profile_{cell}")
        shutil.rmtree(kept, ignore_errors=True)
        os.makedirs(kept)
        for path in (parts.newest_profile(capture.profile_dir),
                     parts.programs_path(capture.profile_dir)):
            if path and os.path.exists(path):
                shutil.copy(path, kept)
        return


def install(cell):
    """Patch the reducer and ``Engine.train_batch`` to keep what they
    see under ``chiprun_out/``."""
    from benchmark import trace_reduce
    from realhf_tpu.engine.engine import Engine

    os.makedirs(OUT, exist_ok=True)
    reduce = trace_reduce.reduce

    def reduce_and_keep(trace, categories, chips=None, **kw):
        full = reduce(trace, categories, chips=chips, top=10 ** 6)
        if full is not None:
            with open(os.path.join(OUT, f"ops_{cell}.json"), "w") as f:
                json.dump(dict(busy_s=full["busy_s"],
                               window_s=full["window_s"],
                               ops=full["breakdown"]["device_ops"]), f)
        keep_profile(cell)
        return reduce(trace, categories, chips=chips, **kw)

    train_batch = Engine.train_batch

    def train_and_keep(self, *args, **kw):
        out = train_batch(self, *args, **kw)
        OPT_STEPS.append(out)
        return out

    trace_reduce.reduce = reduce_and_keep
    Engine.train_batch = train_and_keep


def run(argv):
    from benchmark import run as bench_run
    cell = workload_of(argv)
    install(cell)
    sys.argv = ["benchmark/run.py"] + argv
    rc = bench_run.main()
    keep_spans(cell)
    return rc


def keep_spans(cell):
    """``spans_<cell>.json``: every capture's counters, gauges and its
    ``engine:*`` spans with their attributes (``program``,
    ``program_fingerprint``, ``flash_block_share``, ``moe_dispatch``,
    ``compiled``), which no reader takes."""
    from realhf_tpu.obs import tracing
    kept = [dict(profiled=c.profile_dir is not None, sync=c.sync,
                 counters=c.counters, gauges=c.gauges,
                 spans=[dict(name=s["name"], secs=s["end"] - s["start"],
                             **s["attributes"])
                        for s in c.spans if s["name"].startswith("engine:")])
            for c in tracing.captures()]
    with open(os.path.join(OUT, f"spans_{cell}.json"), "w") as f:
        json.dump(kept, f)
    with open(os.path.join(OUT, f"steps_{cell}.json"), "w") as f:
        json.dump(OPT_STEPS, f)


if __name__ == "__main__":
    sys.exit(run(sys.argv[1:]))
