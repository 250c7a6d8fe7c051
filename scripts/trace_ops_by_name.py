#!/usr/bin/env python3
"""A builder's tool: one ``benchmark/run.py`` run that also keeps what
the benchmark's reducer drops, for reading a cell's device time by
operation and by what each operation is.

    chiprun --chips 1 -- python3 scripts/trace_ops_by_name.py \
        --workload <cell> --seed <n> --seconds 45 --trace 2

Same arguments and same last line as ``benchmark/run.py``, which it
calls. Beside that it writes under ``chiprun_out/``:

- ``ops_<cell>.json``: EVERY device operation of the traced window
  with its own seconds (``trace_reduce.reduce`` keeps ten), by the
  names the ledger's ``breakdown.device_ops`` uses;
- ``hlo_<cell>_<program>.txt``: the optimized HLO of each program the
  kind names (what ``Engine.compiled_text`` returns): an operation's
  line there carries ``op_name`` metadata, which says what a
  ``fusion.597`` is; and ``memory_<cell>_<program>.json``: the
  compiler's own count of the program's arguments, outputs and
  temporaries;
- ``spans_<cell>.json``: the counters and the ``engine:*`` spans, with
  their attributes, of every capture the run made;
- ``steps_<cell>.json``: every optimizer step's statistics as
  ``Engine.train_batch`` returned them (loss, ``grad_norm``; warm-up
  and traced steps included), every digit: two commits at one seed
  are compared step by step with them.

``--summarize <cell>`` needs no chip: it reads those files back and
prints the window's seconds by kind of operation.
"""

import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out")

#: kind of operation <- a pattern in the HLO line's ``op_name`` (the
#: jaxpr path that made it) or in its name; first match wins
KINDS = [
    ("ragged products", r"ragged[-_]dot"),
    ("flash attention kernels", r"flash_(fwd|bwd)"),
    ("sort", r"\bsort\b|argsort"),
    ("scatter-add", r"scatter"),
    ("gather", r"gather|dynamic_slice|take"),
    ("optimizer", r"/optimizer/"),
    ("vocabulary head and loss",
     r"vocab_head|slh,hv->slv|log_softmax|logsumexp"),
    ("gradient accumulation", r"closed_call/add$|closed_call/add "),
    ("copies", r" copy$|/squeeze|/remat2"),
]


#: what every ``Engine.train_batch`` call of the run returned, in order
OPT_STEPS = []


def workload_of(argv):
    return argv[argv.index("--workload") + 1]


def install(cell):
    """Patch the reducer and ``Engine.compiled_text`` to keep what
    they see under ``chiprun_out/``."""
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
        return reduce(trace, categories, chips=chips, **kw)

    compiled_text = Engine.compiled_text

    def text_and_keep(self, name):
        fn, args, static = self._last_call[name]
        compiled = fn.lower(*args, **static).compile()
        m = compiled.memory_analysis()
        with open(os.path.join(OUT, f"memory_{cell}_{name}.json"),
                  "w") as f:
            json.dump({k: getattr(m, k) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "alias_size_in_bytes", "temp_size_in_bytes",
                "generated_code_size_in_bytes")}, f)
        text = compiled_text(self, name)
        with open(os.path.join(OUT, f"hlo_{cell}_{name}.txt"), "w") as f:
            f.write(text)
        return text

    train_batch = Engine.train_batch

    def train_and_keep(self, *args, **kw):
        out = train_batch(self, *args, **kw)
        OPT_STEPS.append(out)
        return out

    trace_reduce.reduce = reduce_and_keep
    Engine.compiled_text = text_and_keep
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
    """``spans_<cell>.json``: every capture's counters and its
    ``engine:*`` spans with their attributes (``flash_block_share``,
    ``moe_dispatch``, ``compiled``), which no reader takes."""
    from realhf_tpu.obs import tracing
    kept = [dict(profiled=c.profile_dir is not None, sync=c.sync,
                 counters=c.counters,
                 spans=[dict(name=s["name"], secs=s["end"] - s["start"],
                             **s["attributes"])
                        for s in c.spans if s["name"].startswith("engine:")])
            for c in tracing.captures()]
    with open(os.path.join(OUT, f"spans_{cell}.json"), "w") as f:
        json.dump(kept, f)
    with open(os.path.join(OUT, f"steps_{cell}.json"), "w") as f:
        json.dump(OPT_STEPS, f)


def summarize(cell, program="train"):
    with open(os.path.join(OUT, f"ops_{cell}.json")) as f:
        kept = json.load(f)
    with open(os.path.join(OUT, f"hlo_{cell}_{program}.txt")) as f:
        hlo = f.read()
    # instruction name -> its line (fusions: the calling line)
    line_of = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        if m:
            line_of.setdefault(m.group(1), line)
    by_kind = collections.defaultdict(float)
    rows = []
    for name, secs in kept["ops"]:
        op = name.split("/", 1)[-1].split(" ")[0]
        line = line_of.get(op, "")
        meta = re.search(r'op_name="([^"]*)"', line)
        what = (meta.group(1) if meta else "") + " " + name
        kind = next((k for k, pat in KINDS if re.search(pat, what)),
                    "other")
        by_kind[kind] += secs
        rows.append((secs, name, kind, meta.group(1) if meta else ""))
    total = sum(by_kind.values())
    print(json.dumps(dict(cell=cell, busy_s=kept["busy_s"],
                          window_s=kept["window_s"], own_s=total)))
    for kind, secs in sorted(by_kind.items(), key=lambda x: -x[1]):
        print(f"{secs:9.4f} s  {100 * secs / total:5.1f}%  {kind}")
    print()
    for secs, name, kind, meta in rows[:40]:
        print(f"{secs:9.4f} s  {name}  [{kind}]  {meta[-110:]}")


if __name__ == "__main__":
    if sys.argv[1] == "--summarize":
        summarize(*sys.argv[2:])
    else:
        sys.exit(run(sys.argv[1:]))
