#!/usr/bin/env python3
"""Run one cell of the benchmark once, in one process.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1|2>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a model
configuration under a traffic mix. The run makes weights, tokenizer and
data from the seed, enters the program as a user does
(``realhf_tpu.apps.quickstart.main``), takes one whole warm-up step
(everything up to its end is ``setup_s``), then whole steps until
``--seconds`` have passed since the first measured step began, and
prints one JSON object as its last line. ``--trace 2`` is a ``--trace
0`` run that, once its window has closed, traces ``TRACE_STEPS`` more
steps in the same process (and runs as many again with the profiler off
and every span of the program synced), and prints both kinds of metric.
README.md has the rest, trace_in_run.md what ``--trace 2`` adds.

Without a TPU, with fewer chips than the cell asks for, or with a
device kind that ``arith.PEAKS`` does not list, it exits non-zero and
prints no result: there is no CPU path behind this command.
"""

import time

T0 = time.monotonic()  # set-up counts from here

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: |importance weight - 1| allowed on a step's first, on-policy
#: minibatch (generation's log-probabilities against training's)
ON_POLICY_TOL = 0.05
#: the steps the traced run records: two steady ones, after one
#: measured step without the profiler (``--trace 1``) or after the
#: window has closed (``--trace 2``)
TRACE_FROM, TRACE_STEPS = 2, 2


def say(**fields):
    print(json.dumps(fields), flush=True)


# ----------------------------------------------------------------------
# Finding a cell's files by the names in the manifest
# ----------------------------------------------------------------------
def find(manifest, sub, filename):
    """``<path>/<sub>/<filename>`` under the first of the manifest's
    ``paths`` (relative to the checkout's root) that has it."""
    tried = []
    for path in manifest["paths"]:
        full = os.path.join(ROOT, path, sub, filename)
        if os.path.exists(full):
            return full
        tried.append(full)
    raise FileNotFoundError(f"none of {tried} exists")


def load_module(path):
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(manifest, group, workload):
    """The metrics of ``group`` that this cell reports."""
    return [m for m in manifest[group]
            if workload in m.get("workloads", [workload])]


def load_cell(manifest_path, workload):
    """Everything a run needs, found from the manifest by name."""
    from benchmark import generate

    with open(manifest_path) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in {manifest_path}; "
                         f"it has {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in manifest["configs"]
                  if c["name"] == cell["config"])
    hf, meta = generate.load_config(os.path.join(ROOT, config["file"]))
    with open(find(manifest, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    kind = load_module(find(manifest, "kinds", traffic["kind"] + ".py"))
    family = load_module(find(manifest, "families", meta["family"] + ".py"))
    readers = {
        m["name"]: load_module(find(manifest, "layer_metrics",
                                    m["name"] + ".py"))
        for m in metrics_of(manifest, "per_layer", workload)}
    return dict(name=workload, chips=cell["chips"], config=config,
                hf=hf, meta=meta, family=family, traffic=traffic,
                kind=kind, readers=readers, manifest=manifest)


# ----------------------------------------------------------------------
# The run
# ----------------------------------------------------------------------
def device_line():
    import jax
    dev = jax.devices()[0]
    return dict(platform=dev.platform, kind=dev.device_kind,
                count=len(jax.devices()))


def memory_peaks(chips):
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices()[:chips]]


def check_reference(cell, runner, ckpt, seed):
    """The engine's log-probabilities on the fixed batch against the
    family's plain float32 forward, for every engine the kind names."""
    import numpy as np

    from benchmark import generate, reference

    family = cell["family"]
    ids = generate.fixed_batch(cell["hf"], seed)
    want = family.logprobs(cell["hf"], reference.load_tensors(ckpt), ids)
    out = {}
    for label, engine, refresh in cell["kind"].reference_engines(runner):
        if refresh is not None:
            refresh()
        got = np.asarray(engine.forward_logprobs(ids, np.ones_like(ids)),
                         np.float32)[:, :-1]
        gap, spread = reference.gap(got, want)
        out[label] = dict(mean_abs_delta=gap, reference_std=spread,
                          share_of_std=gap / spread,
                          ok=reference.within_tolerance(
                              got, want, family.TOLERANCE))
    return out


def run_cell(cell, seed, seconds, trace, work, peaks,
             expect_kernels=True):
    """One run of one cell. ``work`` is an empty directory for what the
    run writes. ``trace`` is 0 (end-to-end metrics), 1 (a traced run
    of its own: per-layer metrics) or 2 (the run of 0, then
    ``TRACE_STEPS`` traced steps: both). Returns the result line as a
    dict."""
    import jax

    from benchmark import generate, observe, trace_reduce
    from realhf_tpu.apps import quickstart
    from realhf_tpu.base.backend import enable_compile_cache

    kind, hf, meta = cell["kind"], cell["hf"], cell["meta"]
    family = cell["family"]
    chips = cell["chips"]
    cache_dir = enable_compile_cache()
    say(phase="start", workload=cell["name"], seed=seed, seconds=seconds,
        trace=trace, device=device_line(), jax=jax.__version__,
        cache_dir=cache_dir)

    t = time.monotonic()
    ckpt = os.path.join(work, "ckpt")
    n_params, write_secs = generate.write_checkpoint(ckpt, family, hf, seed)
    overrides = [
        f"experiment_name=benchmark-{cell['name']}",
        f"trial_name=seed{seed}", f"seed={seed}",
        "total_train_epochs=100000",
    ] + kind.build(hf, meta, cell["traffic"], ckpt, work, seed)
    say(phase="generated", secs=round(time.monotonic() - t, 2),
        since_process_start=round(time.monotonic() - T0, 2),
        checkpoint_secs=write_secs, params=n_params, overrides=overrides)

    watch = observe.CompileWatch()
    checks = {}

    def before_first_step(runner):
        t = time.monotonic()
        checks["reference"] = check_reference(cell, runner, ckpt, seed)
        say(phase="reference", secs=round(time.monotonic() - t, 2),
            tolerance=family.TOLERANCE, **checks["reference"])

    trace_dir = os.path.join(work, "trace") if trace else None
    obs = observe.Observer(
        seconds, watch, before_first_step, trace_dir=trace_dir,
        trace_from=TRACE_FROM, trace_steps=TRACE_STEPS, say=say,
        after_window=trace == 2)
    obs.install()
    t_main = time.monotonic()
    try:
        quickstart.main([kind.EXPERIMENT] + overrides)
        raise RuntimeError("the run ended by itself before the window "
                           "was over: too little data")
    except observe.WindowOver:
        pass
    finally:
        obs.uninstall()
        watch.close()
        from realhf_tpu.obs import http as obs_http
        obs_http.stop_default()
    runner = obs.runner

    # -- what the window held --------------------------------------------
    steps = obs.window_steps()
    setup_s = obs.steps[0]["end"] - T0
    wall = steps[-1]["end"] - steps[0]["start"]
    tokens = sum(s["tokens"] for s in steps)
    step_secs = [s["end"] - s["start"] for s in steps]
    # programs lowered after the warm-up, the traced stretch included
    window_compiles = sum(s["compiles"] for s in obs.steps[1:])

    bad_steps = set()
    first_minibatch = {}
    for index, stats in obs.opt_steps:
        if index > steps[-1]["index"]:
            break  # traced after the window: not what is judged
        first_minibatch.setdefault(index, stats)
        if not (math.isfinite(stats["loss"])
                and math.isfinite(stats["grad_norm"])):
            bad_steps.add(index)
    checks["finite"] = not bad_steps
    if kind.ON_POLICY:
        weights = [first_minibatch[s["index"]]["importance_weight"]
                   for s in obs.steps[:len(steps) + 1]]
        checks["importance_weight"] = dict(
            first_minibatch=weights,
            ok=all(abs(w - 1.0) < ON_POLICY_TOL for w in weights))
    t = time.monotonic()
    if expect_kernels:
        checks["tpu_custom_call"] = {
            label: "tpu_custom_call" in engine.compiled_text(program)
            for label, engine, program in kind.programs(runner)}
    say(phase="checks", kernel_check_secs=round(time.monotonic() - t, 2),
        **checks)
    correct = bool(
        checks["finite"]
        and all(c["ok"] for c in checks["reference"].values())
        and checks.get("importance_weight", dict(ok=True))["ok"]
        and all(checks.get("tpu_custom_call", {}).values()))

    peak_bytes = memory_peaks(chips)
    say(phase="window", steps=len(steps), wall_secs=wall,
        step_secs=step_secs, tokens=tokens,
        window_compiles=window_compiles,
        setup_compile_secs=obs.setup_compile_secs,
        programs_lowered=len(watch.programs),
        persistent_cache_hits=watch.hits,
        persistent_cache_misses=watch.misses,
        peak_bytes_in_use=peak_bytes,
        note="peak_bytes_in_use is blind to a program's temporaries "
             "on this runtime (PERF.md)")

    device = dict(device_line(), memory_peak_bytes=max(peak_bytes))
    result = dict(correct=correct, attempted=len(steps) + obs.failed,
                  failed=obs.failed + len(
                      bad_steps & {s["index"] for s in steps}))
    units = {m["name"]: m["unit"] for group in ("end_to_end", "per_layer")
             for m in cell["manifest"][group]}
    result["metrics"] = {}
    if trace != 1:
        values = dict(tokens_per_s=tokens / wall / chips,
                      step_max_s=max(step_secs), setup_s=setup_s)
        wanted = metrics_of(cell["manifest"], "end_to_end", cell["name"])
        result["metrics"].update(
            (m["name"], dict(value=values[m["name"]], unit=m["unit"]))
            for m in wanted)
    if trace:
        t = time.monotonic()
        files = sorted(glob.glob(os.path.join(
            trace_dir, "**", "*.xplane.pb"), recursive=True))
        reduced = trace_reduce.reduce(
            trace_reduce.read_xplane(files[-1]), kind.MFCS,
            chips=chips) if files else None
        say(phase="trace", secs=round(time.monotonic() - t, 2),
            files=[(os.path.basename(f), os.path.getsize(f))
                   for f in files],
            traced_steps=obs.traced, synced_steps=obs.synced,
            reduced={k: v for k, v in (reduced or {}).items()
                     if k != "breakdown"})
        # the steps whose MFCs were timed blocked: the whole window of
        # a traced run, the traced stretch after an untraced window
        step_records = [obs.step_record(i, kind.MFCS)
                        for i in (obs.traced if trace == 2 else
                                  [s["index"] for s in steps])]
        record = dict(
            steps=step_records,
            medians={k: statistics.median(r[k] for r in step_records)
                     for k in step_records[0]},
            entry_load_s=obs.first_step_entry - t_main,
            setup_compile_s=obs.setup_compile_secs,
            window_compiles=window_compiles, trace=reduced,
            hf=hf, meta=meta, family=family, traffic=cell["traffic"],
            work=kind.work(family, hf, meta, cell["traffic"]),
            chips=chips, peaks=peaks)
        say(phase="mfcs", steps=record["steps"])
        for name, reader in cell["readers"].items():
            value = reader.read(record)
            if value is not None:
                result["metrics"][name] = dict(value=value,
                                               unit=units[name])
        if reduced is not None:
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            result["breakdown"] = reduced["breakdown"]
    result["device"] = device
    return result


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    args = p.parse_args()

    manifest = os.path.join(ROOT, "BENCHMARK.json")
    cell = load_cell(manifest, args.workload)

    import jax

    from benchmark import arith
    dev = device_line()
    if dev["platform"] != "tpu":
        sys.exit(f"benchmark/run.py needs a TPU: jax.devices()[0] is "
                 f"{dev['platform']} ({dev['kind']})")
    if dev["count"] < cell["chips"]:
        sys.exit(f"{cell['name']} needs {cell['chips']} chips, JAX sees "
                 f"{dev['count']}")
    try:
        peaks = arith.peaks(dev["kind"])
    except KeyError as e:
        sys.exit(str(e))

    # Everything the run writes stays inside the checkout, and is fixed
    # before realhf_tpu is imported: logs, checkpoints, the trace.
    work = os.path.join(ROOT, "benchmark", ".cache", "work", cell["name"])
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    os.environ["REALHF_TPU_ROOT"] = os.path.join(work, "root")
    try:
        result = run_cell(cell, args.seed, args.seconds, args.trace,
                          work, peaks)
    finally:
        shutil.rmtree(work, ignore_errors=True)
        try:  # and the empty directories above it
            os.removedirs(os.path.dirname(work))
        except OSError:
            pass
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
