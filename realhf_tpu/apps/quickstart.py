"""Quickstart CLI: ``python -m realhf_tpu.apps.quickstart <algo> a.b=c ...``

Parity with reference ``realhf/apps/quickstart.py:22``: one subcommand
per registered experiment, configured by dotted key=value overrides
(the reference's Hydra override syntax), e.g.::

    python -m realhf_tpu.apps.quickstart sft \
        experiment_name=my-sft trial_name=t0 \
        model.path=/path/to/llama dataset.path=data.jsonl \
        dataset.train_bs_n_seqs=128 model.optimizer.lr=1e-5 \
        model.parallel.data_parallel_size=4 \
        model.parallel.tensor_parallel_size=2
"""

import argparse
import sys

from realhf_tpu.base import logging

logger = logging.getLogger("quickstart")


def parse_overrides(tokens):
    out = {}
    for t in tokens:
        if "=" not in t:
            raise ValueError(f"Override `{t}` is not of the form key=value.")
        k, v = t.split("=", 1)
        out[k] = v
    return out


def _build_spec(experiments, args):
    """The experiment's configuration with its overrides, its spec and
    the allocation (heuristic or search)."""
    from realhf_tpu.experiments.common import apply_overrides
    cfg = experiments.ALL_EXPERIMENT_CLASSES[args.experiment]()
    apply_overrides(cfg, parse_overrides(args.overrides))

    logger.info("Running experiment %s: %s", args.experiment, cfg)
    spec = cfg.build()
    spec.n_model_workers = cfg.n_model_workers
    spec.worker_assignment = cfg.parsed_worker_assignment()
    if cfg.allocation_mode in ("heuristic", "search", "search_profiled"):
        # A distributed launcher stays off JAX: a process that
        # initialises the TPU backend holds the chips its workers need.
        if cfg.n_devices is not None:
            n = cfg.n_devices
        elif cfg.mode == "distributed":
            raise ValueError(
                f"allocation_mode={cfg.allocation_mode} with "
                "mode=distributed requires n_devices=<per-worker chip "
                "count> (the launcher must not initialize the workers' "
                "backend).")
        else:
            from realhf_tpu.parallel.mesh import default_devices
            n = len(default_devices())
        if cfg.allocation_mode == "heuristic":
            from realhf_tpu.experiments.heuristic import (
                apply_heuristic_allocations,
            )
            apply_heuristic_allocations(spec, n)
        else:
            # C++ MCMC search over (device slice x layout) assignments
            from realhf_tpu.search import apply_searched_allocations
            cost_model = None
            if cfg.allocation_mode == "search_profiled":
                # measured calibration (reference estimate.py:323):
                # runs timed probes on THIS process's default backend,
                # so it is inline/local-mode only -- in distributed
                # mode the launcher must not claim the workers' chips.
                if cfg.mode == "distributed":
                    raise ValueError(
                        "allocation_mode=search_profiled probes the "
                        "accelerator from the launcher and cannot be "
                        "used with mode=distributed; run the profile "
                        "inline or use allocation_mode=search.")
                from realhf_tpu.search.engine import calibrate_cost_model
                cost_model = calibrate_cost_model(spec)
            res = apply_searched_allocations(spec, n,
                                             cost_model=cost_model)
            logger.info("Search: best simulated step %.3fs", res.time)
            if (cfg.mode == "distributed" and not spec.worker_assignment
                    and cfg.n_model_workers == 1
                    and res.worker_assignment):
                # realize the simulator's slice concurrency: disjoint
                # role groups become separate worker processes
                spec.worker_assignment = res.worker_assignment
                spec.n_model_workers = (
                    max(res.worker_assignment.values()) + 1)
                logger.info(
                    "Search-derived worker assignment: %s "
                    "(%d model workers)", spec.worker_assignment,
                    spec.n_model_workers)
        logger.info("%s allocations on %d devices: %s",
                    cfg.allocation_mode, n,
                    {k: str(v) for k, v in spec.allocations.items()})
    return cfg, spec


def main(argv=None):
    from realhf_tpu.obs import tracing

    # the program records its own set-up, from here to the end of its
    # first step (docs/observability.md, "The spans of set-up"); a
    # capture that a caller has running takes the spans instead
    tracing.start_setup()
    try:
        return _main(argv)
    finally:
        tracing.end_setup()  # raised before the first step had ended


def _main(argv):
    from realhf_tpu.obs import metrics, tracing

    with tracing.span("setup:imports"):
        import realhf_tpu.experiments as experiments
        from realhf_tpu.base.backend import enable_compile_cache
        from realhf_tpu.base.importing import import_usercode

        enable_compile_cache()  # a config update: touches no device
        import_usercode()  # REALHF_TPU_PACKAGE_PATH custom registrations
        # the stages of every lowering from here on, the checkpoint's
        # and the optimizer's before the first engine is whole too
        metrics.watch_compiles()

    argv = argv if argv is not None else sys.argv[1:]
    parser = argparse.ArgumentParser("realhf_tpu quickstart")
    parser.add_argument(
        "experiment", choices=sorted(experiments.ALL_EXPERIMENT_CLASSES))
    parser.add_argument("overrides", nargs="*",
                        help="dotted key=value config overrides")
    args = parser.parse_args(argv)

    with tracing.span("setup:spec", experiment=args.experiment) as sp:
        cfg, spec = _build_spec(experiments, args)
        sp.set_attribute("allocation_mode", cfg.allocation_mode)

    if getattr(spec, "serving", None) is not None:
        # rollout/serving deployment: no master/dataflow, just
        # GenServerWorker processes answering RolloutClient traffic
        # (docs/serving.md)
        from realhf_tpu.apps.main import run_serve
        tracing.end_setup()  # a launcher's set-up ends here
        stats = run_serve(
            spec, duration=getattr(cfg, "serve_duration_secs", None))
    elif cfg.mode == "distributed":
        # master + model-worker processes, concurrent MFCs on disjoint
        # meshes (reference multi-worker runtime)
        from realhf_tpu.apps.main import main_start
        tracing.end_setup()  # a launcher's set-up ends here
        stats = main_start(spec, recover_mode=cfg.recover_mode,
                           recover_retries=cfg.recover_retries)
    else:
        from realhf_tpu.system.inline import InlineRunner
        runner = InlineRunner(spec, recover_mode=cfg.recover_mode)
        stats = runner.run()
    logger.info("Experiment complete. Last step stats: %s", stats)
    _report_observability_artifacts()
    return stats


def _report_observability_artifacts():
    """Point the operator at what REALHF_TPU_TRACE=1 produced: the
    merged Chrome trace (written by the inline runner or the launcher
    teardown, docs/observability.md) and the per-process metrics
    JSONL directory."""
    import os

    from realhf_tpu.obs import tracing
    if not tracing.trace_env_enabled():
        return
    d = tracing.trace_dir()
    merged = os.path.join(d, tracing.MERGED_TRACE_NAME)
    if os.path.exists(merged):
        logger.info("Trace timeline: %s (load in https://ui.perfetto.dev"
                    " or chrome://tracing).", merged)
        from realhf_tpu.obs import analyze
        summary = analyze.summarize_path(merged)
        if summary:
            logger.info("%s (full report: python "
                        "scripts/analyze_trace.py %s)", summary,
                        merged)
    elif os.path.isdir(d):
        logger.info("Per-process trace shards under %s (merge with "
                    "realhf_tpu.obs.tracing.merge_traces).", d)


if __name__ == "__main__":
    main()
