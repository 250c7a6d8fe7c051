"""Single-process experiment runner: the minimum end-to-end slice.

Executes an ExperimentSpec's dataflow graph in one process with all
models sharing the local device fleet in "symmetric allocation" (every
MFC on the same mesh), which is the reference's
``allocation_mode=d$Np$Pm$M`` global-hybrid mode
(``experiments/common/common.py:319``). The distributed
master/model-worker runtime adds disjoint sub-meshes and parameter
reallocation on top of the exact same interface calls.

Responsibilities mirrored from the reference master worker
(``system/master_worker.py``): dataset loading and epoch accounting,
topological MFC execution with key remapping, amending results into
the step's data buffer, save/eval frequency control, per-step
throughput logging (tokens + TFLOP/s), and benchmark early exit.
"""

import os
import time
from typing import Dict

import numpy as np

from realhf_tpu import obs
from realhf_tpu.api import data as data_api
from realhf_tpu.api.config import ModelInterfaceType
from realhf_tpu.api.dfg import DFG
from realhf_tpu.api.experiment import ExperimentSpec
from realhf_tpu.base import constants, logging, recover, seeding, timeutil
from realhf_tpu.base.backend import enable_compile_cache
from realhf_tpu.obs import metrics, tracing
from realhf_tpu.system.model_host import ModelHost

logger = logging.getLogger("InlineRunner", "benchmark")


class InlineRunner:

    def __init__(self, spec: ExperimentSpec, recover_mode: str = "disabled"):
        self.spec = spec
        enable_compile_cache()
        constants.set_experiment_trial_names(spec.experiment_name,
                                             spec.trial_name)
        # REALHF_TPU_TRACE=1 gives the single-process runner the same
        # span timeline the distributed runtime emits (one process)
        obs.configure_from_env("inline", experiment=spec.experiment_name,
                               trial=spec.trial_name)
        # live telemetry endpoints, same surface as any worker
        # (obs/http.py; REALHF_TPU_TELEMETRY=0 opts out)
        from realhf_tpu.base import name_resolve, names
        from realhf_tpu.obs import http as obs_http
        self.telemetry = obs_http.start_from_env(
            "inline", health=self._telemetry_health)
        if self.telemetry is not None:
            try:
                name_resolve.add(
                    names.telemetry(spec.experiment_name,
                                    spec.trial_name, "inline"),
                    self.telemetry.address, replace=True)
            except Exception:  # noqa: BLE001 - discovery is advisory
                pass
        seeding.set_random_seed(spec.seed)

        # Recovery (reference recover_mode resume, base/recover.py +
        # master_worker.__recover_save:1541): restore step counters and
        # the set of data ids consumed in the interrupted epoch, and
        # redirect trainable models to their latest checkpoints.
        self.recover_mode = recover_mode
        self._recover_info = None
        if recover_mode == "resume":
            # load_safe: a corrupt/truncated/future-schema file means
            # a fresh start, not a crash loop
            self._recover_info = recover.load_safe()
        if self._recover_info is not None:
            logger.info("Resuming from recover info (schema v%d): %s",
                        self._recover_info.version,
                        self._recover_info.recover_start)
            for role, mspec in spec.models.items():
                ckpt = os.path.join(constants.run_save_path(), role)
                if os.path.exists(os.path.join(ckpt, "config.json")):
                    mspec.path = ckpt
                    mspec.random_init_config = None
                    mspec.restore_optimizer_state = True
                    logger.info("Recovered %s from %s", role, ckpt)

        with tracing.span("setup:imports"):
            import realhf_tpu.datasets  # noqa: F401 - register datasets
            import realhf_tpu.interfaces  # noqa: F401 - register interfaces

        self.dfg = DFG(spec.mfcs)
        with tracing.span("setup:data") as sp:
            t0 = time.monotonic()
            self.tokenizer = spec.tokenizer or (
                data_api.load_hf_tokenizer(spec.tokenizer_path)
                if spec.tokenizer_path else None)
            sp.set_attribute("tokenizer_s", time.monotonic() - t0)

            src = self.dfg.sources[0]
            self.dataset = data_api.make_dataset(
                spec.dataset, seed=spec.seed, dp_rank=0, world_size=1,
                tokenizer_or_path=self.tokenizer)
            self.dataloader = data_api.PackedDataLoader(
                self.dataset, batch_size=src.n_seqs, seed=spec.seed)
            sp.set_attribute("sequences", len(self.dataset))
            self.eval_dataloader = None
            if spec.eval_dataset is not None:
                eval_ds = data_api.make_dataset(
                    spec.eval_dataset, seed=spec.seed, dp_rank=0,
                    world_size=1, tokenizer_or_path=self.tokenizer)
                self.eval_dataloader = data_api.PackedDataLoader(
                    eval_ds, batch_size=src.n_seqs, shuffle=False)

        steps_per_epoch = len(self.dataloader)
        total_steps = steps_per_epoch * spec.total_train_epochs
        self.host = ModelHost(spec, list(spec.models), self.dfg.nodes,
                              self.tokenizer, total_steps)

        ctl = spec.ctl
        self.save_ctl = timeutil.EpochStepTimeFreqCtl(
            freq_epoch=ctl.save_freq_epochs, freq_step=ctl.save_freq_steps,
            freq_sec=ctl.save_freq_secs)
        self.eval_ctl = timeutil.EpochStepTimeFreqCtl(
            freq_epoch=ctl.eval_freq_epochs, freq_step=ctl.eval_freq_steps,
            freq_sec=None)
        self.global_step = 0
        self._start_epoch = 0
        self._start_epoch_step = 0
        self._ids_to_skip = set()
        if self._recover_info is not None:
            self.global_step = self._recover_info.last_step_info.global_step
            self._start_epoch = self._recover_info.recover_start.epoch
            self._ids_to_skip = set(self._recover_info.hash_vals_to_ignore)
            # dataloader epoch state (schema v2): resume epoch-step
            # accounting mid-epoch so save/eval frequency control and
            # logs line up with the interrupted run (consumed-id
            # skipping already prevents data re-consumption)
            dl = self._recover_info.dataloader_state or {}
            self._start_epoch_step = int(dl.get("epoch_step", 0))

    def _telemetry_health(self):
        return dict(worker="inline", state="RUNNING",
                    global_step=self.global_step)

    # -- compat accessors (tests + callers use these) -------------------
    @property
    def models(self):
        return self.host.models

    @property
    def replicas(self):
        return self.host.replicas

    @property
    def replica_mgr(self):
        return self.host.replica_mgr

    @property
    def interfaces(self):
        return self.host.interfaces

    # ------------------------------------------------------------------
    def run_step(self, batch: data_api.SequenceSample) -> Dict[str, Dict]:
        """Execute the full DFG once over one batch; returns per-MFC
        stats (mirrors one master-worker _poll iteration)."""
        tracing.setup_spans(True)
        stats: Dict[str, Dict] = {}
        data = batch
        # Execute level by level; independent MFCs within a level run
        # concurrently (host.execute_level), mirroring the distributed
        # master's concurrent dispatch. Outputs merge in level order.
        for level in self.dfg.topological_levels():
            named = [(node.name,
                      data.select([k for k in node.input_keys
                                   if k in data.keys]))
                     for node in level]
            outs = self.host.execute_level(named)
            for node, out in zip(level, outs):
                if isinstance(out, data_api.SequenceSample):
                    data.update_(out)
                elif isinstance(out, dict):
                    stats[node.name] = out
                    if node.log_return_value:
                        logger.info("MFC %s stats: %s", node.name, out)
        return stats

    def _maybe_save(self, epochs: int = 0, steps: int = 0, force=False):
        if not force and not self.save_ctl.check(epochs=epochs, steps=steps):
            return
        for node in self.dfg.nodes:
            if node.interface_type != ModelInterfaceType.TRAIN_STEP:
                continue
            # host.save_role streams the weights AND the optimizer
            # state -- the resume path above restores Adam moments
            # only if they were written here (it used to call the
            # interface save directly, which silently dropped them).
            self.host.save_role(node.role, node.name)
        # Recover info is only valid paired with the checkpoint it
        # describes (reference couples them in __recover_save), so it
        # is dumped here, never on unsaved steps.
        if self.recover_mode != "disabled":
            recover.dump(recover.RecoverInfo(
                recover_start=recover.StepInfo(
                    epoch=self._cur_epoch,
                    epoch_step=self._cur_epoch_step + 1,
                    global_step=self.global_step),
                last_step_info=recover.StepInfo(
                    epoch=self._cur_epoch,
                    epoch_step=self._cur_epoch_step,
                    global_step=self.global_step),
                hash_vals_to_ignore=list(self._consumed_ids),
                dataloader_state=dict(
                    epoch=self._cur_epoch,
                    epoch_step=self._cur_epoch_step)))

    def _maybe_eval(self, epochs: int = 0, steps: int = 0):
        if self.eval_dataloader is None:
            return
        if not self.eval_ctl.check(epochs=epochs, steps=steps):
            return
        for node in self.dfg.nodes:
            if node.interface_type != ModelInterfaceType.TRAIN_STEP:
                continue
            ev = self.interfaces[node.name].evaluate(
                self.models[node.role], self.eval_dataloader)
            if ev:
                logger.info("Eval %s: %s", node.role, ev)

    def run(self) -> Dict[str, Dict]:
        """Train for the configured epochs; returns the last step stats."""
        spec = self.spec
        last_stats = {}
        done = False
        self._consumed_ids = list(self._ids_to_skip)
        self._cur_epoch = self._start_epoch
        self._cur_epoch_step = self._start_epoch_step
        for epoch in range(self._start_epoch, spec.total_train_epochs):
            self._cur_epoch = epoch
            for step, batch in enumerate(self.dataloader):
                self._cur_epoch_step = step
                if self._ids_to_skip:
                    # first epoch after recovery: drop already-consumed
                    # data (reference master_worker.py:762-768)
                    batch = data_api.drop_ids(batch, self._ids_to_skip)
                    if batch is None:
                        continue
                t0 = time.monotonic()
                with tracing.span("step", epoch=epoch, epoch_step=step,
                                  global_step=self.global_step + 1):
                    # what a caller wraps around run_step is not the
                    # program's set-up: its own capture takes no span
                    # from here to that method's body
                    tracing.setup_spans(False)
                    last_stats = self.run_step(batch)
                dt = time.monotonic() - t0
                # the program's own record of its set-up ends with its
                # first step (a no-op from the second on, and where
                # quickstart began none): the run goes on untraced
                tracing.end_setup()
                self.global_step += 1
                metrics.inc("master_steps_total")
                metrics.observe("master_step_secs", dt)
                token_key = next(
                    (k for k in ("packed_input_ids", "packed_prompts")
                     if k in batch.keys),
                    max(batch.keys, key=batch.total_len))
                n_tokens = batch.total_len(token_key)
                logger.info(
                    "epoch %d step %d (global %d): %.2fs, #tokens %d, %s",
                    epoch, step, self.global_step, dt, n_tokens,
                    {k: {kk: round(vv, 4) for kk, vv in v.items()
                         if isinstance(vv, float)}
                     for k, v in last_stats.items()})
                self._consumed_ids.extend(batch.ids)
                self._maybe_save(steps=1)
                self._maybe_eval(steps=1)
                if (spec.ctl.benchmark_steps is not None
                        and self.global_step >= spec.ctl.benchmark_steps):
                    done = True
                    break
            if done:
                break
            self._ids_to_skip = set()
            self._consumed_ids = []
            self._maybe_save(epochs=1)
            self._maybe_eval(epochs=1)
        self._maybe_save(force=True)
        if tracing.enabled():
            tracing.flush()
            merged = tracing.merge_traces()
            if merged:
                logger.info("Chrome trace written: %s (open in "
                            "Perfetto / chrome://tracing).", merged)
                from realhf_tpu.obs import analyze
                summary = analyze.summarize_path(merged)
                if summary:
                    logger.info("%s (full report: python "
                                "scripts/analyze_trace.py %s)",
                                summary, merged)
        # final metrics snapshot: the poll-loop interval flush never
        # runs here, so a short run would exit with buffered/last
        # gauge values unpersisted
        metrics.flush_final()
        return last_stats
