"""Watching a run from outside: compiles, whole steps, and in the
traced run each MFC and each reshard on a blocked clock.

Nothing of the program changes. The observers wrap
``InlineRunner.run_step``, ``Engine.train_batch``,
``ModelHost.execute`` and ``ReplicaManager.ensure_fresh``, and take the
wrappers off again. A step always ends blocked; an MFC and a reshard
are blocked only with tracing on (an MFC on what it returned and on
every role's weights), so that the end-to-end run keeps the program's
own overlap and still says, on its step lines, which MFC a
slow step was slow in. ``CompileWatch`` and the shape of
``watched_run_step`` were copied from ``chip_smoke.py``.
"""

import logging
import threading
import time


class WindowOver(Exception):
    """Raised out of the runner's loop when the measured window is
    over, so that the run ends without its forced final save (the
    optimizer state of every check's every run)."""


class CompileWatch:
    """Every program JAX lowers (name and argument shapes), the seconds
    the backend spent compiling or loading each from the persistent
    cache, and the cache's hits and misses."""

    def __init__(self):
        import jax
        from jax import monitoring

        self.programs = []   # "name shapes" in order
        self.secs = 0.0
        self.hits = self.misses = 0
        self.open = True
        self._log_compiles = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self._filters = [
            (logging.getLogger("jax._src.interpreters.pxla"),
             self._on_log),
            (logging.getLogger("jax._src.dispatch"),
             lambda rec: not rec.getMessage().startswith("Finished "))]
        for logger, f in self._filters:
            logger.addFilter(f)
        monitoring.register_event_duration_secs_listener(self._on_secs)
        monitoring.register_event_listener(self._on_event)

    def close(self):
        """Stop counting (JAX keeps the listeners; they go quiet)."""
        import jax
        self.open = False
        jax.config.update("jax_log_compiles", self._log_compiles)
        for logger, f in self._filters:
            logger.removeFilter(f)

    def _on_log(self, rec):
        msg = rec.getMessage()
        if not msg.startswith("Compiling "):
            return True
        name = msg.split()[1]
        shapes = msg.split("global shapes and types ", 1)[-1] \
            .split(". Argument mapping", 1)[0]
        self.programs.append(f"{name} {shapes}")
        return False  # counted here, kept off the console

    def _on_secs(self, event, secs, **_):
        if self.open and \
                event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _on_event(self, event, **_):
        if not self.open:
            return
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return len(self.programs), self.secs

    def since(self, mark):
        n, secs = mark
        return self.programs[n:], self.secs - secs


def short(program, width=200):
    """Program name and the END of its argument list: weights come
    first, and the batch, whose shape is what moves, comes last."""
    if len(program) <= width:
        return program
    name = program.split(" ", 1)[0]
    return f"{name} (... {program[-(width - len(name)):]}"


def union_seconds(intervals):
    """Length of the union of (start, end) intervals."""
    total, at = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > at:
            total += e - max(s, at)
            at = e
    return total


class Observer:
    """Runs the window. Step 0 is the warm-up (set-up ends with it);
    the window opens when step 1 starts and the run is ended, by
    ``WindowOver``, after the first step that ends ``seconds`` or more
    later. With ``trace_dir`` set, steps ``trace_from`` .. ``trace_from
    + trace_steps - 1`` are recorded by ``jax.profiler`` and every MFC
    and reshard is timed blocked (without it, unblocked: the wall until
    the interface returned).

    ``before_first_step(runner)`` runs once, inside set-up, when the
    runner has loaded every role and is about to take its first step.
    """

    def __init__(self, seconds, watch, before_first_step=None,
                 trace_dir=None, trace_from=2, trace_steps=2,
                 say=lambda **kw: None):
        self.seconds = seconds
        self.watch = watch
        self.before_first_step = before_first_step
        self.trace_dir = trace_dir
        self.trace_from = trace_from
        self.trace_steps = trace_steps
        self.say = say
        self.runner = None
        self.steps = []       # one dict a finished step
        self.failed = 0       # steps that raised
        self.opt_steps = []   # (step index, train_batch's stats)
        self.mfcs = []        # (step index, name, start, end), blocked
        self.reshards = []    # (step index, start, end), blocked
        self.first_step_entry = None
        self.setup_compile_secs = None  # backend seconds up to step 0's end
        self.tracing = False
        self.traced = []      # indices of the steps in the trace
        self._lock = threading.Lock()
        self._undo = []

    # -- what is blocked on ----------------------------------------------
    def _weights(self, runner):
        models = list(runner.models.values()) \
            + list(runner.replicas.values())
        return [m.engine.params for m in models]

    @staticmethod
    def _returned(out):
        """What an MFC returned that may still be on the device: the
        arrays of a ``SequenceSample`` (``.data``) or of a dict of
        statistics. Frozen roles' weights do not change, so an MFC
        that only generates or infers is over when these are."""
        return getattr(out, "data", out)

    # -- the wrappers ----------------------------------------------------
    def install(self):
        import jax
        from jax.profiler import TraceAnnotation

        from realhf_tpu.engine.engine import Engine
        from realhf_tpu.parallel.realloc import ReplicaManager
        from realhf_tpu.system.inline import InlineRunner
        from realhf_tpu.system.model_host import ModelHost

        obs = self

        def patch(cls, name, make):
            orig = getattr(cls, name)
            setattr(cls, name, make(orig))
            self._undo.append((cls, name, orig))

        def watched_train_batch(orig):
            def train_batch(engine, *a, **kw):
                out = orig(engine, *a, **kw)
                obs.opt_steps.append((len(obs.steps), out))
                return out
            return train_batch

        def watched_run_step(orig):
            def run_step(runner, batch):
                index = len(obs.steps)
                if index == 0:
                    obs.runner = runner
                    obs.first_step_entry = time.monotonic()
                    if obs.before_first_step is not None:
                        obs.before_first_step(runner)
                if obs.trace_dir and index == obs.trace_from:
                    obs._start_trace()
                mark = obs.watch.mark()
                start = time.monotonic()
                try:
                    with TraceAnnotation("bench:step", step=index):
                        stats = orig(runner, batch)
                        jax.block_until_ready(obs._weights(runner))
                except Exception:
                    obs.failed += 1
                    raise
                end = time.monotonic()
                programs, compile_secs = obs.watch.since(mark)
                if obs.tracing:
                    obs.traced.append(index)
                    if len(obs.traced) == obs.trace_steps:
                        obs._stop_trace()
                step = dict(
                    index=index, start=start, end=end,
                    tokens=batch.total_len("packed_input_ids"),
                    compiles=len(programs), compile_secs=compile_secs,
                    programs=[short(p) for p in programs])
                obs.steps.append(step)
                if index == 0:
                    obs.setup_compile_secs = obs.watch.secs
                obs.say(phase="step", index=index,
                        secs=round(end - start, 4), tokens=step["tokens"],
                        mfc_secs={n: round(e - s, 4)
                                  for i, n, s, e in obs.mfcs if i == index},
                        compiles=step["compiles"],
                        programs=step["programs"][:8])
                done_tracing = not obs.trace_dir or (
                    obs.traced and not obs.tracing)
                if index >= 1 and done_tracing \
                        and end - obs.steps[1]["start"] >= obs.seconds:
                    raise WindowOver()
                return stats
            return run_step

        def watched_execute(orig):
            def execute(host, node_name, inp):
                start = time.monotonic()
                with TraceAnnotation(f"bench:mfc:{node_name}"):
                    out = orig(host, node_name, inp)
                    if obs.trace_dir:
                        jax.block_until_ready(
                            (obs._returned(out), obs._weights(host)))
                with obs._lock:
                    obs.mfcs.append((len(obs.steps), node_name, start,
                                     time.monotonic()))
                return out
            return execute

        def watched_ensure_fresh(orig):
            def ensure_fresh(mgr, role, primary, replica, *a, **kw):
                def synced():
                    return mgr._synced.get(role, {}).get(id(replica))
                before, start = synced(), time.monotonic()
                with TraceAnnotation("bench:reshard"):
                    out = orig(mgr, role, primary, replica, *a, **kw)
                    if obs.trace_dir:
                        jax.block_until_ready(replica.engine.params)
                if synced() != before:
                    with obs._lock:
                        obs.reshards.append((len(obs.steps), start,
                                             time.monotonic()))
                return out
            return ensure_fresh

        patch(InlineRunner, "run_step", watched_run_step)
        patch(Engine, "train_batch", watched_train_batch)
        patch(ModelHost, "execute", watched_execute)
        patch(ReplicaManager, "ensure_fresh", watched_ensure_fresh)

    def uninstall(self):
        if self.tracing:
            self._stop_trace()
        for cls, name, orig in reversed(self._undo):
            setattr(cls, name, orig)
        self._undo = []

    def _start_trace(self):
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # spans by name are enough
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.tracing = True

    def _stop_trace(self):
        import jax
        jax.profiler.stop_trace()
        self.tracing = False

    # -- what came of it -------------------------------------------------
    def window_steps(self):
        """The measured steps: every finished step after the warm-up."""
        return self.steps[1:]

    def step_record(self, index, categories):
        """One step's blocked walls by category, the reshard, and the
        rest (``gap``): what the step's wall holds beside its MFCs."""
        step = self.steps[index]
        mine = [(n, s, e) for i, n, s, e in self.mfcs if i == index]
        reshard = [(s, e) for i, s, e in self.reshards if i == index]
        out = dict(wall=step["end"] - step["start"],
                   reshard=union_seconds(reshard))
        for cat in sorted(set(categories.values())):
            out[cat] = union_seconds(
                [(s, e) for n, s, e in mine if categories.get(n) == cat])
        for rs, re_ in reshard:
            # a reshard runs inside the MFC that needs the replica:
            # reported by itself, so taken off that MFC's category
            for n, s, e in mine:
                if s <= rs and re_ <= e and n in categories:
                    out[categories[n]] -= re_ - rs
                    break
        out["gap"] = out["wall"] - union_seconds(
            [(s, e) for _, s, e in mine])
        return out
