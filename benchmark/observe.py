"""Watching a run from outside: compiles, whole steps, and in the
traced steps each MFC and each reshard on a blocked clock.

Nothing of the program changes. The observers wrap
``InlineRunner.run_step``, ``Engine.train_batch``,
``ModelHost.execute`` and ``ReplicaManager.ensure_fresh``, and take the
wrappers off again. A step always ends blocked; an MFC and a reshard
are blocked only in a traced run or stretch (an MFC on what it returned
and on every role's weights), so that the end-to-end window keeps the
program's own overlap and still says, on its step lines, which MFC a
slow step was slow in. Tracing itself is the program's:
``realhf_tpu.obs.tracing.start(trace_dir, sync=MFC_SYNC)`` starts the
profiler and the program's own spans, ``stop()`` ends both.
``CompileWatch`` and the shape of ``watched_run_step`` were copied from
``chip_smoke.py``.
"""

import logging
import shutil
import threading
import time


#: the program's spans that end blocked while the profiler records:
#: exactly where the outside clocks block (an MFC on what it returned
#: and the role's weights, a reshard on the replica), so the profiled
#: steps are those of PR 23's traced run. Waiting after every engine
#: program as well (``sync=True``) exposes host work that an interface
#: hides behind the device (0.25 s a step in the four-chip cell:
#: PERF.md), so that is done in steps of its own, without the profiler.
MFC_SYNC = ("compute:", "realloc")


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
    the interface returned). With ``after_window`` as well, the window
    runs exactly as without ``trace_dir``; only when it has closed are
    ``trace_steps`` more steps run, traced and blocked, then
    ``trace_steps`` more with the profiler off and every span of the
    program synced (the capture the span readers take), and then the
    run is ended.

    Tracing starts when the step before the first traced one has ended
    and stops when the step after the last has begun, so that the
    program's ``step`` spans lie whole in the capture.

    ``before_first_step(runner)`` runs once, inside set-up, when the
    runner has loaded every role and is about to take its first step.
    """

    def __init__(self, seconds, watch, before_first_step=None,
                 trace_dir=None, trace_from=2, trace_steps=2,
                 say=lambda **kw: None, after_window=False):
        self.seconds = seconds
        self.watch = watch
        self.before_first_step = before_first_step
        self.trace_dir = trace_dir
        self.trace_from = trace_from
        self.trace_steps = trace_steps
        self.say = say
        self.after_window = after_window
        self.window_last = None  # index of the step that closed the window
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
        self.synced = None    # indices of the steps after it, every
        #                       span of the program synced (a list then)
        self._lock = threading.Lock()
        self._undo = []

    # -- what is blocked on ----------------------------------------------
    @property
    def blocking(self):
        """MFCs and reshards end blocked: in the whole of a traced run,
        or once the window of a run that traces afterwards has closed."""
        return bool(self.trace_dir) and (
            not self.after_window or self.window_last is not None)

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
        from realhf_tpu.obs import tracing
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
                if obs.tracing and len(obs.traced) == obs.trace_steps:
                    obs._stop_trace()
                    if obs.after_window:
                        # this step's `step` span is open and finishes
                        # into the new capture, whole
                        tracing.start(sync=True)
                        obs.synced = []
                elif obs.synced is not None \
                        and len(obs.synced) == obs.trace_steps:
                    tracing.stop()
                    raise WindowOver()
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
                elif obs.synced is not None:
                    obs.synced.append(index)
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
                window_over = obs.window_last is None and index >= 1 \
                    and end - obs.steps[1]["start"] >= obs.seconds
                if obs.after_window:
                    if window_over:  # its numbers are taken: now trace
                        obs.window_last = index
                        obs._start_trace()
                    return stats
                if obs.trace_dir and index == obs.trace_from - 1:
                    obs._start_trace()
                to_trace = obs.trace_dir and (obs.tracing or not obs.traced)
                if window_over and not to_trace:
                    raise WindowOver()
                return stats
            return run_step

        def watched_execute(orig):
            def execute(host, node_name, inp):
                start = time.monotonic()
                with TraceAnnotation(f"bench:mfc:{node_name}"):
                    out = orig(host, node_name, inp)
                    if obs.blocking:
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
                    if obs.blocking:
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
        from realhf_tpu.obs import tracing
        if self.tracing:
            self._stop_trace()
        elif self.synced is not None and tracing.enabled():
            tracing.stop()  # the run raised inside the synced steps
        for cls, name, orig in reversed(self._undo):
            setattr(cls, name, orig)
        self._undo = []

    def _start_trace(self):
        from realhf_tpu.obs import tracing
        if self.after_window:
            # the profiler's first start in a process costs seconds:
            # they are paid into a trace that is thrown away
            first = self.trace_dir + ".first"
            tracing.start(first)
            tracing.stop()
            shutil.rmtree(first, ignore_errors=True)
        tracing.start(self.trace_dir, sync=MFC_SYNC)
        self.tracing = True

    def _stop_trace(self):
        from realhf_tpu.obs import tracing
        tracing.stop()
        self.tracing = False

    # -- what came of it -------------------------------------------------
    def window_steps(self):
        """The measured steps: every finished step after the warm-up,
        up to the one that closed the window (steps traced after it
        are not among them)."""
        last = len(self.steps) if self.window_last is None \
            else self.window_last + 1
        return self.steps[1:last]

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
