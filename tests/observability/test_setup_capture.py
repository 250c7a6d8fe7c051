"""Set-up from the inside (ISSUE 50): ``quickstart.main`` records its
own set-up, from its first statement to the end of the runner's first
step, in ONE capture of the tracer it has (``tracing.start_setup`` /
``end_setup``); a capture a caller already has running takes the spans
instead; ``metrics.watch_compiles`` puts the stages of every lowering
on the span of the thread that caused it.

The runs are the benchmark's tiny cells' (their checkpoint, data and
overrides, ``tests/benchmark/manifest.json``) through ``quickstart.main``
itself, with no harness around it."""

import json
import os
import threading
import time

import pytest

from benchmark import generate, run
from realhf_tpu.apps import quickstart
from realhf_tpu.obs import metrics, setup, tracing

MANIFEST = os.path.join(run.ROOT, "tests", "benchmark", "manifest.json")
SEED = 2 ** 31 + 50


def quickstart_args(name, work, steps=2):
    """What a user would type for the tiny cell ``name``, its
    checkpoint and data written under ``work``."""
    cell = run.load_cell(MANIFEST, name)
    ckpt = os.path.join(str(work), "ckpt")
    generate.write_checkpoint(ckpt, cell["family"], cell["hf"], SEED)
    return [cell["kind"].EXPERIMENT,
            f"experiment_name=setup-{name}", "trial_name=t0",
            f"seed={SEED}", "total_train_epochs=100000",
            f"benchmark_steps={steps}"] + cell["kind"].build(
                cell["hf"], cell["meta"], cell["traffic"], ckpt,
                str(work), SEED)


def watch_levels(mp):
    """``tracing.enabled()`` each time the runner hands a level of
    MFCs to its models: once a step in SFT."""
    from realhf_tpu.system.model_host import ModelHost
    seen = []
    orig = ModelHost.execute_level

    def execute_level(host, named):
        seen.append(tracing.enabled())
        return orig(host, named)
    mp.setattr(ModelHost, "execute_level", execute_level)
    return seen


@pytest.fixture
def spans_on_by_level(monkeypatch):
    return watch_levels(monkeypatch)


def isolated(tmp_path_factory, mp):
    """What the tests' autouse fixtures do for one test, for a fixture
    of a module's: fresh obs singletons and file roots of its own."""
    import realhf_tpu.base.constants as constants
    import realhf_tpu.base.name_resolve as name_resolve
    work = tmp_path_factory.mktemp("setup")
    mp.setattr(constants, "ROOT_DIR", str(work / "realhf_tpu_root"))
    name_resolve.reconfigure("memory")
    tracing.reset_default()
    metrics.reset_default()
    return work


@pytest.fixture(scope="module")
def sft_run(tmp_path_factory):
    """ONE tiny ``quickstart sft`` of two steps, nothing started by a
    caller: the captures it left, whether spans were on in each step,
    and what ``end_setup`` told the operator."""
    with pytest.MonkeyPatch.context() as mp:
        work = isolated(tmp_path_factory, mp)
        on = watch_levels(mp)
        quickstart.main(quickstart_args("tiny.sft", work))
        out = dict(captures=tracing.captures(), on=on,
                   enabled_after=tracing.enabled(),
                   gauge=metrics.snapshot()["setup_seconds"]["values"],
                   stages=metrics.default_registry().counter(
                       "engine_stage_secs_total"))
    tracing.reset_default()
    metrics.reset_default()
    return out


@pytest.mark.parametrize("name, attributes", [
    ("setup:imports", ()),
    ("setup:spec", ("experiment", "allocation_mode")),
    ("setup:data", ("sequences", "tokenizer_s")),
    ("setup:model", ("role", "replica", "params", "bytes")),
    ("setup:model:load", ("path", "streamed", "bytes")),
    ("setup:model:shard", ("bytes", "layout")),
    ("setup:model:optimizer", ("zero1",)),
])
def test_quickstart_leaves_one_capture_with_the_spans_of_set_up(
        sft_run, name, attributes):
    [capture] = sft_run["captures"]
    assert capture.sync is False and capture.profile_dir is None
    spans = capture.named(name)
    assert len(spans) == (2 if name == "setup:imports" else 1)
    for span in spans:
        assert set(attributes) <= set(span["attributes"])
        assert capture.start <= span["start"] <= span["end"] <= capture.end
    if name.startswith("setup:model:"):
        [model] = capture.named("setup:model")
        assert all(s["parent_id"] == model["span_id"] for s in spans)
    else:  # the capture is the stretch: its spans hang under no root
        assert all(s["parent_id"] is None for s in spans)


def test_set_up_spans_say_what_they_held(sft_run):
    [capture] = sft_run["captures"]
    one = {n: capture.named(n)[0]["attributes"]
           for n in ("setup:spec", "setup:data", "setup:model",
                     "setup:model:load", "setup:model:shard",
                     "setup:model:optimizer")}
    assert one["setup:spec"]["experiment"] == "sft"
    assert one["setup:data"]["sequences"] == 16
    data = capture.named("setup:data")[0]
    assert 0 <= one["setup:data"]["tokenizer_s"] \
        <= data["end"] - data["start"]
    assert (one["setup:model"]["role"],
            one["setup:model"]["replica"]) == ("default", False)
    assert one["setup:model"]["bytes"] == 2 * one["setup:model"]["params"] \
        == one["setup:model:shard"]["bytes"] \
        == one["setup:model:load"]["bytes"] > 0
    assert one["setup:model:load"]["streamed"] is False
    assert one["setup:model:load"]["path"].endswith("ckpt")
    assert one["setup:model:shard"]["layout"] == "d1t1p1"
    # the optimizer's one jit, put down to the span that caused it
    assert one["setup:model:optimizer"]["programs"] == 1
    assert one["setup:model:optimizer"]["trace_s"] > 0


def test_the_first_step_lies_in_the_capture_with_its_lowering(sft_run):
    [capture] = sft_run["captures"]
    [step] = capture.named("step")
    [mfc] = capture.children(step)
    assert mfc["name"] == "mfc:trainDefault"
    [train] = [s for s in capture.descendants(mfc)
               if s["name"].startswith("engine:")]
    a = train["attributes"]
    assert train["name"] == "engine:train" and a["compiled"] is True
    assert a["programs"] == 1
    assert min(a["trace_s"], a["lower_s"], a["backend_s"]) > 0
    # a load's seconds lie inside the backend's, so the stages of one
    # thread's lowering fit into the span that caused them
    assert a["trace_s"] + a["lower_s"] + a["backend_s"] \
        <= train["end"] - train["start"]
    assert a.get("cache_hits", 0) + a.get("cache_misses", 0) <= 1


def test_spans_are_off_from_the_second_step_on(sft_run):
    [capture] = sft_run["captures"]
    assert sft_run["on"] == [True, False]
    assert sft_run["enabled_after"] is False
    assert len(capture.named("step")) == 1
    assert all(s["end"] <= capture.end for s in capture.spans)


@pytest.mark.parametrize("phase", sorted(setup.PHASES))
def test_the_operator_gets_the_gauge_of_every_phase(sft_run, phase):
    [capture] = sft_run["captures"]
    split = setup.split(capture)
    value = sft_run["gauge"][json.dumps(dict(phase=phase))]
    assert value == split[setup.PHASES[phase]] >= 0
    if phase == "program":
        assert value == pytest.approx(capture.end - capture.start,
                                      abs=1e-3)  # no harness: no head
        assert split["unattributed_s"] <= 0.05 * value
    if phase == "trace_lower":
        # the counters hold what no span was open for as well
        assert 0 < value <= sft_run["stages"].value(stage="trace") \
            + sft_run["stages"].value(stage="lower")


# ----------------------------------------------------------------------
# A capture that is already running
# ----------------------------------------------------------------------
@pytest.mark.parametrize("how", ["callers_start", "trace_env"])
def test_a_running_capture_receives_the_spans_and_is_left_alone(
        how, tmp_path, monkeypatch, spans_on_by_level):
    if how == "callers_start":
        tracing.start(sync=("compute:",))
    else:  # the runner's configure_from_env takes the capture over
        monkeypatch.setenv(tracing.TRACE_ENV, "1")
    quickstart.main(quickstart_args("tiny.sft", tmp_path))

    # the whole run in ONE capture, still running: the program began
    # and ended none of its own
    assert tracing.captures() == []
    assert tracing.enabled() and spans_on_by_level == [True, True]
    assert metrics.snapshot().get("setup_seconds") is None
    if how == "callers_start":
        assert tracing.default_tracer().sync == ("compute:",)
        capture = tracing.stop()
        names = [s["name"] for s in capture.spans]
    else:
        from realhf_tpu.obs import analyze
        assert tracing.default_tracer().path.endswith("inline.trace.jsonl")
        names = [e["name"] for e in analyze.load_events(
            os.path.join(tracing.trace_dir(), tracing.MERGED_TRACE_NAME))
            if e.get("ph") == "X"]
        assert tracing.stop().spans == []  # all flushed to the file
    assert names.count("step") == names.count("engine:train") == 2
    assert names.count("setup:imports") == 2
    for name in ("setup:spec", "setup:data", "setup:model",
                 "setup:model:load", "setup:model:shard",
                 "setup:model:optimizer"):
        assert names.count(name) == 1


def test_a_callers_capture_begun_during_set_up_is_not_stopped():
    tracing.start_setup()
    tracing.start(sync=True)  # stops the program's own, as any start
    assert len(tracing.captures()) == 1
    assert tracing.end_setup() is None and tracing.enabled()
    tracing.setup_spans(False)  # the runner's: nothing of a caller's
    assert tracing.enabled() and tracing.default_tracer().sync is True
    assert tracing.stop() is not None


def test_main_that_raises_before_its_first_step_leaves_spans_off(
        tmp_path):
    args = quickstart_args("tiny.sft", tmp_path)
    args = [a for a in args if not a.startswith("dataset.path=")] \
        + ["dataset.path=" + str(tmp_path / "no-such-file.jsonl")]
    with pytest.raises(Exception):
        quickstart.main(args)
    assert not tracing.enabled()
    [capture] = tracing.captures()
    assert capture.named("setup:spec") and not capture.named("step")
    assert "error" in capture.named("setup:data")[0]["attributes"]
    # and what there was of it is told all the same
    split = setup.split(capture)
    assert split["first_step_s"] == 0 and split["program_s"] > 0


def test_with_the_programs_spans_off_an_open_span_takes_nothing():
    """Between the call of ``run_step`` and its body the program's own
    capture is paused with its ``step`` span open: what a caller's
    wrapper lowers or annotates there lands on no span."""
    import jax
    import jax.numpy as jnp

    metrics.watch_compiles()
    tracing.start_setup()
    with tracing.span("step"):
        tracing.setup_spans(False)
        assert not tracing.enabled()
        assert tracing.current_span() is tracing.NOOP_SPAN
        tracing.current_span().set_attribute("bytes", 7)
        jax.jit(lambda x: jnp.exp(x) + 11.5)(jnp.ones((3, 5)))
        with tracing.span("engine:logprobs") as sp:  # a harness's
            assert sp is tracing.NOOP_SPAN
        tracing.setup_spans(True)
        assert tracing.enabled()
        assert tracing.current_span().name == "step"
    capture = tracing.end_setup()
    [step] = capture.spans
    assert step["name"] == "step" and step["attributes"] == {}
    # the counters are the process's: they hold it all the same
    assert capture.counter("engine_compiles_total") >= 1


def test_set_up_of_an_inline_runner_alone_starts_nothing():
    """Most tests build an ``InlineRunner`` without ``quickstart.main``:
    no capture, no span, and the calls the runner makes every step
    change nothing."""
    tracing.setup_spans(True)
    assert tracing.end_setup() is None
    assert not tracing.enabled() and tracing.captures() == []
    with tracing.span("setup:data") as sp:
        assert sp is tracing.NOOP_SPAN


# ----------------------------------------------------------------------
# The listener
# ----------------------------------------------------------------------
def test_two_compiling_threads_each_get_their_own_events():
    import jax
    import jax.numpy as jnp

    metrics.watch_compiles()
    tracing.start()
    ready = threading.Barrier(2)

    def compile_one(i):
        with tracing.span(f"thread:{i}"):
            ready.wait()
            fn = jax.jit(lambda x: jnp.tanh(x * (i + 2.5)).sum() - i)
            fn(jnp.ones((i + 3, 7))).block_until_ready()
    threads = [threading.Thread(target=compile_one, args=(i,))
               for i in range(2)]
    with tracing.span("main"):
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    capture = tracing.stop()
    for i in range(2):
        [span] = capture.named(f"thread:{i}")
        a = span["attributes"]
        # jnp.ones and the jitted function: each thread's own programs
        assert a["programs"] >= 1
        assert min(a["trace_s"], a["lower_s"], a["backend_s"]) > 0
        assert a["trace_s"] + a["lower_s"] + a["backend_s"] \
            <= span["end"] - span["start"]
    assert capture.named("main")[0]["attributes"] == {}
    assert capture.counter("engine_compiles_total") == sum(
        s["attributes"]["programs"] for s in capture.named("thread:"))
    assert capture.counter("engine_stage_secs_total", stage="trace") \
        == pytest.approx(sum(s["attributes"]["trace_s"]
                             for s in capture.named("thread:")))


def test_an_inner_jits_trace_is_counted_once():
    """jax fires the trace event of a jit traced inside another's
    trace as well: the outer event, which comes later, brings only
    the seconds that no inner one has brought."""
    import jax
    import jax.numpy as jnp

    metrics.watch_compiles()
    inner = jax.jit(lambda x: jnp.sin(x) * 3.25)

    @jax.jit
    def outer(x):
        time.sleep(0.02)  # traced once: the outer trace's own time
        return inner(x) + inner(x * 2.0).sum()
    tracing.start()
    with tracing.span("outer") as sp:
        outer(jnp.full((5, 3), 0.5)).block_until_ready()
    a = tracing.stop().named("outer")[0]["attributes"]
    assert a["trace_s"] >= 0.02
    assert a["trace_s"] + a["lower_s"] + a["backend_s"] \
        <= sp.end - sp.start

    seen = metrics._traces.seen
    seen.clear()
    now = time.monotonic()
    seen.extend([(now - 9.0, 1.0), (now - 5.0, 1.0), (now - 3.0, 2.0)])
    # an event that began 6 s ago holds the last two: 6 - 1 - 2
    assert metrics._outermost_trace_secs(6.0) == pytest.approx(3.0)
    assert len(seen) == 2 and seen[-1][1] == 6.0
    assert metrics._outermost_trace_secs(0.5) == 0.5


def test_with_spans_off_the_listener_only_counts():
    import jax
    import jax.numpy as jnp

    metrics.watch_compiles()
    jax.jit(lambda x: jnp.cos(x) - 7.75)(jnp.ones((2, 9)))
    values = metrics.snapshot()
    assert values["engine_compiles_total"]["values"][""] >= 1
    stages = values["engine_stage_secs_total"]["values"]
    assert {'{"stage": "trace"}', '{"stage": "lower"}'} <= set(stages)
    assert all(v > 0 for v in stages.values())


# ----------------------------------------------------------------------
# GRPO: three roles, and a replica where the layout differs
# ----------------------------------------------------------------------
@pytest.mark.parametrize("cell, replica", [("tiny.grpo", False),
                                           ("tiny.grpo-realloc", True)])
def test_grpo_has_a_model_span_a_role_and_one_a_replica(
        cell, replica, tmp_path):
    quickstart.main(quickstart_args(cell, tmp_path, steps=1))
    [capture] = tracing.captures()
    models = capture.named("setup:model")
    roles = [(s["attributes"]["role"], s["attributes"]["replica"])
             for s in models]
    assert roles[:3] == [("actor", False), ("ref", False),
                         ("reward", False)]
    assert roles[3:] == ([("actor-actor_gen", True)] if replica else [])
    for span in models:
        below = {s["name"] for s in capture.children(span)}
        # a replica takes the primary's live weights: nothing to load,
        # and it owns no optimizer, as the frozen roles own none
        assert ("setup:model:load" in below) \
            == (not span["attributes"]["replica"])
        assert "setup:model:shard" in below
        assert ("setup:model:optimizer" in below) \
            == (span["attributes"]["role"] == "actor")
    layouts = [s["attributes"]["layout"]
               for s in capture.named("setup:model:shard")]
    if replica:
        assert layouts[:4] == ["d2t2p1"] * 3 + ["d4t1p1"]

    # the first step whole: every MFC, each engine program compiled
    # under it with its own stages, two of them in threads of their own
    [step] = capture.named("step")
    assert {s["name"] for s in capture.children(step)} == {
        "mfc:actor_gen", "mfc:ref_inf", "mfc:rew_inf", "mfc:actor_train"}
    for program in ("generate", "logprobs", "values", "train"):
        spans = capture.named(f"engine:{program}")
        assert spans and spans[0]["attributes"]["compiled"] is True
        assert spans[0]["attributes"]["trace_s"] > 0
    split = setup.split(capture)
    assert split["roles"] == len(models)
    assert split["import_s"] + split["data_s"] + split["weights_s"] \
        + split["unattributed_s"] == pytest.approx(
            step["start"] - capture.start)
    assert split["trace_lower_s"] + split["facts_s"] \
        < split["first_step_s"] < split["program_s"]
