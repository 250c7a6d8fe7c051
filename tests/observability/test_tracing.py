"""Span tracer: nesting, ids, noop-off mode, thread buffers, Chrome
export, file flush + multi-process merge."""

import json
import threading
import time

import pytest

from realhf_tpu.obs import tracing
from realhf_tpu.obs.tracing import SpanContext, Tracer


# ----------------------------------------------------------------------
# off-by-default noop
# ----------------------------------------------------------------------
def test_disabled_tracer_is_noop():
    t = Tracer("p")
    assert not t.enabled
    with t.span("work") as sp:
        sp.set_attribute("k", 1)  # must not raise
        assert t.inject() is None
    assert t.start_span("x") is tracing.NOOP_SPAN
    assert t.drain() == []


def test_module_default_off_by_default():
    with tracing.span("anything"):
        assert tracing.inject() is None
    assert tracing.default_tracer().drain() == []


# ----------------------------------------------------------------------
# nesting + ids
# ----------------------------------------------------------------------
def test_nested_spans_share_trace_and_parent():
    t = Tracer("p", enabled=True)
    with t.span("outer") as outer:
        with t.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
            assert t.current_span() is inner
        assert t.current_span() is outer
    assert t.current_span() is None
    names = {s.name: s for s in t.drain()}
    assert set(names) == {"outer", "inner"}
    assert names["inner"].end >= names["inner"].start


def test_start_span_explicit_lifetime_parents_to_current():
    t = Tracer("p", enabled=True)
    with t.span("request") as req:
        long_lived = t.start_span("background", rid="r1")
    # NOT on the stack: finishing the scoped span leaves it open
    assert {s.name for s in t.drain()} == {"request"}
    assert long_lived.parent_id == req.span_id
    long_lived.finish()
    assert [s.name for s in t.drain()] == ["background"]
    assert long_lived.attributes["rid"] == "r1"


def test_exception_recorded_as_error_attribute():
    t = Tracer("p", enabled=True)
    try:
        with t.span("boom"):
            raise ValueError("x")
    except ValueError:
        pass
    (sp,) = t.drain()
    assert "ValueError" in sp.attributes["error"]


# ----------------------------------------------------------------------
# context propagation carrier
# ----------------------------------------------------------------------
def test_inject_extract_roundtrip():
    t = Tracer("p", enabled=True)
    with t.span("root"):
        carrier = t.inject()
    ctx = Tracer.extract(carrier)
    assert isinstance(ctx, SpanContext)
    assert carrier == ctx.to_dict()
    assert Tracer.extract(None) is None
    assert Tracer.extract({"trace_id": "x"}) is None  # malformed


def test_extracted_context_parents_remote_span():
    master = Tracer("master", enabled=True)
    worker = Tracer("model_worker/0", enabled=True)
    with master.span("dispatch") as d:
        carrier = master.inject()
    with worker.span("mfc", parent=Tracer.extract(carrier)) as w:
        assert w.trace_id == d.trace_id
        assert w.parent_id == d.span_id


# ----------------------------------------------------------------------
# per-thread buffers
# ----------------------------------------------------------------------
def test_spans_from_many_threads_all_drain():
    t = Tracer("p", enabled=True)
    n_threads, per = 8, 50

    def work():
        for i in range(per):
            with t.span(f"s{i}"):
                pass

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(t.drain()) == n_threads * per
    assert t.drain() == []  # drained


def test_drain_while_recording_never_loses_spans():
    t = Tracer("p", enabled=True)
    total = 2000
    got = []
    done = threading.Event()

    def producer():
        for _ in range(total):
            with t.span("s"):
                pass
        done.set()

    th = threading.Thread(target=producer)
    th.start()
    while not done.is_set():
        got.extend(t.drain())
    th.join()
    got.extend(t.drain())
    assert len(got) == total


# ----------------------------------------------------------------------
# chrome export + merge
# ----------------------------------------------------------------------
def test_chrome_events_shape_and_stable_pid():
    t = Tracer("model_worker/0", enabled=True)
    with t.span("step", batch_id=3):
        pass
    events = t.to_events(t.drain())
    meta, ev = events[0], events[1]
    assert meta["ph"] == "M" and meta["args"]["name"] == "model_worker/0"
    assert ev["ph"] == "X" and ev["name"] == "step"
    assert ev["dur"] >= 0 and ev["args"]["batch_id"] == 3
    assert ev["pid"] == meta["pid"]
    # pid derives from the NAME: same-named tracers share a lane
    assert Tracer("model_worker/0").pid == t.pid
    assert Tracer("model_worker/1").pid != t.pid


def test_flush_to_file_and_merge(tmp_path):
    d = str(tmp_path / "trace")
    tracers = [
        Tracer("master", enabled=True, path=f"{d}/master.trace.jsonl"),
        Tracer("model_worker/0", enabled=True,
               path=f"{d}/worker0.trace.jsonl"),
    ]
    for t in tracers:
        with t.span("step"):
            with t.span("compute"):
                pass
        t.flush()
        t.flush()  # second flush with nothing buffered: no-op
    merged = tracing.merge_traces(directory=d)
    assert merged.endswith("merged_trace.json")
    doc = json.load(open(merged))
    events = doc["traceEvents"]
    pids = {e["pid"] for e in events if e["ph"] == "X"}
    assert len(pids) == 2  # one lane per process
    assert sum(1 for e in events if e["ph"] == "X") == 4
    names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert names == {"master", "model_worker/0"}


def test_merge_skips_corrupt_lines(tmp_path):
    d = tmp_path / "trace"
    d.mkdir()
    good = Tracer("ok", enabled=True,
                  path=str(d / "ok.trace.jsonl"))
    with good.span("s"):
        pass
    good.flush()
    # a worker killed mid-write leaves a torn line
    (d / "dead.trace.jsonl").write_text('{"name": "torn', )
    merged = tracing.merge_traces(directory=str(d))
    events = json.load(open(merged))["traceEvents"]
    assert any(e.get("name") == "s" for e in events)


def test_merge_empty_dir_returns_none(tmp_path):
    assert tracing.merge_traces(directory=str(tmp_path)) is None
    assert tracing.merge_traces(
        directory=str(tmp_path / "missing")) is None


# ----------------------------------------------------------------------
# env switch
# ----------------------------------------------------------------------
def test_trace_env_enabled():
    assert not tracing.trace_env_enabled(env={})
    assert not tracing.trace_env_enabled(env={"REALHF_TPU_TRACE": "0"})
    assert not tracing.trace_env_enabled(env={"REALHF_TPU_TRACE": ""})
    assert tracing.trace_env_enabled(env={"REALHF_TPU_TRACE": "1"})


def test_configure_from_env_labels_and_enables(tmp_path, monkeypatch):
    import realhf_tpu.base.constants as constants
    from realhf_tpu import obs
    monkeypatch.setenv("REALHF_TPU_TRACE", "1")
    constants.set_experiment_trial_names("obst", "t0")
    obs.configure_from_env("model_worker/0", experiment="obst",
                           trial="t0")
    t = tracing.default_tracer()
    assert t.enabled
    assert t.process_name == "model_worker/0"
    assert t.path.endswith("model_worker-0.trace.jsonl")


# ----------------------------------------------------------------------
# clocks
# ----------------------------------------------------------------------
def test_span_clock_is_monotonic_and_export_is_wall_clock():
    t = Tracer("p", enabled=True)
    before, wall_before = time.monotonic(), time.time()
    with t.span("work") as sp:
        pass
    after = time.monotonic()
    assert before <= sp.start <= sp.end <= after
    ev = t.to_events(t.drain(), with_meta=False)[0]
    # the per-process offset is applied at export, and only there
    assert ev["ts"] == pytest.approx(
        (sp.start + tracing.EPOCH_OFFSET) * 1e6)
    assert abs(ev["ts"] * 1e-6 - wall_before) < 5.0
    assert tracing.to_epoch(sp.start) == sp.start + tracing.EPOCH_OFFSET


def test_export_keeps_the_thread_that_ran_the_span():
    t = Tracer("p", enabled=True)

    def work():
        with t.span("in-thread"):
            pass
    th = threading.Thread(target=work)
    th.start()
    th.join(timeout=10)
    ident = th.ident
    [ev] = t.to_events(t.drain(), with_meta=False)
    assert ev["tid"] == ident & 0x7FFFFFFF
    assert ev["tid"] != threading.get_ident() & 0x7FFFFFFF


# ----------------------------------------------------------------------
# the control: start / stop in a running process
# ----------------------------------------------------------------------
def test_start_stop_start_again_in_one_process():
    assert tracing.stop() is None and tracing.last_capture() is None
    with tracing.span("before"):  # off: recorded nowhere
        pass
    tracing.start()
    assert tracing.enabled()
    with tracing.span("first", k=1):
        with tracing.span("inner"):
            pass
    first = tracing.stop()
    assert not tracing.enabled()
    assert [s["name"] for s in first.spans] == ["first", "inner"]
    outer, inner = first.spans
    assert set(outer) == {"name", "start", "end", "span_id", "parent_id",
                          "trace_id", "thread", "attributes"}
    assert inner["parent_id"] == outer["span_id"]
    assert outer["attributes"] == {"k": 1}
    assert outer["thread"] == threading.get_ident()
    assert first.start <= outer["start"] <= outer["end"] <= first.end
    assert tracing.last_capture() is first
    with tracing.span("between"):
        pass
    tracing.start()
    assert tracing.last_capture() is first  # until the next stop
    with tracing.span("second"):
        pass
    second = tracing.stop()
    assert [s["name"] for s in second.spans] == ["second"]
    assert tracing.last_capture() is second and not second.sync
    assert tracing.captures() == [first, second]  # the last few stay
    for _ in range(tracing.KEPT_CAPTURES):
        tracing.start()
        tracing.stop()
    assert len(tracing.captures()) == tracing.KEPT_CAPTURES
    assert second not in tracing.captures()


def test_start_while_started_closes_the_earlier_capture():
    tracing.start()
    with tracing.span("a"):
        pass
    tracing.start(sync=True)
    with tracing.span("b"):
        pass
    capture = tracing.stop()
    assert [s["name"] for s in capture.spans] == ["b"] and capture.sync


def test_a_span_open_at_stop_is_not_in_the_capture():
    tracing.start()
    with tracing.span("open"):
        with tracing.span("closed"):
            pass
        capture = tracing.stop()
    assert [s["name"] for s in capture.spans] == ["closed"]
    tracing.start()
    assert tracing.stop().spans == []  # and does not leak into the next


def test_capture_counts_only_what_happened_in_between():
    from realhf_tpu.obs import metrics
    metrics.inc("realloc_bytes_total", 100, role="actor")
    metrics.inc("unrelated_total", 5)
    tracing.start()
    metrics.inc("realloc_bytes_total", 40, role="actor")
    metrics.inc("realloc_bytes_total", 2, role="critic")
    metrics.inc("engine_compiles_total")
    metrics.inc("unrelated_total", 5)
    capture = tracing.stop()
    assert capture.counters == {
        "realloc_bytes_total{role=actor}": 40.0,
        "realloc_bytes_total{role=critic}": 2.0,
        "engine_compiles_total": 1.0}
    assert capture.counter("realloc_bytes_total", role="actor") == 40.0
    assert capture.counter("engine_compile_secs_total") == 0.0


def test_with_a_path_stop_also_writes_the_file(tmp_path):
    path = str(tmp_path / "trace" / "p.trace.jsonl")
    tracing.configure(process_name="p", path=path)
    tracing.start()
    with tracing.span("kept"):
        pass
    capture = tracing.stop()
    assert [s["name"] for s in capture.spans] == ["kept"]
    with open(path) as f:
        names = [json.loads(line)["name"] for line in f]
    assert names == ["process_name", "kept"]


def test_without_a_path_flush_leaves_spans_in_memory():
    tracing.start()
    with tracing.span("kept"):
        pass
    tracing.flush()
    assert [s["name"] for s in tracing.stop().spans] == ["kept"]


# ----------------------------------------------------------------------
# the profiler's clock: annotations only while a profile records
# ----------------------------------------------------------------------
class _Annotation:
    """Stands for ``jax.profiler.TraceAnnotation``."""
    live = []
    seen = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Annotation.live.append(self.name)
        _Annotation.seen.append(self.name)

    def __exit__(self, *exc):
        assert _Annotation.live.pop() == self.name


@pytest.fixture
def fake_profiler(monkeypatch):
    import jax
    calls = []
    _Annotation.live, _Annotation.seen = [], []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, profiler_options=None: calls.append(
            ("start", d, profiler_options.python_tracer_level)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    return calls


def test_off_means_the_shared_noop_and_no_annotation(fake_profiler):
    with tracing.span("off") as sp:
        assert sp is tracing.NOOP_SPAN
        assert sp.result(7) == 7
    assert tracing.current_span() is tracing.NOOP_SPAN
    tracing.start()  # spans, but no profile: still no annotation
    with tracing.span("spans-only") as sp:
        assert sp is not tracing.NOOP_SPAN
    tracing.stop()
    assert _Annotation.seen == [] and fake_profiler == []


def test_a_recording_profile_gets_every_scoped_span(fake_profiler,
                                                    tmp_path):
    tracing.start(str(tmp_path))
    with tracing.span("step"):
        with tracing.span("mfc:actor_gen"):
            assert _Annotation.live == ["step", "mfc:actor_gen"]
        explicit = tracing.start_span("request")  # may end elsewhere
        explicit.finish()
    capture = tracing.stop()
    with tracing.span("after"):
        pass
    assert _Annotation.seen == ["step", "mfc:actor_gen"]
    assert _Annotation.live == []
    assert fake_profiler == [("start", str(tmp_path), 0), ("stop",)]
    assert capture.profile_dir == str(tmp_path)
    assert {s["name"] for s in capture.spans} == {
        "step", "mfc:actor_gen", "request"}


# ----------------------------------------------------------------------
# synced against unsynced
# ----------------------------------------------------------------------
class Unfinished:
    """Stands for a device array whose program is still running: ready
    only some time after ``block_until_ready`` is asked for."""

    def __init__(self, secs=0.05):
        self.secs = secs
        self.ready_at = None

    def block_until_ready(self):
        time.sleep(self.secs)
        self.ready_at = time.monotonic()
        return self


@pytest.mark.parametrize("sync", [True, False])
def test_a_synced_span_ends_when_its_result_is_ready(sync):
    leaf = Unfinished()
    tracing.start(sync=sync)
    with tracing.span("engine:logprobs") as sp:
        assert sp.result(dict(out=leaf, n=3))["out"] is leaf
    [span] = tracing.stop().spans
    if sync:
        assert leaf.ready_at is not None and span["end"] >= leaf.ready_at
        assert span["end"] - span["start"] >= leaf.secs
    else:  # the program keeps its own overlap
        assert leaf.ready_at is None
        assert span["end"] - span["start"] < leaf.secs


def test_a_span_that_raised_waits_for_nothing():
    leaf = Unfinished()
    tracing.start(sync=True)
    with pytest.raises(RuntimeError):
        with tracing.span("engine:train") as sp:
            sp.result(leaf)
            raise RuntimeError("boom")
    [span] = tracing.stop().spans
    assert leaf.ready_at is None and "boom" in span["attributes"]["error"]


# ----------------------------------------------------------------------
# self time on a constructed capture
# ----------------------------------------------------------------------
def _span(name, start, end, span_id, parent_id=None, thread=1):
    return dict(name=name, start=start, end=end, span_id=span_id,
                parent_id=parent_id, trace_id="t", thread=thread,
                attributes={})


def test_self_time_arithmetic():
    spans = [
        _span("step", 0.0, 10.0, "s"),
        _span("mfc:actor_gen", 0.0, 6.0, "g", "s"),
        _span("realloc", 0.5, 3.5, "r", "g"),
        _span("realloc:repad", 0.5, 0.75, "rp", "r"),
        _span("realloc:put", 1.0, 3.0, "pu", "r"),
        _span("compute:actor_gen", 3.5, 6.0, "c", "g"),
        _span("engine:generate", 4.0, 5.5, "e", "c"),
        # two MFCs in two threads, overlapping in time
        _span("mfc:ref_inf", 6.0, 8.0, "m1", "s", thread=2),
        _span("engine:logprobs", 6.5, 7.5, "e1", "m1", thread=2),
        _span("mfc:rew_inf", 6.25, 8.25, "m2", "s", thread=3),
        _span("engine:values", 6.5, 7.0, "e2", "m2", thread=3),
        _span("engine:values", 6.75, 7.25, "e3", "m2", thread=3),
    ]
    c = tracing.Capture(spans=spans, counters={}, start=0.0, end=10.0)
    by = {s["span_id"]: s for s in spans}
    assert [s["span_id"] for s in c.named("mfc:")] == ["g", "m1", "m2"]
    assert [s["span_id"] for s in c.named("realloc")] == ["r"]
    assert {s["span_id"] for s in c.descendants(by["g"])} == {
        "r", "rp", "pu", "c", "e"}
    # children: the union of their intervals, clipped to the span
    assert c.self_seconds(by["g"]) == pytest.approx(6.0 - 3.0 - 2.5)
    assert c.self_seconds(by["r"]) == pytest.approx(3.0 - 0.25 - 2.0)
    assert c.self_seconds(by["s"]) == pytest.approx(10.0 - 8.25)
    assert c.self_seconds(by["e"]) == pytest.approx(1.5)
    # descendants that a predicate picks: an MFC's time outside every
    # engine program and reshard beneath it
    below = lambda s: s["name"].startswith("engine:") \
        or s["name"] == "realloc"  # noqa: E731
    assert c.self_seconds(by["g"], cover=below) == pytest.approx(
        6.0 - 3.0 - 1.5)
    assert c.self_seconds(by["m1"], cover=below) == pytest.approx(1.0)
    assert c.self_seconds(by["m2"], cover=below) == pytest.approx(
        2.0 - 0.75)  # overlapping children count once
    assert c.self_seconds(by["r"], cover=lambda s: s["name"]
                          == "realloc:put") == pytest.approx(1.0)


# ----------------------------------------------------------------------
# parentage through the runner's layers, across execute_level's threads
# ----------------------------------------------------------------------
class _Batch:
    keys = ("packed_input_ids",)

    def total_len(self, key):
        return 12


class _FakeEngine:
    from realhf_tpu.engine.engine import Engine
    _run = Engine._run
    _model_attrs = {}  # a dense model of one block: no such attributes
    params = ()

    def __init__(self):
        self._last_call, self._last_key = {}, {}
        self._facts, self._unread = {}, {}

    def ensure_on_device(self):
        pass


class _FakeInterface:

    def __init__(self, fn):
        self.fn = fn

    def inference(self, model, inp, n_mbs=None):
        import numpy as np
        return dict(out=model.engine._run("logprobs", (4,), self.fn, {},
                                          np.ones(4, np.float32)))


class _FakeHost:
    """``ModelHost`` with what ``execute`` touches filled in by hand:
    two inference MFCs of two roles, whose interfaces run one jitted
    program each through ``Engine._run``."""
    from realhf_tpu.system.model_host import ModelHost
    execute = ModelHost.execute
    execute_level = ModelHost.execute_level
    _execute_locked = ModelHost._execute_locked
    _role_lock = ModelHost._role_lock
    engines_of_node = ModelHost.engines_of_node

    def __init__(self):
        import types

        import jax

        from realhf_tpu.api.config import (
            ModelInterfaceAbstraction,
            ModelInterfaceType,
        )
        from realhf_tpu.api.dfg import MFCDef
        fn = jax.jit(lambda x: x + 1)
        names = {"ref_inf": "ref", "rew_inf": "reward"}
        self.nodes = {
            n: MFCDef(name=n, n_seqs=1,
                      interface_type=ModelInterfaceType.INFERENCE,
                      interface_impl=ModelInterfaceAbstraction("null"),
                      model_name=role)
            for n, role in names.items()}
        self.models = {role: types.SimpleNamespace(engine=_FakeEngine())
                       for role in names.values()}
        self.interfaces = {n: _FakeInterface(fn) for n in names}
        self.replicas, self.cross_group_nodes = {}, set()
        self._role_locks, self._role_locks_guard = {}, threading.Lock()
        self._hbm_memo, self.exec_infos = {}, {}


@pytest.mark.parametrize("parallel", [True, False])
def test_parentage_step_mfc_compute_engine(parallel, monkeypatch):
    # a level overlaps where the host has more than one CPU
    import realhf_tpu.system.model_host as mh
    monkeypatch.setattr(mh.os, "cpu_count", lambda: 2 if parallel else 1)
    host = _FakeHost()
    seen = set()
    tracing.start()
    with tracing.span("step", epoch=0, epoch_step=0):
        orig = host.execute

        def execute(node_name, inp):
            seen.add(threading.get_ident())
            return orig(node_name, inp)
        host.execute = execute
        outs = host.execute_level(
            [("ref_inf", _Batch()), ("rew_inf", _Batch())])
    capture = tracing.stop()
    assert len(outs) == 2 and len(seen) == (2 if parallel else 1)
    [step] = capture.named("step")
    mfcs = capture.children(step)
    assert sorted(s["name"] for s in mfcs) == ["mfc:ref_inf",
                                              "mfc:rew_inf"]
    for mfc in mfcs:
        name = mfc["name"][len("mfc:"):]
        assert mfc["attributes"]["kind"] == "inference"
        assert mfc["attributes"]["waited_s"] >= 0
        [compute] = capture.children(mfc)
        assert compute["name"] == f"compute:{name}"
        assert compute["attributes"]["tokens_in"] == 12
        [engine] = capture.children(compute)
        assert engine["name"] == "engine:logprobs"
        assert engine["attributes"]["compiled"] in (True, False)
        assert mfc["thread"] == compute["thread"] == engine["thread"]
        assert mfc["trace_id"] == step["trace_id"]
        assert (step["start"] <= mfc["start"] <= compute["start"]
                <= engine["start"] <= engine["end"] <= compute["end"]
                <= mfc["end"] <= step["end"])
        # one clock an MFC: exec_infos reads the span's clock around
        # the span (loose: a thread switch may fall in between)
        info = host.exec_infos[name]
        assert info["secs"] == pytest.approx(
            compute["end"] - compute["start"], abs=0.25)
        assert info["start"] == pytest.approx(
            tracing.to_epoch(compute["start"]), abs=0.25)
    if parallel:
        assert {m["thread"] for m in mfcs}.isdisjoint(
            {threading.get_ident()})
    # the first call lowered the program, the second did not
    assert sorted(e["attributes"]["compiled"]
                  for e in capture.named("engine:")) == [False, True] \
        or parallel  # (two threads may both find the cache empty)


def test_off_the_runner_layers_record_nothing():
    host = _FakeHost()
    host.execute_level([("ref_inf", _Batch()), ("rew_inf", _Batch())])
    assert set(host.exec_infos) == {"ref_inf", "rew_inf"}
    assert tracing.default_tracer().drain() == []


def test_sync_by_name_prefix_waits_only_for_those_spans():
    """An MFC ends blocked, an engine program inside it does not: the
    overlap inside the MFC stays the program's own."""
    inner, outer = Unfinished(), Unfinished()
    tracing.start(sync=("compute:", "realloc"))
    with tracing.span("compute:actor_train") as mfc:
        with tracing.span("engine:train") as sp:
            sp.result(inner)
        assert inner.ready_at is None
        mfc.result(outer)
    with tracing.span("realloc:put") as sp:
        sp.result(inner)
    capture = tracing.stop()
    assert outer.ready_at is not None and inner.ready_at is not None
    assert capture.sync == ("compute:", "realloc")
    by = {s["name"]: s for s in capture.spans}
    assert by["engine:train"]["end"] < outer.ready_at \
        <= by["compute:actor_train"]["end"]


def test_capture_reports_only_gauges_written_while_it_ran():
    """A gauge that an earlier model of the process left behind is not
    this capture's, whatever its value; one written meanwhile is, even
    with the value it had before."""
    from realhf_tpu.obs import metrics
    metrics.set_gauge("moe_held_load_max_over_mean", 3.0, role="earlier")
    metrics.set_gauge("moe_load_max_over_mean", 2.0, role="default")
    tracing.start()
    metrics.set_gauge("moe_load_max_over_mean", 2.0, role="default")
    capture = tracing.stop()
    assert capture.gauges == {"moe_load_max_over_mean{role=default}": 2.0}
    tracing.start()
    assert tracing.stop().gauges == {}
