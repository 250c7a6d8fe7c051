"""The nine ``setup.*`` readers (ISSUE 50): each takes one number of
the program's own arithmetic (``realhf_tpu.obs.setup.split``) over the
capture that ``quickstart.main`` records of its set-up, on a hand-made
capture with known seconds, gives nothing where the program recorded
none, stands at the end of ``BENCHMARK.json`` as the issue asks, and is
reported by a tiny cell run whole."""

import json
import os

import pytest
from tiny_cells import MANIFEST, PEAKS, check_line

from benchmark import run

#: reader -> what it reads of the capture below
NINE = {
    "setup.program_s": 26.0,       # 30 of capture less the head's 4
    "setup.import_s": 2.0,         # imports 1 + 0.5, spec 0.5
    "setup.data_s": 2.0,
    "setup.weights_s": 6.0,        # two roles end to end, 4 + 2
    "setup.trace_lower_s": 4.3,    # the optimizer's 0.3, the step's 4
    "setup.cache_misses": 1,       # the optimizer's; not the head's
    "setup.facts_s": 1.5,
    "setup.first_step_s": 14.0,    # first MFC's start to the step's end
    "setup.unattributed_s": 2.0,   # the gaps: 0.5 + 0.5 + 1
}
FIRST = 47  # per-layer metrics of BENCHMARK.json before this PR


def span(name, start, end, span_id, parent=None, **attributes):
    return dict(name=name, start=start, end=end, span_id=span_id,
                parent_id=parent, trace_id="t", thread=0,
                attributes=attributes)


def setup_capture():
    """A set-up of 30 s from 100.0: 12 s before the warm-up step, of
    which the harness's reference comparison takes the first 4."""
    from realhf_tpu.obs import tracing
    spans = [
        span("setup:imports", 100.0, 101.0, "i0"),
        span("setup:spec", 101.0, 101.5, "s0", experiment="sft",
             allocation_mode="manual"),
        span("setup:imports", 102.0, 102.5, "i1"),
        span("setup:data", 102.5, 104.5, "d0", tokenizer_s=1.5,
             sequences=64),
        span("setup:model", 105.0, 109.0, "m0", role="actor",
             replica=False, params=500, bytes=1000),
        span("setup:model:load", 105.0, 107.0, "m0l", "m0", bytes=1000),
        span("setup:model:shard", 107.0, 108.0, "m0s", "m0", bytes=1000),
        span("setup:model:optimizer", 108.0, 109.0, "m0o", "m0",
             trace_s=0.2, lower_s=0.1, backend_s=0.3, programs=1,
             cache_misses=1),
        span("setup:model", 109.0, 111.0, "m1", role="ref",
             replica=False, params=500, bytes=1000),
        span("step", 112.0, 130.0, "st"),
        # the harness's doing, inside the patched run_step
        span("engine:logprobs", 112.5, 115.0, "h0", "st", trace_s=1.0,
             lower_s=0.5, backend_s=0.7, programs=1, cache_misses=1),
        span("mfc:trainDefault", 116.0, 129.5, "mf", "st"),
        span("engine:train", 116.5, 126.0, "e0", "mf", compiled=True,
             trace_s=3.0, lower_s=1.0, backend_s=2.0, programs=1,
             cache_hits=1, cache_load_s=1.5),
        span("engine:facts", 126.0, 127.5, "f0", "mf", trace_s=0.5,
             lower_s=0.25, backend_s=0.5, programs=1, cache_hits=1,
             cache_load_s=0.4),
    ]
    return tracing.Capture(spans=spans, counters={}, start=100.0,
                           end=130.0)


def steps_capture():
    from realhf_tpu.obs import tracing
    return tracing.Capture(
        spans=[span("step", 0.0, 1.0, "st"),
               span("mfc:trainDefault", 0.1, 0.9, "mf", "st")],
        counters={}, start=0.0, end=1.0, sync=True)


def reader(name):
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return run.load_module(run.find(manifest, "layer_metrics",
                                    name + ".py"))


@pytest.mark.parametrize("name", NINE)
def test_reader_on_a_capture_with_known_seconds(name, monkeypatch):
    from realhf_tpu.obs import tracing
    # the traced steps' captures came after it: the newest WITH
    # set-up spans is the one
    monkeypatch.setattr(tracing, "captures",
                        lambda: [setup_capture(), steps_capture()])
    assert reader(name).read(dict(chips=1)) == pytest.approx(NINE[name])


def test_the_parts_add_up_to_the_capture_before_the_step(monkeypatch):
    from realhf_tpu.obs import setup, tracing
    monkeypatch.setattr(tracing, "captures", lambda: [setup_capture()])
    read = {name: reader(name).read({}) for name in NINE}
    assert read["setup.import_s"] + read["setup.data_s"] \
        + read["setup.weights_s"] + read["setup.unattributed_s"] \
        == pytest.approx(112.0 - 100.0)
    # in a user's run nothing stands before the first MFC: no head
    capture = setup_capture()
    capture.spans = [s for s in capture.spans if s["span_id"] != "h0"]
    for s in capture.spans:
        if s["name"] == "step":
            s["start"] = 116.0
    split = setup.split(capture)
    assert split["program_s"] == pytest.approx(30.0)
    assert split["unattributed_s"] == pytest.approx(6.0)
    assert (split["programs"], split["cache_hits"], split["roles"],
            split["weight_bytes"]) == (3, 2, 2, 2000)
    assert split["cache_load_s"] == pytest.approx(1.9)


@pytest.mark.parametrize("name", NINE)
@pytest.mark.parametrize("captures", ["none", "steps_only"])
def test_reader_gives_nothing_without_a_set_up_capture(
        name, captures, monkeypatch):
    """A commit before this one records no set-up: the metric is left
    out of the line and nothing raises."""
    from realhf_tpu.obs import tracing
    monkeypatch.setattr(
        tracing, "captures",
        lambda: [] if captures == "none" else [steps_capture()])
    assert reader(name).read(dict(chips=1)) is None


def test_the_nine_stand_appended_and_name_every_cell():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = manifest["per_layer"][FIRST:FIRST + len(NINE)]
    assert [e["name"] for e in entries] == list(NINE)
    ten = [w["name"] for w in manifest["workloads"]][:10]
    assert len(ten) == 10
    for e in entries:
        assert (e["moves"], e["better"], e["source"]) == (
            "setup_s", "lower", "program_span")
        assert e["unit"] == ("count" if e["name"] == "setup.cache_misses"
                             else "s")
        assert e["layer"] in ("entry", "engine")
        assert e["workloads"][:10] == ten
    # nothing that was there is changed: the two outside readings stay
    before = [e["name"] for e in manifest["per_layer"][:FIRST]]
    assert {"entry.load_s", "engine.compile_s"} <= set(before)
    assert not set(before) & set(NINE)


def test_a_tiny_cell_reports_all_nine(tmp_path):
    """``tiny.sft`` whole through ``run_cell`` with ``--trace 2``, the
    nine entries of ``BENCHMARK.json`` beside the tests' own: what the
    program says of its set-up against what the harness clocks from
    outside."""
    from realhf_tpu.obs import tracing
    with open(MANIFEST) as f:
        tiny = json.load(f)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    tiny["per_layer"] += [
        {k: v for k, v in real[n].items() if k != "workloads"}
        for n in NINE]
    manifest = os.path.join(str(tmp_path), "manifest.json")
    with open(manifest, "w") as f:
        json.dump(tiny, f)
    out = run.run_cell(run.load_cell(manifest, "tiny.sft"),
                       seed=2 ** 31 + 50, seconds=0.3, trace=2,
                       work=str(tmp_path), peaks=PEAKS,
                       expect_kernels=False)
    check_line(out, trace=2)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NINE) <= set(m)
    assert out["metrics"]["setup.cache_misses"]["unit"] == "count"
    assert out["metrics"]["setup.weights_s"]["unit"] == "s"

    # the set-up capture, then the thrown-away profile, the profiled
    # steps and the synced ones: four, of the eight the tracer keeps
    captures = tracing.captures()[-4:]
    assert [bool(c.named("setup:")) for c in captures] == [
        True, False, False, False]
    # the inside reading of entry.load_s, and of setup_s less the
    # harness's own
    assert m["setup.import_s"] + m["setup.data_s"] + m["setup.weights_s"] \
        + m["setup.unattributed_s"] == pytest.approx(m["entry.load_s"],
                                                     abs=0.05)
    assert m["setup.unattributed_s"] <= 0.05 * m["setup.program_s"]
    assert m["setup.trace_lower_s"] + m["setup.facts_s"] \
        < m["setup.first_step_s"] < m["setup.program_s"] < m["setup_s"]
    assert captures[0].counter("engine_compile_secs_total") \
        == pytest.approx(m["engine.compile_s"], rel=0.01)
    # the harness's reference comparison ran inside the warm-up step,
    # before the program's first MFC: no span of it in the program's
    # record, and its seconds no part of program_s
    [step] = captures[0].named("step")
    [mfc] = captures[0].children(step)
    assert not captures[0].named("engine:logprobs")
    assert m["setup.program_s"] == pytest.approx(
        captures[0].end - captures[0].start
        - (mfc["start"] - step["start"]))
    assert m["setup.first_step_s"] == pytest.approx(
        step["end"] - mfc["start"])
