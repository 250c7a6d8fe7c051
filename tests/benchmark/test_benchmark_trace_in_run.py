"""``--trace 2``: the run of ``--trace 0``, and only when its window has
closed a traced stretch in the same process, started and stopped
through the program's own control. The tests' tiny cells whole through
``run_cell`` on the CPU, with the per-layer metrics that read the
program's capture."""

import glob
import json
import os

import pytest
from tiny_cells import MANIFEST, PEAKS, check_line

from benchmark import observe, run

NEW = ("interface.host_s", "reshard.put_gbps", "reshard.prep_s")


def go(name, tmp_path, seconds=0.3):
    """One ``--trace 2`` run of a tiny cell, as ``tiny_cells.go`` makes
    the others, which reports beside the tests' own per-layer metrics
    the ``NEW`` ones of ``BENCHMARK.json``, every cell each (their
    readers say where there is nothing to read)."""
    with open(MANIFEST) as f:
        tiny = json.load(f)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    tiny["per_layer"] += [
        {k: v for k, v in real[n].items() if k != "workloads"}
        for n in NEW]
    manifest = os.path.join(str(tmp_path), "manifest.json")
    with open(manifest, "w") as f:
        json.dump(tiny, f)
    cell = run.load_cell(manifest, name)
    return run.run_cell(cell, seed=2 ** 31 + 77, seconds=seconds,
                        trace=2, work=str(tmp_path), peaks=PEAKS,
                        expect_kernels=False)


@pytest.fixture
def tracing_state(monkeypatch):
    """What the program's tracer said each time the runner took a
    step: (enabled, synced), in order. Patched in under the harness's
    own wrapper, so the harness sees nothing of it."""
    from realhf_tpu.obs import tracing
    from realhf_tpu.system.inline import InlineRunner
    seen = []
    orig = InlineRunner.run_step

    def run_step(runner, batch):
        seen.append((tracing.enabled(), tracing.default_tracer().sync))
        return orig(runner, batch)
    monkeypatch.setattr(InlineRunner, "run_step", run_step)
    return seen


def phases(capsys):
    out = {}
    for line in capsys.readouterr().out.splitlines():
        if line.startswith('{"phase"'):
            d = json.loads(line)
            out.setdefault(d["phase"], []).append(d)
    return out


def host_spans(work):
    """Names of the events on the host planes of the one trace."""
    from jax.profiler import ProfileData
    [path] = glob.glob(os.path.join(str(work), "trace", "**",
                                    "*.xplane.pb"), recursive=True)
    return {e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


@pytest.mark.parametrize("cell", ["tiny.sft", "tiny.grpo",
                                  "tiny.grpo-realloc"])
def test_cell_measured_then_traced(cell, tmp_path, capsys, tracing_state):
    from realhf_tpu.obs import tracing

    out = go(cell, tmp_path)
    check_line(out, trace=2)
    assert {"tokens_per_s", "step_max_s", "setup_s"} < set(out["metrics"])
    said = phases(capsys)
    m = out["metrics"]
    grpo, realloc = cell != "tiny.sft", cell == "tiny.grpo-realloc"

    # -- the window is the untraced run's, the traced steps follow it
    n = said["window"][0]["steps"]
    assert out["attempted"] == n >= 1
    traced = said["trace"][0]["traced_steps"]
    assert traced == [n + 1, n + 2]  # step 0 is the warm-up
    assert said["trace"][0]["synced_steps"] == [n + 3, n + 4]
    assert [s["index"] for s in said["step"]] == list(range(n + 5))
    # profiled steps block where the outside clocks do; only the steps
    # after them, profiler off, wait after every engine program
    assert tracing_state == [(False, False)] * (n + 1) \
        + [(True, observe.MFC_SYNC)] * 2 + [(True, True)] * 2
    assert not tracing.enabled()
    assert len(said["window"][0]["step_secs"]) == n
    assert m["step_max_s"]["value"] == max(said["window"][0]["step_secs"])
    assert len(said["mfcs"][0]["steps"]) == run.TRACE_STEPS

    # -- the profile holds the program's spans under their own names
    names = host_spans(tmp_path)
    assert "step" in names and "bench:step" in names
    mfcs = {"trainDefault"} if not grpo else {
        "actor_gen", "ref_inf", "rew_inf", "actor_train"}
    for mfc in mfcs:
        assert {f"mfc:{mfc}", f"compute:{mfc}"} <= names
    assert "engine:train" in names
    assert ("realloc" in names and "realloc:put" in names) == realloc
    assert not os.path.exists(str(tmp_path / "trace") + ".first")

    # -- and so does the capture, each span the child of its cause
    # (the profiler's first start and stop, thrown away, is a capture too)
    _, profiled, capture = tracing.captures()[-3:]
    assert profiled.sync == observe.MFC_SYNC and profiled.profile_dir
    assert capture is tracing.last_capture()
    assert capture.sync is True and capture.profile_dir is None
    assert len(profiled.named("step")) == run.TRACE_STEPS
    assert len(capture.named("step")) == run.TRACE_STEPS
    for step in capture.named("step"):
        below = capture.children(step)
        assert {s["name"] for s in below} == {f"mfc:{n}" for n in mfcs}
        for mfc in below:
            kinds = {s["name"].split(":")[0]
                     for s in capture.descendants(mfc)}
            assert {"compute", "engine"} <= kinds
            assert ("realloc" in kinds) == (
                realloc and mfc["name"] == "mfc:actor_gen")
    assert all(not s["attributes"]["compiled"]
               for s in capture.named("engine:"))

    # -- each new reader: a number where the cell has the layer
    assert m["interface.host_s"]["value"] > 0
    assert m["interface.host_s"]["unit"] == "s/step"
    if realloc:
        assert m["reshard.put_gbps"]["value"] > 0
        assert 0 <= m["reshard.prep_s"]["value"] < m["reshard.s"]["value"]
        # the reshard's readers take the steps `reshard.s` is of
        moved = profiled.counter("realloc_bytes_total", role="actor")
        puts = profiled.named("realloc:put")
        assert moved == sum(s["attributes"]["bytes"] for s in puts) > 0
        assert m["reshard.put_gbps"]["value"] * sum(
            s["end"] - s["start"] for s in puts) == pytest.approx(
                moved / 1e9)
    else:
        assert "reshard.put_gbps" not in m and "reshard.prep_s" not in m
    # an MFC holds its engine programs: host time is what is left
    mfc = "actor_train" if grpo else "trainDefault"
    for span in capture.named(f"mfc:{mfc}"):
        programs = [s for s in capture.descendants(span)
                    if s["name"] == "engine:train"]
        assert programs and sum(
            s["end"] - s["start"] for s in programs) \
            <= span["end"] - span["start"]


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_without_a_capture(name):
    """As under a program that lacks the control, or before any traced
    stretch: the metric is left out, nothing raises."""
    from realhf_tpu.obs import tracing
    tracing.reset_default()
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert manifest["trace_in_run"] is True
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert entry["source"] == "program_span"
    reader = run.load_module(run.find(manifest, "layer_metrics",
                                      name + ".py"))
    assert reader.read({}) is None


class Unfinished:
    def __init__(self):
        self.asked = False

    def block_until_ready(self):
        self.asked = True
        return self


class Host:
    models, replicas = {}, {}


def test_clocks_block_only_in_the_traced_stretch(monkeypatch, tmp_path):
    """With ``after_window`` the MFC clock is the untraced run's until
    the window has closed, and the traced run's from then on."""
    from realhf_tpu.system.model_host import ModelHost
    monkeypatch.setattr(ModelHost, "execute",
                        lambda host, node_name, inp: inp)
    obs = observe.Observer(1.0, watch=None, trace_dir=str(tmp_path / "t"),
                           after_window=True)
    obs.install()
    try:
        before, after = Unfinished(), Unfinished()
        ModelHost.execute(Host(), "ref_inf", dict(out=before))
        assert not obs.blocking and not before.asked
        obs.window_last = 6  # the window has closed with step 6
        ModelHost.execute(Host(), "ref_inf", dict(out=after))
        assert obs.blocking and after.asked
    finally:
        obs.uninstall()
