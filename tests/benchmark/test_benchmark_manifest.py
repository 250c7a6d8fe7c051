"""The manifest is whole: every name in ``BENCHMARK.json`` (and in the
tests' own tiny manifest beside this file) leads to a file, and the
contract's limits on names, units, bounds and four-chip cells hold."""

import json
import os
import re

import pytest

from benchmark import run

ROOT = run.ROOT
MANIFESTS = {
    "benchmark": os.path.join(ROOT, "BENCHMARK.json"),
    "tests": os.path.join(ROOT, "tests", "benchmark", "manifest.json"),
}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}


def load(which):
    with open(MANIFESTS[which]) as f:
        return json.load(f)


def entries(group):
    return [pytest.param(which, e["name"], id=f"{which}:{e['name']}")
            for which in MANIFESTS for e in load(which)[group]]


def entry(which, group, name):
    return next(e for e in load(which)[group] if e["name"] == name)


@pytest.mark.parametrize("which", MANIFESTS)
def test_top_level_keys_and_limits(which):
    m = load(which)
    assert sorted(set(m) - {"trace_in_run"}) == sorted([
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"])
    assert m.get("trace_in_run", True) is True  # the key is only ever true
    assert os.path.getsize(MANIFESTS[which]) <= 64 * 1024
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["workloads"]) <= 24 and 1 <= len(m["configs"]) <= 24
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[group]]
        assert len(names) == len(set(names)), group
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)
    assert "setup_s" in [e["name"] for e in m["end_to_end"]]
    for path in m["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    for word in m["command"]:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in m["paths"])


@pytest.mark.parametrize("which,name", entries("workloads"))
def test_cell_files_exist(which, name):
    """The harness finds the cell's configuration, traffic, kind and
    every reader by name, as a run would."""
    m = load(which)
    w = entry(which, "workloads", name)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    cell = run.load_cell(MANIFESTS[which], name)
    family = cell["family"]
    for attr in ("shapes", "logprobs", "n_params", "forward_flops",
                 "decode_bytes"):
        assert callable(getattr(family, attr)), attr
    assert 0 < family.TOLERANCE < 0.1
    assert family.n_params(cell["hf"]) > 0
    for attr in ("EXPERIMENT", "MFCS", "ON_POLICY", "build",
                 "programs", "reference_engines", "work"):
        assert hasattr(cell["kind"], attr), attr
    reported = run.metrics_of(m, "per_layer", name)
    assert reported and set(cell["readers"]) == {
        e["name"] for e in reported}
    for reader in cell["readers"].values():
        assert callable(reader.read) and reader.__doc__
    layout = cell["meta"].get("layout", {})
    assert layout.get("chips", 1) == w["chips"]
    # every cell reports setup_s, another end-to-end metric and the
    # end-to-end metric that each of its per-layer metrics moves
    e2e = {e["name"] for e in run.metrics_of(m, "end_to_end", name)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert {e["moves"] for e in reported} <= e2e


@pytest.mark.parametrize("which,name", entries("configs"))
def test_config_entry(which, name):
    c = entry(which, "configs", name)
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"])
    m = load(which)
    assert any(c["file"].startswith(p + "/") for p in m["paths"])
    with open(os.path.join(ROOT, c["file"])) as f:
        raw = json.load(f)
    # what the manifest says was reduced is what the file says, and a
    # width is never among it
    assert sorted(c["reduced"]) == sorted(raw.get("reduced", {}))
    for key in c["reduced"]:
        assert NAME.match(key)
        assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size"
                             r"|head_dim|experts_per_tok)$", key)
        assert raw[key] == raw["reduced"][key]["run"]


@pytest.mark.parametrize("which,name", entries("end_to_end"))
def test_end_to_end_metric(which, name):
    e = entry(which, "end_to_end", name)
    assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert NAME.match(e["name"]) and UNIT.match(e["unit"])
    assert e["better"] in ("lower", "higher")
    assert e["source"] in ("host_clock", "device_trace")
    assert 0.01 <= e["bound"] <= 0.1


@pytest.mark.parametrize("which,name", entries("per_layer"))
def test_per_layer_metric(which, name):
    m = load(which)
    e = entry(which, "per_layer", name)
    assert set(e) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert NAME.match(e["name"]) and UNIT.match(e["unit"])
    assert e["better"] in ("lower", "higher") and e["source"] in SOURCES
    assert e["moves"] in [x["name"] for x in m["end_to_end"]]
    assert 1 <= len(e["layer"]) <= 200
    cells = [w["name"] for w in m["workloads"]]
    assert set(e.get("workloads", cells)) <= set(cells)
    assert os.path.exists(run.find(m, "layer_metrics", name + ".py"))


def test_files_under_paths_are_named_from_allowed_characters():
    m = load("benchmark")
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in m["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs
                       if d not in ("__pycache__", ".cache")]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel), rel
