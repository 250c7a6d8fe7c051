"""The fourteen metrics that read what the PROGRAM says of its parts
(``benchmark/program_parts.py``, ``layer_metrics/train.*_s.py``,
``gen.*_s.py``, ``engine.program_gb.py``), on the CPU: a tiny cell a
kind (``tests/benchmark/parts/manifest.json``) whole through
``run_cell --trace 2``. The CPU's profile holds no device plane, so the
tests lay one under the reducer: every instruction the capture's
``programs`` name becomes one device operation of a known length, in
its program's module, inside the first traced step. The readers then
have an exact answer."""

import json
import math
import os

import pytest
from tiny_cells import PEAKS, check_line

from benchmark import program_capture, program_parts, run, trace_reduce

MANIFEST = os.path.join(run.ROOT, "tests", "benchmark", "parts",
                        "manifest.json")
NEW = ("train.attn_s", "train.attn_proj_s", "train.mlp_s",
       "train.experts_s", "train.conv_s", "train.head_s", "train.accum_s",
       "train.unscoped_s", "train.collective_s", "gen.prefill_s",
       "gen.decode_attn_s", "gen.decode_dense_s", "gen.sample_s",
       "engine.program_gb")
OP_SECS = 1e-4  # of every laid operation
#: these tests read scopes out of compiled programs (tests/conftest.py)
pytestmark = pytest.mark.usefixtures("programs_compiled_by_this_tree")


def real_entries():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


@pytest.fixture
def laid_device(monkeypatch):
    """``trace_reduce.read_xplane`` as it is, plus one device whose
    operations are the instructions of the profiled capture's
    programs; the paths it was asked to read, in order."""
    read, asked = trace_reduce.read_xplane, []

    def with_a_device(path):
        asked.append(path)
        trace = read(path)
        got = program_capture.last(program_capture.profiled)
        steps = sorted((s, e) for n, s, e in trace["spans"]
                       if n == "bench:step")
        if trace["devices"] or got is None or not steps:
            return trace
        at, ops, modules = steps[0][0], [], []
        for facts in got.programs.values():
            start = at
            for name, (_, _, opcode, _, _) in facts["ops"].items():
                ops.append((f"%{name} = f32[8]{{0}} {opcode}(%x)",
                            at, at + OP_SECS))
                at += OP_SECS
            modules.append((facts["module"] + "(7)", start, at))
        assert at < steps[0][1], "the laid operations pass the step"
        trace["devices"] = {0: dict(ops=ops, modules=modules)}
        return trace

    monkeypatch.setattr(trace_reduce, "read_xplane", with_a_device)
    program_parts._CACHE.clear()
    yield asked
    program_parts._CACHE.clear()


def expected(programs, module_prefix, where, steps):
    return sum(OP_SECS for f in programs.values()
               if f["module"].startswith(module_prefix)
               for op in f["ops"].values() if where(*op[:4])) / steps


@pytest.mark.parametrize("name", ["tiny.sft", "tiny.grpo"])
def test_cell_reports_the_parts_of_its_programs(name, tmp_path,
                                                laid_device):
    cell = run.load_cell(MANIFEST, name)
    grpo = name == "tiny.grpo"
    assert set(cell["readers"]) == {"mfc.train_s"} | {
        n for n in NEW if grpo or not n.startswith("gen.")}
    out = run.run_cell(cell, seed=2 ** 31 + 77, seconds=0.3, trace=2,
                       work=str(tmp_path), peaks=PEAKS,
                       expect_kernels=False)
    check_line(out, trace=2)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # every metric its `workloads` name reports a finite number
    for metric in cell["readers"]:
        assert math.isfinite(m[metric]), metric
    assert all(out["metrics"][n]["unit"] == real_entries()[n]["unit"]
               for n in NEW if n in m)

    got = program_capture.last(program_capture.profiled)
    steps = len(got.named("step"))
    assert steps == run.TRACE_STEPS
    # the capture explains every engine program that ran in it, and
    # the trace file was parsed once for run.py and once for all of
    # the readers
    spans = got.named("engine:")
    assert spans and all(
        s["attributes"]["program_fingerprint"] in got.programs
        and got.programs[s["attributes"]["program_fingerprint"]]["module"]
        == s["attributes"]["program"] for s in spans)
    assert len(laid_device) == 2 and len(set(laid_device)) == 1
    with open(os.path.join(os.path.dirname(laid_device[0]),
                           "programs.json")) as f:
        assert json.load(f).keys() == got.programs.keys()
    modules = {f["module"] for f in got.programs.values()}
    assert "jit_train_step" in modules or "jit_train_seq" in modules
    assert ("jit_generate" in modules) == grpo

    def train(*parts):
        return expected(got.programs, "jit_train_",
                        lambda part, *_: (part or "").split("/")[0]
                        in parts, steps)

    def same(metric, want):
        assert m[metric] == pytest.approx(want) and want > 0, metric

    same("train.attn_s", train("attn"))
    same("train.attn_proj_s", train("attn_proj"))
    same("train.mlp_s", train("mlp"))
    same("train.head_s", train("vocab_head", "loss"))
    same("train.accum_s", train("grad_accum"))
    assert m["train.experts_s"] == m["train.conv_s"] == 0.0  # a dense model
    assert m["train.collective_s"] == 0.0                    # on one chip
    # the parts, the three named ones without a metric and what no
    # part claims add up to the train program
    whole = expected(got.programs, "jit_train_", lambda *_: True, steps)
    named = sum(m[n] for n in NEW[:8])
    assert named + train("optimizer", "embed", "layers") \
        == pytest.approx(whole)
    assert m["engine.program_gb"] == max(
        f["memory"]["argument_size_in_bytes"]
        + f["memory"]["temp_size_in_bytes"]
        for f in got.programs.values()) / 1e9 > 0
    if grpo:
        def gen(where):
            return expected(got.programs, "jit_generate", where, steps)
        same("gen.prefill_s",
             gen(lambda part, p, o, phase: phase == "prefill"))
        same("gen.decode_attn_s", gen(
            lambda part, p, o, phase: (phase, part) == ("decode", "attn")))
        same("gen.decode_dense_s", gen(
            lambda part, p, o, phase: phase == "decode"
            and part in ("attn_proj", "mlp", "layers")))
        same("gen.sample_s", gen(
            lambda part, p, o, phase: phase == "sample" or (
                phase, part) == ("decode", "vocab_head")))

    # a capture without `programs` (the parent commit under these
    # files): every reader reads nothing, none raises
    record = dict(chips=1)
    got.programs = {}
    program_parts._CACHE.clear()
    for metric in NEW:
        assert cell["readers"].get(metric) is None \
            or cell["readers"][metric].read(record) is None


@pytest.mark.parametrize("name", NEW)
def test_reader(name):
    """Every reader has its docstring and file, is declared as the
    issue says, and reads nothing before any capture."""
    from realhf_tpu.obs import tracing
    tracing.reset_default()
    program_parts._CACHE.clear()
    entry = real_entries()[name]
    assert entry["moves"] == "tokens_per_s" and entry["better"] == "lower"
    assert entry["workloads"], "a later cell must not inherit it"
    assert (entry["unit"], entry["source"]) == (
        ("GB", "program_counter") if name == "engine.program_gb"
        else ("s/step", "device_trace"))
    with open(MANIFEST) as f:
        manifest = json.load(f)
    reader = run.load_module(run.find(manifest, "layer_metrics",
                                      name + ".py"))
    assert len(reader.__doc__) > 200 and callable(reader.read)
    assert reader.read(dict(chips=1)) is None


def test_manifest_gains_the_fourteen_at_its_end_and_nothing_else():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    assert tuple(names[-14:]) == NEW and len(names) == 34
    cells = [w["name"] for w in manifest["workloads"]]
    by = real_entries()
    assert by["train.attn_s"]["workloads"] == cells
    assert by["train.experts_s"]["workloads"] == cells[3:]
    assert by["train.conv_s"]["workloads"] == [cells[4]]
    assert by["train.collective_s"]["workloads"] == [cells[2]]
    assert by["gen.sample_s"]["workloads"] == [cells[0], cells[2]]


def test_two_texts_under_one_module_name_are_not_guessed():
    a = dict(module="jit_values", ops={}, memory={})
    b = dict(module="jit_values", ops={"x": []}, memory={})
    c = dict(module="jit_train_step", ops={}, memory={})
    assert program_parts.facts_by_module(dict(a=a, b=b, c=c)) == {
        "jit_train_step": c}
