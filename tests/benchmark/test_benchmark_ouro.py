"""The ``ouro`` family (ByteDance's Ouro-2.6B, a LOOPED model) in the
benchmark, on the CPU at toy widths: the eleventh cell's entries and
configuration file against the issue and the catalog row, its
arithmetic at published widths (parameters, the FLOPs of T x N layer
applications and T heads, a cache T times as deep), a tiny cell (its
own manifest and configuration under ``tests/benchmark/ouro/``, the
tests' ``tiny-sft`` and ``tiny-grpo`` traffic) whole through
``run_cell`` by ``sft`` and by ``grpo`` (generation, inference and
training agree on pass T: the first minibatch's importance weight), and
the three readers the family brings.

Nothing here says where in its lists an entry stands or how long they
are (``in``, never ``[-1]`` or ``== n``): a later PR appends to them.
"""

import json
import os

import numpy as np
import pytest
from tiny_cells import PEAKS, check_line

from benchmark import generate, reference, run

MANIFEST = os.path.join(run.ROOT, "tests", "benchmark", "ouro",
                        "manifest.json")
CELL = "tiny-ouro.sft"
REAL = "ouro-2.6b-l6.sft-4k-x4"
CONFIG = "ouro-2.6b-l6"
#: the accepted per-layer lists this PR appended its cell to
APPENDED = ("train.attn_s", "train.attn_proj_s", "train.mlp_s",
            "train.head_s", "train.accum_s", "train.unscoped_s",
            "engine.program_gb", "setup.program_s", "setup.import_s",
            "setup.data_s", "setup.weights_s", "setup.trace_lower_s",
            "setup.cache_misses", "setup.facts_s", "setup.first_step_s",
            "setup.unattributed_s")
NEW = ("train.exit_s", "train.loop_s", "loop.expected_exit_pass")
#: parameters the checkpoint holds (ISSUE 53's arithmetic)
PARAMS = 509_661_185
REDUCED = ["num_hidden_layers", "layer_types", "max_window_layers"]


def go(trace, tmp_path, cell=CELL):
    cell = run.load_cell(MANIFEST, cell)
    return cell, run.run_cell(cell, seed=2 ** 31 + 77, seconds=0.3,
                              trace=trace, work=str(tmp_path),
                              peaks=PEAKS, expect_kernels=False)


def real_manifest():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def real_cell():
    return run.load_cell(os.path.join(run.ROOT, "BENCHMARK.json"), REAL)


def test_real_manifest_names_the_cell_as_the_issue_does():
    manifest = real_manifest()
    cell = real_cell()
    assert cell["chips"] == 1 and cell["meta"]["family"] == "ouro"
    assert cell["config"]["name"] == CONFIG
    assert cell["config"]["reduced"] == REDUCED \
        == list(cell["meta"]["reduced"])
    assert cell["config"]["file"] == f"benchmark/configs/{CONFIG}.json"
    hf, t = cell["hf"], cell["traffic"]
    assert (hf["num_hidden_layers"], hf["total_ut_steps"],
            hf["early_exit_threshold"], hf["vocab_size"]) == (6, 4, 1, 49152)
    entry = next(w for w in manifest["workloads"] if w["name"] == REAL)
    assert entry["traffic"] == "sft-4k-x4"
    assert "22%" in entry["why"] and "3.4%" in entry["why"]  # the heads
    assert (t["kind"], t["docs_per_step"], t["doc_len"], t["prompt_len"],
            t["docs_per_row"], t["lr"], t["steps_of_data"]) == (
        "sft", 4, 4096, 512, 1, 1e-4, 8)
    assert cell["meta"]["layout"] == {"chips": 1, "roles": "d1t1"}
    assert set(APPENDED) | set(NEW) | {
        "train.mfu", "mfc.train_s", "interface.host_s",
        "device.idle_share"} <= set(cell["readers"])
    # what reads another model's mechanisms stays off this cell
    assert not {"moe.pairs_per_s", "moe.held_pairs_per_s", "mfc.gen_s",
                "gen.hbm_share", "train.conv_s", "train.collective_s",
                "train.experts_s", "flash.mxu_share", "train.delta_s",
                "train.ssm_s", "sparse.index_s"} & set(cell["readers"])
    by = {m["name"]: m for m in manifest["per_layer"]}
    for name in APPENDED:
        assert REAL in by[name]["workloads"], name
    for name, unit, source in (
            ("train.exit_s", "s/step", "device_trace"),
            ("train.loop_s", "s/step", "device_trace"),
            ("loop.expected_exit_pass", "passes", "program_counter")):
        assert REAL in by[name]["workloads"]
        assert (by[name]["unit"], by[name]["layer"], by[name]["moves"],
                by[name]["source"]) == (unit, "model", "tokens_per_s",
                                        source)
    # eleven cells, one of them on four chips
    assert len({w["name"] for w in manifest["workloads"]}) >= 11
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert "mistral-7b-v0.3-l4.grpo-realloc" in four and REAL not in four
    assert 0 <= hf["eos_token_id"] < hf["vocab_size"]


def test_every_width_is_the_published_one():
    """The configuration file against the catalog row the driver drew:
    every key of the row's ``config`` is in the file under the same
    name with the same value, but the three depth keys the file lists
    as reduced, and those say what was published."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    cell = real_cell()
    hf, meta = cell["hf"], cell["meta"]
    assert meta["source"] == row["source_url"] == cell["config"]["source"]
    for key, published in row["config"].items():
        if key in meta["reduced"]:
            assert meta["reduced"][key]["published"] == published, key
            assert meta["reduced"][key]["run"] == hf[key] != published
        else:
            assert hf[key] == published, key
    assert sorted(meta["reduced"]) == sorted(REDUCED)
    assert hf["layer_types"] == ["full_attention"] * 6
    # no width is cut, and the loop is as published
    assert (hf["hidden_size"], hf["num_attention_heads"],
            hf["num_key_value_heads"], hf["head_dim"],
            hf["intermediate_size"], hf["vocab_size"], hf["total_ut_steps"],
            hf["rms_norm_eps"], hf["rope_theta"]) == (
        2048, 16, 16, 128, 5632, 49152, 4, 1e-6, 1000000)
    for key in ("the four norms, their names and order",
                "the final norm is fed back", "the exit gate",
                "the cache's indexing", "tensor names", "beta",
                "what is NOT run", "initializer_range", "eos_token_id"):
        assert key in meta["assumed"], key
    assert set(hf) - set(row["config"]) == {"initializer_range",
                                            "eos_token_id"}
    assert "20 bytes a parameter" in meta["deployment"]
    assert "10.19 GB" in meta["deployment"]


def test_arithmetic_at_published_widths():
    """The numbers the issue works the cell out from: 509,661,185
    parameters (10.19 GB at 20 bytes, 64% of the chip), 3.67 GFLOP a
    token forward (24 layer applications 2.47, four heads 0.81,
    attention at 4096 causal 0.40), the heads 22% here and 3.4% in the
    whole model; a cache T x N layers deep and weights read T times a
    decoded token."""
    cell = real_cell()
    family, hf = cell["family"], cell["hf"]
    assert family.n_params(hf) == PARAMS
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert 6 * layer + 2 * 49152 * 2048 + 2048 + 2049 == PARAMS
    assert round(PARAMS * 20 / 1e9, 2) == 10.19
    assert round(100 * PARAMS * 20 / 16e9) == 64
    with open(os.path.join(run.ROOT, cell["config"]["file"])) as f:
        published = {k: v["published"]
                     for k, v in json.load(f)["reduced"].items()}
    whole = dict(hf, **published)
    assert abs(family.n_params(whole) / 2.67e9 - 1) < 0.01
    assert round(family.n_params(dict(hf, num_hidden_layers=8)) * 20 / 1e9,
                 2) == 12.25
    seqlens = [4096] * 4
    tokens = sum(seqlens)
    flops = family.forward_flops(hf, seqlens) / tokens
    assert round(flops / 1e9, 2) == 3.67
    matrices = 24 * 2 * (layer - 4 * 2048)
    assert round(matrices / 1e9, 2) == 2.47
    heads = 4 * 2 * 2048 * 49152
    assert round(heads / 1e9, 2) == 0.81
    attention = 24 * 2 * (4096 * 4097 // 2) * 16 * 2 * 128 / 4096
    assert round(attention / 1e9, 2) == 0.40
    assert abs(matrices + heads + attention + 4 * 2 * 2048 - flops) < 1
    assert round(100 * family.head_share(hf, seqlens)) == 22
    assert round(100 * family.head_share(whole, seqlens), 1) == 3.4
    assert 23.7 < family.forward_flops(whole, seqlens) / tokens / 1e9 < 23.8
    work = cell["kind"].work(family, hf, cell["meta"], cell["traffic"])
    assert work["tokens_per_step"] == 16384
    assert round(work["train_flops"] / tokens / 1e9, 1) == 11.0
    # at cell 2's 40.8% of the peak: the issue's 2.3 s a step
    assert round(work["train_flops"] / (0.408 * 197e12), 1) == 2.2
    # a cache of T x N layers, weights read once a pass
    assert family.kv_bytes_per_token(hf) == 2 * 24 * 16 * 128 * 2 == 196608
    assert family.kv_bytes_per_token(whole) == 8 * 196608
    read = 4 * 6 * layer + 49152 * 2048 + 2048
    assert family.decode_bytes(hf, 4, 1024, 1) == 2 * read \
        + 4 * 1024 * 196608
    names = family.shapes(hf)
    assert sum(int(np.prod(s)) for s, _ in names.values()) == PARAMS
    assert sum("layernorm" in n for n in names) == 4
    assert names["model.early_exit_gate.weight"] == ((1, 2048), "matrix")
    assert names["model.early_exit_gate.bias"] == ((1,), "bias")
    assert names["model.layers.{}.input_layernorm_2.weight"] == (
        (6, 2048), "norm")


def test_cell_end_to_end(tmp_path):
    _, out = go(0, tmp_path)
    check_line(out, trace=False)


def test_grpo_runs_the_loop_through_every_mfc(tmp_path):
    """Generation (prefill and decode steps through the T x N-deep
    cache), the reference's and the reward model's inference and the
    actor's train step through quickstart: ``correct`` holds the first
    minibatch's importance weight within 0.05 of 1, so generation,
    inference and training agree on pass T."""
    cell, out = go(0, tmp_path, "tiny-ouro.grpo")
    check_line(out, trace=False)
    assert cell["kind"].ON_POLICY


def test_cell_measured_then_traced(tmp_path):
    from realhf_tpu.obs import tracing
    cell, out = go(2, tmp_path)
    check_line(out, trace=True)
    m = out["metrics"]
    assert {"mfc.train_s", "train.mfu", "interface.host_s",
            "engine.program_gb", "tokens_per_s",
            "loop.expected_exit_pass"} <= set(m)
    # every lambda near 0.5 at the harness's weights: 1/2 + 2/4 + 3/8 + 4/8
    assert abs(m["loop.expected_exit_pass"]["value"] - 1.875) < 0.1
    # the CPU's trace holds no device operation: the parts' readers,
    # the two new ones among them, leave their metric out of the line
    # without raising
    assert {"train.exit_s", "train.loop_s", "train.head_s"} <= set(
        cell["readers"])
    assert not {"train.exit_s", "train.loop_s", "train.head_s"} & set(m)
    profiled, synced = tracing.captures()[-2:]
    assert profiled.profile_dir is not None and synced.sync is True
    t = cell["traffic"]
    tokens = t["doc_len"] * t["docs_per_step"]
    for capture in (profiled, synced):
        trains = capture.named("engine:train")
        assert len(trains) == run.TRACE_STEPS
        for span in trains:
            a = span["attributes"]
            assert (a["passes"], a["kv_layers"], a["post_norm"],
                    a["exit_gate"]) == (4, 8, True, True)
            assert 1.5 < a["expected_exit_pass"] < 2.3
            assert 0.9 < a["exit_entropy"] < 1.4
            assert abs(sum(a[f"exit_p{i}"] for i in range(1, 5)) - 1) < 1e-4
            assert all(4.0 < a[f"nll_pass{i}"] < 5.5 for i in range(1, 5))
        assert capture.counter("loop_token_passes_total", role="default") \
            == run.TRACE_STEPS * tokens * 4
    # the program names the parts in the facts the capture carries:
    # what the readers will find on the chip
    parts = {row[0] for facts in profiled.programs.values()
             for row in facts["ops"].values()}
    assert {"layers", "layers/loop", "exit", "attn_proj", "attn", "mlp",
            "vocab_head"} <= parts
    assert not {"experts", "conv", "delta", "ssm"} & parts


def _capture(counters, profile_dir="x", programs=None, attributes=None):
    from realhf_tpu.obs import tracing
    spans = [dict(name="step", start=0.0, end=1.0, span_id="a",
                  parent_id=None, trace_id="t", thread=0, attributes={})]
    for i, attrs in enumerate(attributes or ()):
        spans.append(dict(name="engine:train", start=0.1, end=0.9,
                          span_id=f"e{i}", parent_id="a", trace_id="t",
                          thread=0, attributes=attrs))
    capture = tracing.Capture(
        spans=spans, counters=counters, start=0.0, end=1.0,
        sync=("compute:",), profile_dir=profile_dir)
    capture.programs = programs or {}
    return capture


def test_the_three_readers_read_their_parts_and_the_span(monkeypatch,
                                                         tmp_path):
    """Against a constructed trace: operations of the train program
    under ``exit`` in three passes are ``train.exit_s``; those under
    ``layers`` and ``layers/loop`` together ``train.loop_s``; those of
    another part, of another program and of an operation the text does
    not name count for neither. ``loop.expected_exit_pass`` is the
    median of the ``engine:train`` spans' attribute. Nothing where the
    capture has no ``programs`` or no such attribute (the parent commit
    under these files); 0 where the program has no such part."""
    from benchmark import program_parts, trace_reduce
    from realhf_tpu.obs import tracing
    cell = run.load_cell(MANIFEST, CELL)
    exit_s, loop_s, expected = (cell["readers"][n] for n in NEW)
    ops = {"f.1": ["exit", "fwd", "fusion", "forward_backward", ""],
           "f.2": ["exit", "remat", "fusion", "forward_backward", ""],
           "f.3": ["exit", "bwd", "fusion", "forward_backward", ""],
           "f.4": ["layers", "fwd", "fusion", "forward_backward", ""],
           "f.5": ["layers/loop", "bwd", "fusion", "forward_backward", ""],
           "f.6": ["vocab_head", "fwd", "dot", "forward_backward", ""]}
    programs = {
        "train": dict(module="jit_train_step", ops=ops, memory={}),
        "other": dict(module="jit_logprobs", memory={}, ops={
            "f.1": ["exit", "fwd", "fusion", "prefill", ""]})}
    names = [("jit_train_step", f"f.{i}", float(i)) for i in range(1, 8)] \
        + [("jit_logprobs", "f.1", 100.0)]
    t, events, modules = 0.0, [], []
    for module, op, secs in names:
        events.append((f"%{op} = f32[] fusion(%x)", t, t + secs))
        modules.append((f"{module}(1)", t, t + secs))
        t += secs
    trace = dict(devices={0: dict(ops=events, modules=modules)}, spans=[])
    profile = tmp_path / "plugins" / "profile" / "x"
    profile.mkdir(parents=True)
    (profile / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda path: trace)
    program_parts._CACHE.clear()
    attributes = [dict(expected_exit_pass=v) for v in (1.9, 1.7, 1.8)] \
        + [dict()]
    monkeypatch.setattr(tracing, "captures", lambda: [
        _capture({}, str(tmp_path), programs, attributes)])
    record = dict(chips=1)
    assert exit_s.read(record) == pytest.approx(1.0 + 2.0 + 3.0)
    assert loop_s.read(record) == pytest.approx(4.0 + 5.0)
    assert expected.read(record) == pytest.approx(1.8)
    # a program without the part: 0 seconds
    for row in ops.values():
        row[0] = "attn_proj"
    program_parts._CACHE.clear()
    assert (exit_s.read(record), loop_s.read(record)) == (0.0, 0.0)
    # nothing to read: no programs and no attribute in the capture (the
    # parent commit), no capture
    program_parts._CACHE.clear()
    monkeypatch.setattr(tracing, "captures", lambda: [
        _capture({}, str(tmp_path), attributes=[dict()])])
    assert [r.read(record) for r in (exit_s, loop_s, expected)] == [None] * 3
    monkeypatch.setattr(tracing, "captures", lambda: [])
    assert [r.read(record) for r in (exit_s, loop_s, expected)] == [None] * 3
    program_parts._CACHE.clear()


@pytest.mark.parametrize("name", NEW)
def test_reader_files_say_what_they_read(name):
    manifest = real_manifest()
    reader = run.load_module(run.find(manifest, "layer_metrics",
                                      name + ".py"))
    assert len(reader.__doc__) > 200 and callable(reader.read)
    if name == "loop.expected_exit_pass":
        assert "NO better direction" in reader.__doc__
    from realhf_tpu.obs import tracing
    tracing.reset_default()
    assert reader.read(dict(chips=1, family=object())) is None


def test_the_loop_scope_is_read_back_from_an_op_name():
    """``obs/parts.py``'s rule on the paths the loop's operations
    carry: the loop's own work, a layer scan's inside it, a block's
    part inside that, the gate's and the final norm's inside the
    loop."""
    from realhf_tpu.obs import parts as P
    assert P.classify("jit(f)/layers/loop/stack")[0] == "layers/loop"
    assert P.classify("jit(f)/layers/loop/layers/while/body/"
                      "dynamic_slice")[0] == "layers"
    assert P.classify("jit(f)/layers/loop/layers/while/body/checkpoint/"
                      "attn_proj/dot_general")[0] == "attn_proj"
    assert P.classify("jit(f)/transpose(jvp(layers))/loop/checkpoint/"
                      "exit/dot_general")[:2] == ("exit", "bwd")
    assert P.classify("jit(f)/layers/loop/checkpoint/vocab_head/mul")[0] \
        == "vocab_head"
    assert P.classify("jit(f)/layers/while/body/dynamic_slice")[0] \
        == "layers"  # a model that is not looped: as it was
    assert "exit" in P.PARTS and P.SUB_STEPS["layers"] == ("loop",)


def test_reference_holds_the_engine_and_a_wrong_model_fails(tmp_path):
    """The tiny cell's checkpoint through the program's loader in bf16
    against the family's float32 forward; the same forward with the
    final norm not fed back, without the post-operator norms, with one
    cache for all passes, or with every matrix rounded to float8, is
    outside the family's tolerance (toy widths: the chip run sizes it,
    ``scripts/chip_check.py ouro``). The fourth WRONG entry changes the
    objective alone, which ``correct`` cannot see: the tests on the
    CPU and ``chip_check.py``'s row ``objective`` hold it."""
    import jax
    import jax.numpy as jnp

    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.models.hf import registry
    from realhf_tpu.parallel import mesh as mesh_lib

    cell = run.load_cell(MANIFEST, CELL)
    hf, family = cell["hf"], cell["family"]
    ids = generate.fixed_batch(hf, seed=7, rows=2, length=160)
    par = mesh_lib.ParallelismConfig()
    ctx = mesh_lib.MeshContext(
        ModelName("default", 0),
        mesh_lib.make_mesh(par, jax.devices()[:1]), par)
    ckpt = str(tmp_path / "ckpt")
    generate.write_checkpoint(ckpt, family, hf, seed=7)
    tensors = reference.load_tensors(ckpt)
    want = family.logprobs(hf, tensors, ids)
    cfg, params = registry.load_hf_checkpoint(ckpt, "ouro")
    cfg.param_dtype = "bfloat16"
    got = np.asarray(Engine(cfg, ctx, params).forward_logprobs(
        ids, np.ones_like(ids)), np.float32)[:, :-1]
    assert got.shape == want.shape == (2, 159)
    assert reference.within_tolerance(got, want, family.TOLERANCE)
    for wrong in family.WRONG:
        off = family.logprobs(hf, tensors, ids, wrong=(wrong,))
        if wrong == "last_pass_loss_alone":
            np.testing.assert_array_equal(off, want)
        else:
            assert not reference.within_tolerance(off, want,
                                                  family.TOLERANCE), wrong
    float8 = family.logprobs(
        hf, tensors, ids,
        cast=lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32))
    assert not reference.within_tolerance(float8, want, family.TOLERANCE)
