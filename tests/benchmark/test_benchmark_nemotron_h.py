"""The ``nemotron_h`` family (NVIDIA-Nemotron-3-Nano-30B-A3B-BF16) in the
benchmark, on the CPU at toy widths: the tenth cell's entries and
configuration file against the issue and the catalog row, its
arithmetic at published widths, the reference's shares tied to the
uncut model with the shared expert counted once, a tiny cell (its own
manifest and configuration under ``tests/benchmark/nemotron_h/``, the
tests' ``tiny-sft`` traffic: four documents of 32 tokens a row, so the
state is reset and the convolution stops three times a row, inside a
chunk of 128) whole through ``run_cell``, and the three readers the
family brings.

Nothing here says where in its lists an entry stands or how long they
are (``in``, never ``[-1]`` or ``== n``): a later PR appends to them.
"""

import json
import os

import numpy as np
import pytest
from tiny_cells import PEAKS, check_line

from benchmark import generate, reference, run

MANIFEST = os.path.join(run.ROOT, "tests", "benchmark", "nemotron_h",
                        "manifest.json")
CELL = "tiny-nemotron-h.sft"
REAL = "nemotron-3-nano-30b-a3b-l7-ep16.sft-1k"
CONFIG = "nemotron-3-nano-30b-a3b-l7-ep16"
#: the accepted per-layer lists this PR appended its cell to
APPENDED = ("moe.held_pairs_per_s", "moe.held_load_max_over_mean",
            "train.attn_s", "train.attn_proj_s", "train.mlp_s",
            "train.experts_s", "train.head_s", "train.accum_s",
            "train.unscoped_s", "engine.program_gb")
NEW = ("train.ssm_s", "ssm.scan_s", "ssm.scan_mxu_share")
#: parameters the checkpoint holds (ISSUE 48's arithmetic)
PARAMS = 528_093_120
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size"]
FULL_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def go(trace, tmp_path):
    cell = run.load_cell(MANIFEST, CELL)
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
    assert cell["chips"] == 1 and cell["meta"]["family"] == "nemotron_h"
    assert cell["config"]["name"] == CONFIG
    assert cell["config"]["reduced"] == REDUCED \
        == list(cell["meta"]["reduced"])
    assert cell["config"]["file"] == f"benchmark/configs/{CONFIG}.json"
    hf, t = cell["hf"], cell["traffic"]
    assert (hf["num_hidden_layers"], hf["hybrid_override_pattern"],
            hf["n_routed_experts"], hf["expert_share"],
            hf["vocab_size"]) == (
        7, "EMEMEM*", 8, {"of": 128, "first": 0}, 16384)
    assert next(w for w in manifest["workloads"]
                if w["name"] == REAL)["traffic"] == "sft-1k-x64"
    assert (t["kind"], t["docs_per_step"], t["doc_len"], t["prompt_len"],
            t["docs_per_row"], t["lr"], t["steps_of_data"]) == (
        "sft", 64, 1024, 256, 4, 1e-4, 8)
    assert cell["meta"]["layout"] == {"chips": 1, "roles": "d1t1"}
    assert set(APPENDED) | set(NEW) | {
        "train.mfu", "mfc.train_s", "interface.host_s",
        "device.idle_share"} <= set(cell["readers"])
    # what reads another model's mechanisms stays off this cell
    assert not {"moe.pairs_per_s", "moe.load_max_over_mean", "mfc.gen_s",
                "gen.hbm_share", "train.conv_s", "train.collective_s",
                "flash.mxu_share", "flash.visited_share",
                "mla.flash_mxu_share", "mla.latent_s", "train.delta_s",
                "delta.scan_s", "sparse.index_s"} & set(cell["readers"])
    by = {m["name"]: m for m in manifest["per_layer"]}
    for name in APPENDED:
        assert REAL in by[name]["workloads"], name
    for name, unit, better, layer in (
            ("train.ssm_s", "s/step", "lower", "model"),
            ("ssm.scan_s", "s/step", "lower", "kernels"),
            ("ssm.scan_mxu_share", "%", "higher", "kernels")):
        assert by[name]["workloads"] == [REAL] or REAL in by[name][
            "workloads"]
        assert (by[name]["unit"], by[name]["better"], by[name]["layer"],
                by[name]["moves"], by[name]["source"]) == (
            unit, better, layer, "tokens_per_s", "device_trace")
    # ten cells, one of them on four chips
    assert len({w["name"] for w in manifest["workloads"]}) >= 10
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert "mistral-7b-v0.3-l4.grpo-realloc" in four and REAL not in four
    assert 0 <= hf["eos_token_id"] < hf["vocab_size"]


def test_every_width_is_the_published_one():
    """The configuration file against the catalog row the driver drew:
    every key of the row's ``config`` is in the file under the same
    name with the same value, but the four the file lists as reduced,
    and those say what was published."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
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
    # published layers 6 to 12: the first of the four periods of seven
    assert row["config"]["hybrid_override_pattern"] == FULL_PATTERN
    assert FULL_PATTERN[6:13] == hf["hybrid_override_pattern"] == "EMEMEM*"
    assert FULL_PATTERN[6:34] == "EMEMEM*" * 4
    # the guide's floors: a whole period, eight routed experts, an
    # eighth of the vocabulary; no width is cut
    assert hf["n_routed_experts"] == 8
    assert hf["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert (hf["hidden_size"], hf["mamba_num_heads"], hf["mamba_head_dim"],
            hf["ssm_state_size"], hf["n_groups"], hf["conv_kernel"],
            hf["use_conv_bias"], hf["num_attention_heads"],
            hf["num_key_value_heads"], hf["head_dim"],
            hf["num_experts_per_tok"], hf["routed_scaling_factor"],
            hf["moe_intermediate_size"],
            hf["moe_shared_expert_intermediate_size"],
            hf["mlp_hidden_act"]) == (
        2688, 64, 64, 128, 8, 4, True, 32, 2, 128, 6, 2.5, 1856, 3712,
        "relu2")
    for key in ("no rotary embedding", "d_inner",
                "grouped norm and its order", "no clamp on Delta",
                "the MoE module's form", "tensor names",
                "initializer_range", "eos_token_id", "expert_share"):
        assert key in meta["assumed"], key
    assert set(hf) - set(row["config"]) == {
        "initializer_range", "eos_token_id", "expert_share"}
    assert "SIXTEEN chips share each layer" in meta["deployment"]
    assert "20 bytes a parameter" in meta["deployment"]


def test_arithmetic_at_published_widths():
    """The numbers the issue works the cell out from: 528,093,120
    parameters (10.56 GB at 20 bytes), 526 MFLOP a token forward of
    which the three Mamba layers 45% (projections 77.4 MFLOP a layer,
    the recurrence 2.1), the expert layers 27%, the head 17%, the one
    attention layer 10%; the uncut model is the published 31.6 B."""
    cell = real_cell()
    family, hf = cell["family"], cell["hf"]
    assert family.n_params(hf) == PARAMS
    assert round(PARAMS * 20 / 1e9, 2) == 10.56
    assert round(100 * PARAMS * 20 / 16e9) == 66
    # layer by layer, as the issue counts them
    h = hf["hidden_size"]
    mamba = h * 10304 + 5 * 6144 + 3 * 64 + 4096 + 4096 * h + h
    expert = h * 128 + 128 + 8 * 2 * h * 1856 + 2 * h * 3712 + h
    attention = h * 4096 + 2 * h * 256 + 4096 * h + h
    assert (mamba, expert, attention) == (38_744_896, 100_125_440,
                                          23_399_040)
    assert 3 * mamba + 3 * expert + attention + 2 * 16384 * h + h == PARAMS
    with open(os.path.join(run.ROOT, cell["config"]["file"])) as f:
        published = {k: v["published"]
                     for k, v in json.load(f)["reduced"].items()}
    whole = {k: v for k, v in dict(hf, **published).items()
             if k != "expert_share"}
    assert abs(family.n_params(whole) / 31.6e9 - 1) < 0.01
    # the next sizes up do not fit: 16 experts a layer; the longest run
    # between two attention layers
    assert round(family.n_params(dict(hf, n_routed_experts=16)) * 20 / 1e9,
                 2) == 15.35
    assert round(family.n_params(dict(
        hf, num_hidden_layers=9,
        hybrid_override_pattern="EMEMEMEM*")) / 1e6) == 667
    seqlens = [1024] * 64
    tokens = sum(seqlens)
    flops = family.forward_flops(hf, seqlens) / tokens
    assert round(flops / 1e6) == 526
    projections = 2 * (h * 10304 + 4096 * h)
    assert round(projections / 1e6, 1) == 77.4
    recurrence = family.ssm_flops(hf, seqlens) / tokens / 3
    assert recurrence == 64 * 2 * 2 * 64 * 128 == 2_097_152
    assert round(100 * 3 * (projections + recurrence) / flops) == 45
    experts = 3 * 2 * (h * 128 + 2 * h * 3712 + 2 * h * 1856 * 6 * 8 / 128)
    assert round(100 * experts / flops) == 27
    assert round(100 * 2 * h * hf["vocab_size"] / flops) == 17
    attn = 2 * (h * 4096 + 2 * h * 256 + 4096 * h) \
        + 2 * family.visible_pairs(1024) * 32 * 2 * 128 / 1024
    assert round(100 * attn / flops) == 10
    active = family.forward_flops(whole, [1]) / 2 - h * 131072
    assert 2.5e9 < active < 3.7e9  # "A3B": parameters a token touches
    work = cell["kind"].work(family, hf, cell["meta"], cell["traffic"])
    assert work["tokens_per_step"] == 65536
    assert round(work["train_flops"] / tokens / 1e9, 2) == 1.58
    assert family.routed_pairs(hf, seqlens) == tokens * 6 * 3
    assert family.held_pairs(hf, seqlens) == tokens * 6 * 3 / 16
    assert family.held_pairs(hf, [4096]) / 3 / 8 == 192  # an expert a row
    # the fourth kind of decode state: 64 x 64 x 128 float32 and one
    # tail of 3 x 6144 an M layer a stream
    assert family.kv_bytes_per_token(hf) == 2 * 2 * 128 * 2
    assert family.decode_bytes(hf, 4, 1024, 1) == 2 * PARAMS \
        + 4 * 1024 * 1024 + 2 * family.ssm_state_bytes(hf, 4)
    assert family.ssm_state_bytes(hf, 1) == 3 * (
        64 * 64 * 128 * 4 + 3 * 6144 * 2)
    names = family.shapes(hf)
    assert sum(int(np.prod(s)) for s, _ in names.values()) == PARAMS
    assert sum(n.endswith("e_score_correction_bias") for n in names) == 3
    assert sum(n.endswith("A_log") for n in names) == 3
    assert names["backbone.layers.1.mixer.in_proj.weight"][0] == (10304,
                                                                  2688)
    assert names["backbone.layers.1.mixer.conv1d.weight"][0] == (6144, 1,
                                                                 4)
    assert names["backbone.layers.6.mixer.k_proj.weight"][0] == (256, 2688)
    assert names["backbone.layers.0.mixer.experts.7.up_proj.weight"][0] \
        == (1856, 2688)
    assert "backbone.layers.0.mixer.experts.8.up_proj.weight" not in names
    assert not any("gate_proj" in n for n in names)  # ungated
    # one norm a layer, and the final one
    assert sum(k == "norm" and s == (2688,) for s, k in names.values()) == 8


def test_published_init_draws_the_decay_as_published(tmp_path):
    cell = run.load_cell(MANIFEST, CELL)
    hf, family = cell["hf"], cell["family"]
    ckpt = str(tmp_path / "ckpt")
    generate.write_checkpoint(ckpt, family, hf, seed=3)
    tensors = reference.load_tensors(ckpt)
    out = family.published_init(hf, tensors, seed=3)
    assert set(out) == set(tensors)
    changed = {k for k in out if not np.array_equal(
        np.asarray(out[k], np.float32), np.asarray(tensors[k], np.float32))}
    assert changed == {f"backbone.layers.{i}.mixer.{n}"
                       for i in (1, 3, 5)
                       for n in ("A_log", "dt_bias", "D", "conv1d.weight",
                                 "conv1d.bias")}
    for i in (1, 3, 5):
        m = f"backbone.layers.{i}.mixer."
        a = np.exp(np.asarray(out[m + "A_log"], np.float32))
        dt = np.log1p(np.exp(np.asarray(out[m + "dt_bias"], np.float32)))
        assert out[m + "A_log"].dtype == tensors[m + "A_log"].dtype
        assert (a > 0.98).all() and (a < 16.2).all()
        assert (dt > 0.9e-3).all() and (dt < 0.11).all()
        assert (np.asarray(out[m + "D"], np.float32) == 1).all()
        taps = np.asarray(out[m + "conv1d.weight"], np.float32)
        assert taps.shape == (128, 1, 4) and np.abs(taps).max() <= 0.5 \
            and taps.std() > 0.25
    # the harness's own draw: a state halves every token
    a = np.exp(np.asarray(tensors["backbone.layers.1.mixer.A_log"],
                          np.float32))
    assert (np.abs(a - 1) < 0.15).all()


def test_the_references_shares_add_up_to_the_uncut_model(tmp_path):
    """The guide's tie of the share to the model, on the reference's
    side: one E layer's ROUTED output under each of SIXTEEN shares of
    one expert adds up to the routed output with all 16 held; the
    shared expert, which every share computes alike, is counted
    once."""
    import jax
    from benchmark.families import nemotron_h as family
    cell = run.load_cell(MANIFEST, CELL)
    hf = dict(cell["hf"], num_hidden_layers=2, n_routed_experts=16,
              hybrid_override_pattern="ME")
    del hf["expert_share"]
    ckpt = str(tmp_path / "ckpt")
    generate.write_checkpoint(ckpt, family, hf, seed=5)
    get = family._getter(reference.load_tensors(ckpt), None)
    ids = generate.fixed_batch(hf, seed=5, rows=2, length=32)

    def after(first, count):
        with jax.default_matmul_precision("highest"):
            return np.asarray(family._blocks(
                dict(hf, n_routed_experts=count,
                     expert_share={"of": 16, "first": first}), get,
                ids)[0])

    whole, alike = after(0, 16), after(0, 0)
    routed = sum(after(f, 1) - alike for f in range(16))
    assert np.abs(whole - alike).max() > 1e-5
    assert np.abs(alike + routed - whole).max() \
        < 2e-5 * np.abs(whole).max()
    # every share adds the mixer layer's output and the shared expert:
    # summed as they are, the sixteen shares count them sixteen times
    naive = sum(after(f, 1) for f in range(16))
    assert np.abs(naive - whole - 15 * alike).max() \
        < 2e-5 * np.abs(whole).max()


def test_cell_end_to_end(tmp_path):
    _, out = go(0, tmp_path)
    check_line(out, trace=False)


def test_cell_measured_then_traced(tmp_path):
    from realhf_tpu.obs import tracing
    from realhf_tpu.ops.ssm_scan import CHUNK
    cell, out = go(2, tmp_path)
    check_line(out, trace=True)
    m = out["metrics"]
    assert {"moe.held_pairs_per_s", "moe.held_load_max_over_mean",
            "mfc.train_s", "train.mfu", "interface.host_s",
            "engine.program_gb", "tokens_per_s"} <= set(m)
    # the CPU's trace holds no device operation: the parts' readers,
    # the three new ones among them, leave their metric out of the line
    # without raising
    assert set(NEW) | {"train.attn_proj_s"} <= set(cell["readers"])
    assert not (set(NEW) | {"train.attn_proj_s"}) & set(m)
    profiled, synced = tracing.captures()[-2:]
    assert profiled.profile_dir is not None and synced.sync is True
    t = cell["traffic"]
    seqlens = [t["doc_len"]] * t["docs_per_step"]
    for capture in (profiled, synced):
        trains = capture.named("engine:train")
        assert len(trains) == run.TRACE_STEPS
        for span in trains:
            a = span["attributes"]
            assert (a["layer_pattern"], a["ssm_layers"], a["ssm_heads"],
                    a["ssm_head_dim"], a["ssm_state"], a["ssm_groups"],
                    a["ssm_chunk"], a["expert_ff"], a["rotary"],
                    a["shared_expert"], a["experts_held"], a["experts"],
                    a["router"], a["dense_layers"]) == (
                "- m - m - m a", 3, 8, 8, 16, 2, CHUNK, "relu2/ungated",
                "a:none", 48, 4, 16, "sigmoid_bias", 4)
        assert capture.counter("ssm_tokens_total", role="default") \
            == run.TRACE_STEPS * sum(seqlens) * 3
        routed = capture.counter("moe_routed_pairs_total", role="default",
                                 dispatch="ragged")
        assert routed == run.TRACE_STEPS * cell["family"].routed_pairs(
            cell["hf"], seqlens)
        held = capture.counter("moe_held_pairs_total", role="default")
        assert 0.1 < held / routed < 0.4
    # the program names the part and its sub-part in the facts the
    # capture carries: what the three readers will find on the chip
    parts = {row[0] for facts in profiled.programs.values()
             for row in facts["ops"].values()}
    assert {"ssm/scan", "ssm", "attn_proj", "attn", "shared_expert",
            "experts/route"} <= parts
    assert not {"mlp", "conv", "delta"} & parts


def _capture(counters, profile_dir="x", programs=None):
    from realhf_tpu.obs import tracing
    capture = tracing.Capture(
        spans=[dict(name="step", start=0.0, end=1.0, span_id="a",
                    parent_id=None, trace_id="t", thread=0, attributes={})],
        counters=counters, start=0.0, end=1.0, sync=("compute:",),
        profile_dir=profile_dir)
    capture.programs = programs or {}
    return capture


def test_the_three_readers_read_the_part_and_its_sub_part(monkeypatch,
                                                          tmp_path):
    """Against a constructed trace: operations of the train program
    under ``ssm/scan`` in three passes are ``ssm.scan_s``, with those of
    ``ssm`` itself ``train.ssm_s``; those of another part (another
    model's ``delta/scan`` among them), of another program and of an
    operation the text does not name count for neither.
    ``ssm.scan_mxu_share`` is the family's FLOPs of the recurrence AS
    WRITTEN, times 3, over those seconds and the peak. Nothing where the
    capture has no ``programs`` (the parent commit under these files); 0
    (and no share) where the program has no such part."""
    from benchmark import program_parts, trace_reduce
    from realhf_tpu.obs import tracing
    cell = run.load_cell(MANIFEST, CELL)
    whole, scan, share = (cell["readers"][n] for n in NEW)
    ops = {"f.1": ["ssm/scan", "fwd", "fusion", "forward_backward", ""],
           "f.2": ["ssm/scan", "remat", "fusion", "forward_backward", ""],
           "f.3": ["ssm/scan", "bwd", "fusion", "forward_backward", ""],
           "f.4": ["ssm", "fwd", "dot", "forward_backward", ""],
           "f.5": ["delta/scan", "fwd", "dot", "forward_backward", ""]}
    programs = {
        "train": dict(module="jit_train_step", ops=ops, memory={}),
        "other": dict(module="jit_logprobs", memory={}, ops={
            "f.1": ["ssm/scan", "fwd", "fusion", "prefill", ""]})}
    names = [("jit_train_step", f"f.{i}", float(i)) for i in range(1, 7)] \
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
    monkeypatch.setattr(tracing, "captures", lambda: [
        _capture({}, str(tmp_path), programs)])
    hf = cell["hf"]
    record = dict(chips=1, family=cell["family"], hf=hf,
                  traffic=dict(doc_len=32, docs_per_step=8),
                  peaks=dict(flops=1e6))
    assert scan.read(record) == pytest.approx(1.0 + 2.0 + 3.0)
    assert whole.read(record) == pytest.approx(1.0 + 2.0 + 3.0 + 4.0)
    # three M layers, 8 heads of 8, a state of 16, 256 tokens a step
    flops = 3 * 256 * 3 * 8 * 2 * 2 * 8 * 16
    assert cell["family"].ssm_flops(hf, [32] * 8) * 3 == flops
    assert share.read(record) == pytest.approx(100.0 * flops / (6.0 * 1e6))
    assert share.read(dict(record, family=object())) is None
    # a program without the part: 0 seconds, and no share of nothing
    for row in ops.values():
        row[0] = "attn_proj"
    program_parts._CACHE.clear()
    assert (scan.read(record), whole.read(record)) == (0.0, 0.0)
    assert share.read(record) is None
    # nothing to read: no programs in the capture, no capture
    program_parts._CACHE.clear()
    monkeypatch.setattr(tracing, "captures", lambda: [
        _capture({}, str(tmp_path))])
    assert [r.read(record) for r in (whole, scan, share)] == [None] * 3
    monkeypatch.setattr(tracing, "captures", lambda: [])
    assert [r.read(record) for r in (whole, scan, share)] == [None] * 3
    program_parts._CACHE.clear()


@pytest.mark.parametrize("name", NEW)
def test_reader_files_say_what_they_read(name):
    manifest = real_manifest()
    reader = run.load_module(run.find(manifest, "layer_metrics",
                                      name + ".py"))
    assert len(reader.__doc__) > 200 and callable(reader.read)
    from realhf_tpu.obs import tracing
    tracing.reset_default()
    assert reader.read(dict(chips=1, family=object())) is None


@pytest.mark.parametrize("init", ["harness", "published"])
def test_reference_holds_the_engine_and_a_wrong_model_fails(tmp_path, init):
    """The tiny cell's checkpoint through the program's loader in bf16
    against the family's float32 forward, under the harness's weights
    and under the published initialisation of the decay; the same
    forward with ``silu`` for ``relu2``, or with every matrix rounded to
    float8, is outside the family's tolerance (toy widths: the chip run
    sizes it, ``scripts/chip_check.py nemotron_h``)."""
    import jax
    import jax.numpy as jnp
    import safetensors.numpy

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
    ckpt = str(tmp_path / init)
    generate.write_checkpoint(ckpt, family, hf, seed=7)
    tensors = reference.load_tensors(ckpt)
    if init == "published":
        tensors = family.published_init(hf, tensors, seed=7)
        safetensors.numpy.save_file(
            tensors, os.path.join(ckpt, "model.safetensors"))
    want = family.logprobs(hf, tensors, ids)
    cfg, params = registry.load_hf_checkpoint(ckpt, "nemotron_h")
    cfg.param_dtype = "bfloat16"
    got = np.asarray(Engine(cfg, ctx, params).forward_logprobs(
        ids, np.ones_like(ids)), np.float32)[:, :-1]
    assert got.shape == want.shape == (2, 159)
    assert reference.within_tolerance(got, want, family.TOLERANCE)
    gap, spread = reference.gap(got, want)
    assert gap < 0.5 * family.TOLERANCE * spread  # toy widths: inside
    assert not reference.within_tolerance(
        family.logprobs(hf, tensors, ids, wrong=("silu_for_relu2",)),
        want, family.TOLERANCE)
    float8 = family.logprobs(
        hf, tensors, ids,
        cast=lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32))
    assert not reference.within_tolerance(float8, want, family.TOLERANCE)
