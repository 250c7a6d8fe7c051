"""The ``kimi_linear`` family (Kimi-Linear-48B-A3B-Instruct) in the
benchmark, on the CPU at toy widths: the eighth cell's entries and
configuration file against the issue and the catalog row, its
arithmetic at published widths, the reference's shares tied to the
uncut model with the shared expert counted once, a tiny cell (its own
manifest and configuration under ``tests/benchmark/kimi_linear/``, the
tests' ``tiny-sft`` traffic: four documents of 32 tokens a row, so the
delta state is reset and the convolutions stop three times a row) whole
through ``run_cell``, and the three readers the family brings.

Nothing here says where in its lists an entry stands or how long they
are (``in``, never ``[-1]`` or ``== n``): a later PR appends to them.
"""

import json
import os

import numpy as np
import pytest
from tiny_cells import PEAKS, check_line

from benchmark import generate, reference, run

MANIFEST = os.path.join(run.ROOT, "tests", "benchmark", "kimi_linear",
                        "manifest.json")
CELL = "tiny-kimi-linear.sft"
REAL = "kimi-linear-48b-a3b-l5-ep32.sft-2k"
CONFIG = "kimi-linear-48b-a3b-l5-ep32"
#: the accepted per-layer lists this PR appended its cell to
APPENDED = ("moe.held_pairs_per_s", "moe.held_load_max_over_mean",
            "train.attn_s", "train.attn_proj_s", "train.mlp_s",
            "train.experts_s", "train.head_s", "train.accum_s",
            "train.unscoped_s", "engine.program_gb")
NEW = ("train.delta_s", "delta.scan_s", "delta.scan_mxu_share")
#: parameters the checkpoint holds (ISSUE 39's arithmetic)
PARAMS = 602_434_432
REDUCED = ["num_hidden_layers", "linear_attn_config", "num_experts",
           "vocab_size"]


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
    assert cell["chips"] == 1 and cell["meta"]["family"] == "kimi_linear"
    assert cell["config"]["name"] == CONFIG
    assert cell["config"]["reduced"] == REDUCED \
        == list(cell["meta"]["reduced"])
    assert cell["config"]["file"] == f"benchmark/configs/{CONFIG}.json"
    hf, t = cell["hf"], cell["traffic"]
    lin = hf["linear_attn_config"]
    assert (hf["num_hidden_layers"], hf["first_k_dense_replace"],
            lin["kda_layers"], lin["full_attn_layers"], hf["num_experts"],
            hf["expert_share"], hf["vocab_size"]) == (
        5, 1, [1, 2, 3, 5], [4], 8, {"of": 256, "first": 0}, 20480)
    assert next(w for w in manifest["workloads"]
                if w["name"] == REAL)["traffic"] == "sft-2k-x32"
    assert (t["kind"], t["docs_per_step"], t["doc_len"], t["prompt_len"],
            t["docs_per_row"], t["lr"], t["steps_of_data"]) == (
        "sft", 32, 2048, 256, 1, 1e-4, 8)
    assert cell["meta"]["layout"] == {"chips": 1, "roles": "d1t1"}
    assert set(APPENDED) | set(NEW) | {
        "train.mfu", "mfc.train_s", "interface.host_s",
        "device.idle_share"} <= set(cell["readers"])
    # what reads another model's mechanisms stays off this cell: every
    # expert held, generation, the gated convolutions, collectives, and
    # the flash and latent readers, whose lists stay pinned to a cell
    assert not {"moe.pairs_per_s", "moe.load_max_over_mean", "mfc.gen_s",
                "gen.hbm_share", "train.conv_s", "train.collective_s",
                "flash.mxu_share", "flash.visited_share",
                "mla.flash_mxu_share", "mla.latent_s"} \
        & set(cell["readers"])
    by = {m["name"]: m for m in manifest["per_layer"]}
    for name in APPENDED:
        assert REAL in by[name]["workloads"], name
    for name, unit, better, layer in (
            ("train.delta_s", "s/step", "lower", "model"),
            ("delta.scan_s", "s/step", "lower", "kernels"),
            ("delta.scan_mxu_share", "%", "higher", "kernels")):
        assert REAL in by[name]["workloads"]
        assert (by[name]["unit"], by[name]["better"], by[name]["layer"],
                by[name]["moves"], by[name]["source"]) == (
            unit, better, layer, "tokens_per_s", "device_trace")
    # eight cells, one of them on four chips
    assert len({w["name"] for w in manifest["workloads"]}) >= 8
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert "mistral-7b-v0.3-l4.grpo-realloc" in four
    assert 0 <= hf["eos_token_id"] < hf["vocab_size"]


def test_every_width_is_the_published_one():
    """The configuration file against the catalog row the driver drew:
    every key of the row's ``config`` is in the file under the same
    name with the same value, but the four the file lists as reduced,
    and those say what was published; inside ``linear_attn_config``
    only the two layer lists differ."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
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
    lin, pub = hf["linear_attn_config"], row["config"]["linear_attn_config"]
    assert {k for k in pub if lin[k] != pub[k]} == {"kda_layers",
                                                    "full_attn_layers"}
    # layers 1 to 5 of the published lists, cut where the depth is
    assert lin["kda_layers"] == [i for i in pub["kda_layers"] if i <= 5]
    assert lin["full_attn_layers"] == [
        i for i in pub["full_attn_layers"] if i <= 5]
    # the guide's floors: the lead and four layers after it (one whole
    # period of three delta layers and a latent one, and a delta layer
    # more), eight routed experts, an eighth of the vocabulary
    assert hf["num_hidden_layers"] - hf["first_k_dense_replace"] == 4
    assert hf["num_experts"] == 8
    assert hf["vocab_size"] * 8 == row["config"]["vocab_size"]
    for key in ("tensor names", "layer lists are 1-based",
                "convolutions have no bias", "l2 norm epsilon",
                "o_norm epsilon", "kv_a_layernorm epsilon",
                "initializer_range", "eos_token_id", "expert_share"):
        assert key in meta["assumed"], key
    assert set(hf) - set(row["config"]) == {
        "initializer_range", "eos_token_id", "expert_share"}
    assert "THIRTY-TWO chips share each layer" in meta["deployment"]
    assert "20 bytes a parameter" in meta["deployment"]


def test_arithmetic_at_published_widths():
    """The numbers the issue works the cell out from: 602,434,432
    parameters (12.05 GB at 20 bytes), 705 MFLOP a token forward of
    which the four delta layers 47% (projections 79 MFLOP a layer, the
    recurrence 3.1), the dense lead 18%, the head 13%, the latent layer
    11%, shared + router + held experts 11%; the uncut model is the
    published 48 B with 3 B active."""
    cell = real_cell()
    family, hf = cell["family"], cell["hf"]
    assert family.n_params(hf) == PARAMS
    assert round(PARAMS * 20 / 1e9, 2) == 12.05
    with open(os.path.join(run.ROOT, cell["config"]["file"])) as f:
        published = {k: v["published"]
                     for k, v in json.load(f)["reduced"].items()}
    whole = {k: v for k, v in dict(hf, **published).items()
             if k != "expert_share"}
    assert round(family.n_params(whole) / 1e9) == 49
    # the next size up does not fit: 16 experts a layer
    assert round(family.n_params(dict(hf, num_experts=16)) * 20 / 1e9,
                 1) == 16.6
    seqlens = [2048] * 32
    tokens = sum(seqlens)
    flops = family.forward_flops(hf, seqlens) / tokens
    assert round(flops / 1e6) == 705
    h = hf["hidden_size"]
    projections = 2 * (4 * h * 4096 + 2 * (h + 4096) * 128 + h * 32)
    assert round(projections / 1e6) == 79
    recurrence = family.delta_flops(hf, seqlens) / tokens / 4
    assert recurrence == 32 * 3 * 2 * 128 * 128 == 3_145_728
    assert round(100 * 4 * (projections + recurrence) / flops) == 47
    assert round(100 * 6 * h * hf["intermediate_size"] / flops) == 18
    assert round(100 * 2 * h * hf["vocab_size"] / flops) == 13
    latent = 2 * (h * 32 * 192 + h * 576 + 512 * 32 * 256 + 32 * 128 * h) \
        + 2 * family.visible_pairs(2048) * 32 * (192 + 128) / 2048
    assert round(100 * latent / flops) == 11
    experts = 4 * 2 * (h * 256 + 3 * h * 1024 + 3 * h * 1024 * 8 * 8 / 256)
    assert round(100 * experts / flops) == 11
    active = family.forward_flops(whole, [1]) / 2 - h * 163840
    assert 2.0e9 < active < 3.5e9  # "A3B": parameters a token touches
    work = cell["kind"].work(family, hf, cell["meta"], cell["traffic"])
    assert work["tokens_per_step"] == 65536
    assert round(work["train_flops"] / tokens / 1e9, 2) == 2.11
    assert family.routed_pairs(hf, seqlens) == tokens * 8 * 4
    assert family.held_pairs(hf, seqlens) == tokens * 8 * 4 / 32
    assert family.held_pairs(hf, [2048]) / 4 / 8 == 64  # an expert a row
    # the third kind of decode state: 32 x 128 x 128 float32 and three
    # tails of 3 x 4096 a delta layer a stream; K/V of ONE layer
    assert family.delta_state_bytes(hf, 1) == 4 * (
        32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2)
    assert family.kv_bytes_per_token(hf) == 32 * (192 + 128) * 2
    assert family.decode_bytes(hf, 4, 1024, 1) == \
        2 * PARAMS + 4 * 1024 * 32 * 320 * 2 \
        + 2 * family.delta_state_bytes(hf, 4)
    names = family.shapes(hf)
    assert sum(int(np.prod(s)) for s, _ in names.values()) == PARAMS
    assert sum(n.endswith("e_score_correction_bias") for n in names) == 4
    assert sum(n.endswith("A_log") for n in names) == 4
    assert "model.layers.3.self_attn.kv_a_proj_with_mqa.weight" in names
    assert "model.layers.0.block_sparse_moe.gate.weight" not in names


def test_the_references_shares_add_up_to_the_uncut_model(tmp_path):
    """The guide's tie of the share to the model, on the reference's
    side: one sparse delta layer's ROUTED output under each of EIGHT
    shares of 2 experts adds up to the routed output with all 16 held;
    the mixer and the shared expert, which every share computes alike,
    are counted once."""
    import jax
    from benchmark.families import kimi_linear as family
    cell = run.load_cell(MANIFEST, CELL)
    hf = dict(cell["hf"], num_hidden_layers=2, num_experts=16,
              linear_attn_config=dict(cell["hf"]["linear_attn_config"],
                                      kda_layers=[1, 2],
                                      full_attn_layers=[]))
    del hf["expert_share"]
    ckpt = str(tmp_path / "ckpt")
    generate.write_checkpoint(ckpt, family, hf, seed=5)
    get = family._getter(reference.load_tensors(ckpt), None)
    ids = generate.fixed_batch(hf, seed=5, rows=2, length=32)

    def after(first, count):
        with jax.default_matmul_precision("highest"):
            return np.asarray(family._blocks(
                dict(hf, num_experts=count,
                     expert_share={"of": 16, "first": first}), get,
                ids)[0])

    whole, alike = after(0, 16), after(0, 0)
    routed = sum(after(f, 2) - alike for f in range(0, 16, 2))
    assert np.abs(whole - alike).max() > 1e-4
    assert np.abs(alike + routed - whole).max() \
        < 2e-5 * np.abs(whole).max()
    # every share adds the mixer and the shared expert: summed as they
    # are, the eight shares count them eight times
    naive = sum(after(f, 2) for f in range(0, 16, 2))
    assert np.abs(naive - whole - 7 * alike).max() \
        < 2e-5 * np.abs(whole).max()


def test_cell_end_to_end(tmp_path):
    _, out = go(0, tmp_path)
    check_line(out, trace=False)


def test_cell_measured_then_traced(tmp_path):
    from realhf_tpu.obs import tracing
    from realhf_tpu.ops.delta_rule import CHUNK
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
            assert (a["layer_pattern"], a["delta_layers"],
                    a["delta_heads"], a["delta_head_dim"],
                    a["delta_chunk"], a["latent_layers"], a["rotary"],
                    a["shared_expert"], a["experts_held"], a["experts"],
                    a["router"]) == (
                "d d d l d", 4, 4, 16, CHUNK, 1, "l:none", 16, 4, 16,
                "sigmoid_bias")
        assert capture.counter("delta_tokens_total", role="default") \
            == run.TRACE_STEPS * sum(seqlens) * 4
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
    assert {"delta/scan", "delta", "attn_proj", "attn"} <= parts


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
    under ``delta/scan`` in three passes are ``delta.scan_s``, with
    those of ``delta`` itself ``train.delta_s``; those of another part,
    of another program and of an operation the text does not name count
    for neither. ``delta.scan_mxu_share`` is the family's FLOPs of the
    recurrence AS WRITTEN, times 3, over those seconds and the peak.
    Nothing where the capture has no ``programs`` (the parent commit
    under these files); 0 (and no share) where the program has no such
    part."""
    from benchmark import program_parts, trace_reduce
    from realhf_tpu.obs import tracing
    cell = run.load_cell(MANIFEST, CELL)
    whole, scan, share = (cell["readers"][n] for n in NEW)
    ops = {"f.1": ["delta/scan", "fwd", "fusion", "forward_backward", ""],
           "f.2": ["delta/scan", "remat", "fusion", "forward_backward", ""],
           "f.3": ["delta/scan", "bwd", "fusion", "forward_backward", ""],
           "f.4": ["delta", "fwd", "dot", "forward_backward", ""],
           "f.5": ["mlp", "fwd", "dot", "forward_backward", ""]}
    programs = {
        "train": dict(module="jit_train_step", ops=ops, memory={}),
        "other": dict(module="jit_logprobs", memory={}, ops={
            "f.1": ["delta/scan", "fwd", "fusion", "prefill", ""]})}
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
    # four delta layers, 4 heads of 16, 256 tokens a step
    flops = 3 * 256 * 4 * 4 * 3 * 2 * 16 * 16
    assert cell["family"].delta_flops(hf, [32] * 8) * 3 == flops
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


def test_reference_holds_the_engine_and_a_wrong_model_fails(tmp_path):
    """The tiny cell's checkpoint through the program's loader in bf16
    against the family's float32 forward, under the harness's weights
    and under the published initialisation of the decay; the same
    forward with the output gate left out, or with every matrix
    rounded to float8, is outside the family's tolerance (toy widths:
    the chip run sizes it, ``scripts/chip_check.py kimi_linear``)."""
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
    for decay in ("harness", "published"):
        ckpt = str(tmp_path / decay)
        generate.write_checkpoint(ckpt, family, hf, seed=7)
        tensors = reference.load_tensors(ckpt)
        if decay == "published":
            tensors = family.published_decay(hf, tensors, seed=7)
            safetensors.numpy.save_file(
                tensors, os.path.join(ckpt, "model.safetensors"))
        want = family.logprobs(hf, tensors, ids)
        cfg, params = registry.load_hf_checkpoint(ckpt, "kimi_linear")
        cfg.param_dtype = "bfloat16"
        got = np.asarray(Engine(cfg, ctx, params).forward_logprobs(
            ids, np.ones_like(ids)), np.float32)[:, :-1]
        assert got.shape == want.shape == (2, 159)
        assert reference.within_tolerance(got, want, family.TOLERANCE)
        gap, spread = reference.gap(got, want)
        assert gap < 0.01 * spread  # toy widths: far inside
        assert not reference.within_tolerance(
            family.logprobs(hf, tensors, ids,
                            wrong=("output_gate_left_out",)),
            want, family.TOLERANCE)
    float8 = family.logprobs(
        hf, tensors, ids,
        cast=lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32))
    assert not reference.within_tolerance(float8, want, family.TOLERANCE)
