"""The ``keye_vl2`` family (Keye-VL-2.0-30B-A3B) in the benchmark, on the
CPU at toy widths: the ninth cell's entries and configuration file
against the issue and the catalog row, its arithmetic at published
widths, the reference's shares tied to the uncut model, a tiny cell
(its own manifest, configuration and traffic under
``tests/benchmark/keye_vl2/``: two documents of 256 tokens a row and an
indexer that picks 192, so that the selection is live in the train
step) whole through ``run_cell``, and the five readers the family
brings.

Nothing here says where in its lists an entry stands or how long they
are (``in``, never ``[-1]`` or ``== n``): a later PR appends to them.
"""

import json
import os

import numpy as np
import pytest
from tiny_cells import PEAKS, check_line

from benchmark import generate, reference, run

MANIFEST = os.path.join(run.ROOT, "tests", "benchmark", "keye_vl2",
                        "manifest.json")
CELL = "tiny-keye-vl2.sft"
REAL = "keye-vl-2.0-30b-a3b-l5-ep8.sft-4k"
CONFIG = "keye-vl-2.0-30b-a3b-l5-ep8"
#: the accepted per-layer lists this PR appended its cell to
APPENDED = ("moe.held_pairs_per_s", "moe.held_load_max_over_mean",
            "train.attn_s", "train.attn_proj_s", "train.experts_s",
            "train.head_s", "train.accum_s", "train.unscoped_s",
            "engine.program_gb")
NEW = ("sparse.index_s", "sparse.select_s", "sparse.index_mxu_share",
       "sparse.flash_mxu_share", "sparse.selected_share")
#: parameters in matrices (ISSUE 45's arithmetic) and all the files hold
MATRICES, PARAMS = 562_266_112, 562_290_560
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


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
    assert cell["chips"] == 1 and cell["meta"]["family"] == "keye_vl2"
    assert cell["config"]["name"] == CONFIG
    assert cell["config"]["reduced"] == REDUCED \
        == list(cell["meta"]["reduced"])
    assert cell["config"]["file"] == f"benchmark/configs/{CONFIG}.json"
    hf, t = cell["hf"], cell["traffic"]
    assert (hf["num_hidden_layers"], hf["num_experts"], hf["expert_share"],
            hf["vocab_size"], hf["num_experts_per_tok"]) == (
        5, 16, {"of": 128, "first": 0}, 18992, 8)
    assert hf["sa_config"] == dict(
        indexer_head_dim=64, indexer_num_heads=16, indexer_num_kv_heads=1,
        kv_chunk_size=512, q_chunk_size=512, topk=2048)
    assert next(w for w in manifest["workloads"]
                if w["name"] == REAL)["traffic"] == "sft-4k-x32"
    assert (t["kind"], t["docs_per_step"], t["doc_len"], t["prompt_len"],
            t["docs_per_row"], t["lr"], t["steps_of_data"]) == (
        "sft", 32, 4096, 512, 1, 1e-4, 8)
    assert cell["meta"]["layout"] == {"chips": 1, "roles": "d1t1"}
    assert set(APPENDED) | set(NEW) | {
        "train.mfu", "mfc.train_s", "interface.host_s",
        "device.idle_share"} <= set(cell["readers"])
    # what reads another model's mechanisms stays off this cell: every
    # expert held, generation, a dense feed-forward, the gated
    # convolutions, the delta layers, collectives, and the flash and
    # latent readers, whose lists stay pinned to a cell
    assert not {"moe.pairs_per_s", "moe.load_max_over_mean", "mfc.gen_s",
                "gen.hbm_share", "train.mlp_s", "train.conv_s",
                "train.delta_s", "delta.scan_s", "train.collective_s",
                "flash.mxu_share", "flash.visited_share",
                "mla.flash_mxu_share", "mla.latent_s"} \
        & set(cell["readers"])
    by = {m["name"]: m for m in manifest["per_layer"]}
    for name in APPENDED:
        assert REAL in by[name]["workloads"], name
    for name, unit, better, layer, source in (
            ("sparse.index_s", "s/step", "lower", "model", "device_trace"),
            ("sparse.select_s", "s/step", "lower", "model", "device_trace"),
            ("sparse.index_mxu_share", "%", "higher", "model",
             "device_trace"),
            ("sparse.flash_mxu_share", "%", "higher", "kernels",
             "device_trace"),
            ("sparse.selected_share", "%", "lower", "kernels",
             "program_counter")):
        assert REAL in by[name]["workloads"]
        assert (by[name]["unit"], by[name]["better"], by[name]["layer"],
                by[name]["moves"], by[name]["source"]) == (
            unit, better, layer, "tokens_per_s", source)
    # nine cells, one of them on four chips
    assert len({w["name"] for w in manifest["workloads"]}) >= 9
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert "mistral-7b-v0.3-l4.grpo-realloc" in four and REAL not in four
    assert 0 <= hf["eos_token_id"] < hf["vocab_size"]
    for entry in (cell["config"], next(
            w for w in manifest["workloads"] if w["name"] == REAL)):
        assert len(entry["why"]) <= 200 and "\n" not in entry["why"]


def test_every_width_is_the_published_one():
    """The configuration file against the catalog row the driver drew:
    every key of the row's ``config`` is in the file under the same
    name with the same value, but the three the file lists as reduced,
    and those say what was published."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
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
    # the guide's floors: five whole periods (a period is one layer,
    # there is no dense lead), sixteen routed experts, an eighth of the
    # vocabulary
    assert hf["num_hidden_layers"] >= 4 and hf["mlp_only_layers"] == []
    assert hf["num_experts"] * 8 == row["config"]["num_experts"]
    assert hf["vocab_size"] * 8 == row["config"]["vocab_size"]
    for key in ("language model only", "mrope_section", "q_norm, k_norm",
                "indexer", "indexer query from u", "indexer k_norm",
                "indexer rotary", "indexer tensor names",
                "q_chunk_size, kv_chunk_size", "hadamard and float8",
                "training the indexer", "num_local_experts",
                "initializer_range", "input_layernorm drawn as a matrix",
                "eos_token_id", "expert_share"):
        assert key in meta["assumed"], key
    assert set(hf) - set(row["config"]) == {
        "initializer_range", "eos_token_id", "expert_share"}
    assert "EIGHT chips share each layer" in meta["deployment"]
    assert "20 bytes a parameter" in meta["deployment"]
    assert "published layers 0 to 4" in meta["deployment"]


def test_arithmetic_at_published_widths():
    """The numbers the issue works the cell out from: 562 M parameters
    (11.25 GB at 20 bytes), about 485 MFLOP a token forward of which
    attention's projections 39%, its scores and values over the
    SELECTED pairs 26%, the indexer 9% (projections 4.5, scores 4.2
    MFLOP a layer), the head 16%, router and held experts 10%; 75.0% of
    a 4096-token document's causal pairs are attended; the uncut model
    is the published 30 B with 3 B active."""
    cell = real_cell()
    family, hf = cell["family"], cell["hf"]
    assert family.n_matrix_params(hf) == MATRICES
    assert family.n_params(hf) == PARAMS
    assert round(MATRICES * 20 / 1e9, 2) == 11.25
    d = family.dims(hf)
    assert family._attention_params(d) == 18_874_368
    assert family._index_params(d) == 2_260_992
    assert family._ffn_params(d) == 262_144 + 16 * 4_718_592
    with open(os.path.join(run.ROOT, cell["config"]["file"])) as f:
        published = {k: v["published"]
                     for k, v in json.load(f)["reduced"].items()}
    whole = {k: v for k, v in dict(hf, **published).items()
             if k != "expert_share"}
    assert round(family.n_params(whole) / 1e9, 1) == 30.6
    # the next sizes up do not fit: a sixth layer, 32 experts a layer
    assert round(family.n_params(dict(hf, num_hidden_layers=6)) * 20 / 1e9,
                 1) == 13.2
    assert round(family.n_params(dict(hf, num_experts=32)) * 20 / 1e9,
                 1) == 18.8
    seqlens = [4096] * 32
    tokens = sum(seqlens)
    assert family.selected_pairs(4096, 2048) == 6_292_480
    assert family.visible_pairs(4096) == 8_390_656
    assert round(100 * 6_292_480 / 8_390_656, 1) == 75.0
    # rows under topk select every visible key
    assert family.selected_pairs(256, 2048) == family.visible_pairs(256)
    flops = family.forward_flops(hf, seqlens) / tokens
    assert 485e6 < flops < 486e6
    shares = family.flop_shares(hf, seqlens)
    assert abs(sum(shares.values()) - 1) < 1e-12
    assert {k: round(100 * v) for k, v in shares.items()} == dict(
        attn_proj=39, attn=26, index_proj=5, index_scores=4, experts=10,
        head=16)
    assert round(100 * (shares["index_proj"] + shares["index_scores"])) == 9
    a_layer = 5 * tokens
    assert round(family.index_flops(hf, seqlens) / a_layer / 1e6, 1) == 4.2
    assert round(2 * family._index_params(d) / 1e6, 1) == 4.5
    assert family.index_flops(hf, [4096]) == 5 * 16 * 64 * 2 * 8_390_656
    # the kernels' products OF THE MATHEMATICS: the selected pairs'
    ff = family.flash_flops(hf, [4096])
    pair = 2 * 128 * 32 * 5 * 6_292_480
    assert (ff["fwd"], ff["dq"], ff["dkv"]) == (2 * pair, 3 * pair, 4 * pair)
    active = family.forward_flops(whole, [1]) / 2 - 2048 * 151936
    assert 2.0e9 < active < 3.5e9  # "A3B": parameters a token touches
    work = cell["kind"].work(family, hf, cell["meta"], cell["traffic"])
    assert work["tokens_per_step"] == 131072
    assert family.routed_pairs(hf, seqlens) == tokens * 8 * 5
    assert family.held_pairs(hf, seqlens) == tokens * 8 * 5 / 8
    assert family.held_pairs(hf, [4096]) / 5 / 16 == 256  # an expert a row
    # the three attention caches: 2 x 4 x 128 values of K and V and 64
    # of the indexer's key a token a layer
    assert family.kv_bytes_per_token(hf) == 5 * 2 * (1024 + 64)
    assert family.decode_bytes(hf, 4, 4096, 1) == 2 * PARAMS \
        + 4 * 5 * 2 * (4096 * 64 + 2048 * 1024)
    names = family.shapes(hf)
    assert sum(int(np.prod(s)) for s, _ in names.values()) == PARAMS
    assert sum(".indexer." in n for n in names) == 5 * 5
    assert "model.layers.4.self_attn.indexer.weights_proj.weight" in names
    assert "model.layers.0.mlp.experts.15.down_proj.weight" in names
    assert "model.layers.0.mlp.experts.16.down_proj.weight" not in names
    # the ONE tensor a layer drawn unlike the other families' (the
    # family's docstring, "The harness's weights"): every other norm is
    # 1 + N(0, 0.02)
    kinds = {name.rsplit(".", 2)[-2]: kind for name, (_, kind)
             in names.items() if name.endswith("norm.weight")}
    assert kinds == dict(input_layernorm="matrix", norm="norm",
                         post_attention_layernorm="norm", q_norm="norm",
                         k_norm="norm")


def test_the_references_shares_add_up_to_the_uncut_model(tmp_path):
    """The guide's tie of the share to the model, on the reference's
    side: one layer's ROUTED output under each of EIGHT shares of 2
    experts adds up to the routed output with all 16 held; attention
    over the selection, which every share computes alike, is counted
    once."""
    import jax
    from benchmark.families import keye_vl2 as family
    cell = run.load_cell(MANIFEST, CELL)
    hf = dict(cell["hf"], num_hidden_layers=1, num_experts=16,
              sa_config=dict(cell["hf"]["sa_config"], topk=8))
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
    # every share adds attention: summed as they are, the eight shares
    # count it eight times
    naive = sum(after(f, 2) for f in range(0, 16, 2))
    assert np.abs(naive - whole - 7 * alike).max() \
        < 2e-5 * np.abs(whole).max()


def test_cell_end_to_end(tmp_path):
    _, out = go(0, tmp_path)
    check_line(out, trace=False)


def test_cell_measured_then_traced(tmp_path):
    from realhf_tpu.obs import tracing
    cell, out = go(2, tmp_path)
    check_line(out, trace=True)
    m = out["metrics"]
    assert {"moe.held_pairs_per_s", "moe.held_load_max_over_mean",
            "mfc.train_s", "train.mfu", "interface.host_s",
            "engine.program_gb", "tokens_per_s",
            "sparse.selected_share"} <= set(m)
    # two documents of 256 a row, 192 keys a token: 93.7% of the causal
    # pairs, by the family's own count
    family = cell["family"]
    want = 100 * family.selected_pairs(256, 192) / family.visible_pairs(256)
    assert m["sparse.selected_share"]["value"] == pytest.approx(want)
    assert 93 < want < 94
    # the CPU's trace holds no device operation: the parts' readers,
    # four of the new ones among them, leave their metric out of the
    # line without raising
    quiet = set(NEW) - {"sparse.selected_share"} | {"train.attn_proj_s"}
    assert quiet <= set(cell["readers"]) and not quiet & set(m)
    profiled, synced = tracing.captures()[-2:]
    assert profiled.profile_dir is not None and synced.sync is True
    t = cell["traffic"]
    seqlens = [t["doc_len"]] * t["docs_per_step"]
    for capture in (profiled, synced):
        trains = capture.named("engine:train")
        assert len(trains) == run.TRACE_STEPS
        for span in trains:
            a = span["attributes"]
            assert (a["layer_pattern"], a["sparse_layers"],
                    a["index_heads"], a["index_dim"], a["index_topk"],
                    a["experts_held"], a["experts"], a["router"]) == (
                "s s", 2, 4, 8, 192, 4, 16, "softmax")
        assert capture.counter("index_tokens_total", role="default") \
            == run.TRACE_STEPS * sum(seqlens) * 2
        assert capture.counter("sparse_pairs_total", role="default",
                               kind="causal") == run.TRACE_STEPS * 2 * sum(
            family.visible_pairs(n) for n in seqlens)
        assert capture.counter("sparse_pairs_total", role="default",
                               kind="selected") == run.TRACE_STEPS * 2 \
            * sum(family.selected_pairs(n, 192) for n in seqlens)
        routed = capture.counter("moe_routed_pairs_total", role="default",
                                 dispatch="ragged")
        assert routed == run.TRACE_STEPS * family.routed_pairs(
            cell["hf"], seqlens)
        held = capture.counter("moe_held_pairs_total", role="default")
        assert 0.1 < held / routed < 0.4
    # the program names the part and its sub-parts in the facts the
    # capture carries: what the readers will find on the chip
    train = next(f for f in profiled.programs.values()
                 if f["module"] == "jit_train_step")
    parts = {row[0] for row in train["ops"].values()}
    assert {"index/project", "index/scores", "index/select", "attn_proj",
            "attn"} <= parts
    assert train["attributes"]["flash_mask_calls"] == 0  # the XLA path


def _capture(counters, profile_dir="x", programs=None):
    from realhf_tpu.obs import tracing
    capture = tracing.Capture(
        spans=[dict(name="step", start=0.0, end=1.0, span_id="a",
                    parent_id=None, trace_id="t", thread=0, attributes={})],
        counters=counters, start=0.0, end=1.0, sync=("compute:",),
        profile_dir=profile_dir)
    capture.programs = programs or {}
    return capture


def test_the_readers_read_the_part_its_sub_parts_and_the_counters(
        monkeypatch, tmp_path):
    """Against a constructed trace: operations of the train program
    under ``index/*`` are ``sparse.index_s``, those under
    ``index/select`` alone ``sparse.select_s``; those of another part,
    of another program and of an operation the text does not name count
    for neither. ``sparse.index_mxu_share`` is the family's FLOPs of the
    scores AS WRITTEN over the seconds of ``index/scores`` and the
    peak; ``sparse.selected_share`` the counters' growth. Nothing where
    the capture has no ``programs`` (the parent commit under these
    files); 0 (and no share) where the program has no such part."""
    from benchmark import program_parts, trace_reduce
    from realhf_tpu.obs import tracing
    cell = run.load_cell(MANIFEST, CELL)
    whole, select, share, _, picked = (cell["readers"][n] for n in NEW)
    ops = {"f.1": ["index/project", "fwd", "fusion", "forward_backward", ""],
           "f.2": ["index/scores", "fwd", "fusion", "forward_backward", ""],
           "f.3": ["index/select", "fwd", "fusion", "forward_backward", ""],
           "f.4": ["index", "fwd", "while", "forward_backward", ""],
           "f.5": ["attn", "fwd", "custom-call", "forward_backward", ""]}
    programs = {
        "train": dict(module="jit_train_step", ops=ops, memory={}),
        "other": dict(module="jit_logprobs", memory={}, ops={
            "f.1": ["index/scores", "fwd", "fusion", "prefill", ""]})}
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
    counters = {"sparse_pairs_total{kind=selected,role=default}": 30.0,
                "sparse_pairs_total{kind=causal,role=default}": 40.0}
    monkeypatch.setattr(tracing, "captures", lambda: [
        _capture(counters, str(tmp_path), programs)])
    hf = cell["hf"]
    record = dict(chips=1, family=cell["family"], hf=hf,
                  traffic=dict(doc_len=32, docs_per_step=8),
                  peaks=dict(flops=1e6))
    assert select.read(record) == pytest.approx(3.0)
    assert whole.read(record) == pytest.approx(1.0 + 2.0 + 3.0 + 4.0)
    assert picked.read(record) == pytest.approx(75.0)
    # two sparse layers, 4 index heads of 8, 8 documents of 32 tokens
    flops = 2 * 4 * 8 * 2 * 8 * (32 * 33 // 2)
    assert cell["family"].index_flops(hf, [32] * 8) == flops
    assert share.read(record) == pytest.approx(100.0 * flops / (2.0 * 1e6))
    assert share.read(dict(record, family=object())) is None
    # a program without the part: 0 seconds, and no share of nothing
    for row in ops.values():
        row[0] = "attn_proj"
    program_parts._CACHE.clear()
    assert (select.read(record), whole.read(record)) == (0.0, 0.0)
    assert share.read(record) is None
    # nothing to read: no programs or counters in the capture, no capture
    program_parts._CACHE.clear()
    monkeypatch.setattr(tracing, "captures", lambda: [
        _capture({}, str(tmp_path))])
    assert [r.read(record) for r in (whole, select, share, picked)] \
        == [None] * 4
    monkeypatch.setattr(tracing, "captures", lambda: [])
    assert [r.read(record) for r in (whole, select, share, picked)] \
        == [None] * 4
    program_parts._CACHE.clear()


def test_the_flash_share_counts_the_selected_pairs_products(monkeypatch):
    """``sparse.flash_mxu_share`` is ``flash.mxu_share``'s reading over
    THIS family's ``flash_flops``: kernels named ``flash_*_sel`` are
    found by the accepted reader's names, and the FLOPs are the
    selected pairs' alone, so the share of a layer that visits every
    causal block cannot pass 100%."""
    from benchmark import trace_reduce
    cell = real_cell()
    reader = cell["readers"]["sparse.flash_mxu_share"]
    flash = reader._flash_mxu_share()
    ops = [("%jvp_flash_fwd_sel_.3 = bf16[] custom-call(%a)", 0.0, 1.0),
           ("%flash_bwd_dq_sel.4 = f32[] custom-call(%b)", 1.0, 3.0),
           ("%flash_bwd_dkv_sel.5 = f32[] custom-call(%c)", 3.0, 6.0),
           ("%fusion.9 = f32[] fusion(%flash_bwd_dq_sel.4)", 6.0, 7.0)]
    ran = flash.kernel_seconds_and_calls(
        dict(devices={0: dict(ops=ops, modules=[])}))
    assert {k: tuple(v) for k, v in ran.items()} == dict(
        fwd=(1.0, 1), dq=(2.0, 1), dkv=(3.0, 1))
    family, hf = cell["family"], cell["hf"]
    by_math = sum(family.flash_flops(hf, [4096]).values())
    causal = 9 * 2 * 128 * 32 * 5 * family.visible_pairs(4096)
    assert by_math / causal == pytest.approx(0.75, abs=1e-3)


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
    against the family's float32 forward on rows that pass the
    indexer's ``topk``; the same forward with every matrix rounded to
    float8, with the top-k gates left as the softmax gave them, or with
    no norm on q and k, is further from the reference than the engine
    is (toy widths: the chip run sizes it,
    ``scripts/chip_check.py keye_vl2``). A key near the 192nd score
    falls in or out of the selection with bf16's rounding: what that
    alone costs is inside the tolerance."""
    import jax
    import jax.numpy as jnp

    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.models.hf import registry
    from realhf_tpu.parallel import mesh as mesh_lib

    cell = run.load_cell(MANIFEST, CELL)
    hf, family = cell["hf"], cell["family"]
    ids = generate.fixed_batch(hf, seed=7, rows=2, length=256)
    par = mesh_lib.ParallelismConfig()
    ctx = mesh_lib.MeshContext(
        ModelName("default", 0),
        mesh_lib.make_mesh(par, jax.devices()[:1]), par)
    ckpt = str(tmp_path / "ckpt")
    generate.write_checkpoint(ckpt, family, hf, seed=7)
    tensors = reference.load_tensors(ckpt)
    want = family.logprobs(hf, tensors, ids)
    cfg, params = registry.load_hf_checkpoint(ckpt, "keye_vl2")
    cfg.param_dtype = "bfloat16"
    got = np.asarray(Engine(cfg, ctx, params).forward_logprobs(
        ids, np.ones_like(ids)), np.float32)[:, :-1]
    assert got.shape == want.shape == (2, 255)
    assert reference.within_tolerance(got, want, family.TOLERANCE)
    gap, spread = reference.gap(got, want)
    assert gap < 0.01 * spread  # toy widths: far inside
    # (at toy widths a branch is a smaller part of a token's row than at
    # the cell's, where these read 0.6 to 0.9 of the spread: held to
    # twice the engine's own distance here)
    for wrong in ("gates_not_renormalised", "qk_norm_left_out"):
        off, _ = reference.gap(
            family.logprobs(hf, tensors, ids, wrong=(wrong,)), want)
        assert off > 1.2 * gap, wrong
    float8, _ = reference.gap(family.logprobs(
        hf, tensors, ids,
        cast=lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)),
        want)
    assert float8 > 3 * gap
