"""The ``deepseek_v3`` family (Moonlight-16B-A3B) in the benchmark, on
the CPU at toy widths: the seventh cell's entries and configuration
file against the issue and the catalog row, its arithmetic at published
widths, the reference's shares tied to the uncut model with the shared
experts counted once, a tiny cell (its own manifest and configuration
under ``tests/benchmark/deepseek_v3/``, the tests' ``tiny-sft``
traffic) whole through ``run_cell``, the two readers the family brings,
and the two tests of accepted entries whose last line this PR's
appended entries made stale, each run WHOLE on the manifest as far as
the entries it was written for.

Nothing here says where in its lists an entry stands or how long they
are (``in``, never ``[-1]`` or ``== n``): a later PR appends to them.
"""

import json
import os

import numpy as np
import pytest
from tiny_cells import PEAKS, check_line

from benchmark import generate, reference, run

MANIFEST = os.path.join(run.ROOT, "tests", "benchmark", "deepseek_v3",
                        "manifest.json")
CELL = "tiny-deepseek-v3.sft"
REAL = "moonlight-16b-a3b-l5-ep8.sft-4k"
CONFIG = "moonlight-16b-a3b-l5-ep8"
#: the accepted per-layer lists this PR appended its cell to
APPENDED = ("moe.held_pairs_per_s", "moe.held_load_max_over_mean",
            "train.attn_s", "train.attn_proj_s", "train.mlp_s",
            "train.experts_s", "train.head_s", "train.accum_s",
            "train.unscoped_s", "engine.program_gb")
NEW = ("mla.flash_mxu_share", "mla.latent_s")
#: parameters in matrices (ISSUE 37's arithmetic), and beside them:
#: two norms a layer and the latent's, the final norm, four selection
#: biases over 64 experts
MATRICES = 568_459_264
SMALL = 5 * (2 * 2048 + 512) + 2048 + 4 * 64


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
    assert cell["chips"] == 1 and cell["meta"]["family"] == "deepseek_v3"
    assert cell["config"]["name"] == CONFIG
    assert cell["config"]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"] \
        == list(cell["meta"]["reduced"])
    assert cell["config"]["file"] == f"benchmark/configs/{CONFIG}.json"
    hf, t = cell["hf"], cell["traffic"]
    assert (hf["num_hidden_layers"], hf["first_k_dense_replace"],
            hf["n_routed_experts"], hf["expert_share"],
            hf["vocab_size"]) == (5, 1, 8, {"of": 64, "first": 0}, 20480)
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
    # expert held, generation, convolutions, collectives, and Laguna's
    # two flash readers, whose lists stay pinned to its cell
    assert not {"moe.pairs_per_s", "moe.load_max_over_mean", "mfc.gen_s",
                "gen.hbm_share", "train.conv_s", "train.collective_s",
                "flash.mxu_share", "flash.visited_share"} \
        & set(cell["readers"])
    by = {m["name"]: m for m in manifest["per_layer"]}
    for name in APPENDED:
        assert REAL in by[name]["workloads"], name
    for name, layer, source in (
            ("mla.flash_mxu_share", "kernels", "device_trace"),
            ("mla.latent_s", "model", "device_trace")):
        assert by[name]["workloads"] == [REAL]
        assert (by[name]["layer"], by[name]["moves"],
                by[name]["source"]) == (layer, "tokens_per_s", source)
    assert (by["mla.flash_mxu_share"]["unit"],
            by["mla.flash_mxu_share"]["better"]) == ("%", "higher")
    assert (by["mla.latent_s"]["unit"],
            by["mla.latent_s"]["better"]) == ("s/step", "lower")
    # one four-chip cell still: a second is refused under eight cells
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert four == ["mistral-7b-v0.3-l4.grpo-realloc"]
    assert 0 <= hf["eos_token_id"] < hf["vocab_size"]


def _as_far_as(monkeypatch, last_cell, metrics=None):
    """``json.load`` that hands out BENCHMARK.json as it stood when
    ``last_cell`` was its newest cell: later cells and configurations
    gone from every list, the per-layer metrics cut to the first
    ``metrics`` (where given)."""
    load = json.load

    def earlier(f, **kw):
        loaded = load(f, **kw)
        if isinstance(loaded, dict) and "workloads" in loaded \
                and "configs" in loaded:
            names = [w["name"] for w in loaded["workloads"]]
            assert REAL in names[names.index(last_cell) + 1:]  # appended
            kept = names[:names.index(last_cell) + 1]
            loaded["workloads"] = [w for w in loaded["workloads"]
                                   if w["name"] in kept]
            used = {w["config"] for w in loaded["workloads"]}
            loaded["configs"] = [c for c in loaded["configs"]
                                 if c["name"] in used]
            if metrics is not None:
                assert set(NEW) <= {m["name"] for m in
                                    loaded["per_layer"][metrics:]}
                del loaded["per_layer"][metrics:]
            for m in loaded["per_layer"]:
                if "workloads" in m:
                    m["workloads"] = [c for c in m["workloads"]
                                      if c in kept]
        return loaded

    monkeypatch.setattr(json, "load", earlier)


def test_lagunas_manifest_test_holds_as_far_as_its_cell(monkeypatch):
    """``test_benchmark_laguna.py::
    test_lfm2s_manifest_test_holds_as_far_as_its_cell`` asserts that
    LFM2's and Laguna's cells are the manifest's LAST TWO, which
    stopped being so when this PR appended the seventh, where a new
    entry has to go; that file is not this PR's to edit, and
    ``tests/conftest.py`` expects that one failure by name. So that
    nothing it held goes unheld, its whole body runs here on the
    manifest as far as Laguna's cell: the order of the two cells, and
    inside it every assertion of LFM2's own manifest test."""
    import test_benchmark_laguna as laguna

    _as_far_as(monkeypatch, laguna.REAL)
    laguna.test_lfm2s_manifest_test_holds_as_far_as_its_cell(monkeypatch)


def test_the_parts_manifest_test_holds_as_far_as_its_entries(monkeypatch):
    """``test_benchmark_parts.py::
    test_manifest_gains_the_fourteen_at_its_end_and_nothing_else``
    asserts that the manifest has 34 per-layer metrics and PR 35's
    fourteen are its LAST; this PR appended two. Its whole body runs
    here on the manifest as far as PR 35's entries (six cells, 34
    metrics): the fourteen's order, ``train.attn_s`` on every cell,
    ``train.experts_s`` on cells 3 on, and the rest."""
    import test_benchmark_laguna as laguna
    import test_benchmark_parts as parts

    _as_far_as(monkeypatch, laguna.REAL, metrics=34)
    parts.test_manifest_gains_the_fourteen_at_its_end_and_nothing_else()


def test_the_two_stale_tests_are_expected_by_name(request):
    """Both are in ``tests/conftest.py``'s list (strict: the day a
    ``benchmark`` PR repairs a line its entry fails the run until it is
    taken out), beside PR 33's one; and each is stale for the reason
    written there, not for another: run as they are they fail on the
    named line's assertion."""
    import test_benchmark_laguna as laguna
    import test_benchmark_parts as parts
    # (``import conftest`` is whichever directory's a worker met first)
    stale = next(
        plugin._STALE_BENCHMARK_TESTS
        for plugin in request.config.pluginmanager.get_plugins()
        if hasattr(plugin, "_STALE_BENCHMARK_TESTS"))
    assert {
        "tests/benchmark/test_benchmark_laguna.py::"
        "test_lfm2s_manifest_test_holds_as_far_as_its_cell",
        "tests/benchmark/test_benchmark_parts.py::"
        "test_manifest_gains_the_fourteen_at_its_end_and_nothing_else",
        "tests/benchmark/test_benchmark_lfm2.py::"
        "test_real_manifest_names_the_cell_as_the_issue_does"} <= set(stale)
    assert all(len(reason) > 40 for reason in stale.values())
    with pytest.raises(AssertionError):
        parts.test_manifest_gains_the_fourteen_at_its_end_and_nothing_else()
    with pytest.MonkeyPatch.context() as patch, \
            pytest.raises(AssertionError):
        laguna.test_lfm2s_manifest_test_holds_as_far_as_its_cell(patch)


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
                   if r["name"] == "Moonlight-16B-A3B")
    cell = real_cell()
    hf, meta = cell["hf"], cell["meta"]
    assert meta["source"] == row["source_url"] == cell["config"]["source"]
    for key, published in row["config"].items():
        if key in meta["reduced"]:
            assert meta["reduced"][key]["published"] == published, key
            assert meta["reduced"][key]["run"] == hf[key] != published
        else:
            assert hf[key] == published, key
    assert sorted(meta["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    # the guide's floors: the lead and four layers after it, eight
    # routed experts, an eighth of the vocabulary
    assert hf["num_hidden_layers"] - hf["first_k_dense_replace"] == 4
    assert hf["n_routed_experts"] == 8
    assert hf["vocab_size"] * 8 == row["config"]["vocab_size"]
    for key in ("rope_interleave", "initializer_range", "eos_token_id",
                "expert_share", "kv_a_layernorm"):
        assert key in meta["assumed"], key
    assert set(hf) - set(row["config"]) == {
        "rope_interleave", "initializer_range", "eos_token_id",
        "expert_share"}
    assert "EIGHT chips share each layer" in meta["deployment"]
    assert "20 bytes a parameter" in meta["deployment"]


def test_arithmetic_at_published_widths():
    """The numbers the issue works the cell out from: 568,459,264
    parameters in matrices (11.37 GB at 20 bytes), 656 MFLOP a token
    forward of which the five latent layers 37% (projections 27.5,
    scores and values 21 MFLOP a layer), shared + held experts 29%, the
    dense lead 21%, the head 13%; the kernels' products at 192 for a
    score and 128 for a value; the uncut model is the published 16 B
    with 3 B active."""
    cell = real_cell()
    family, hf = cell["family"], cell["hf"]
    assert family.n_matrix_params(hf) == MATRICES
    assert family.n_params(hf) == MATRICES + SMALL
    assert round(MATRICES * 20 / 1e9, 2) == 11.37
    with open(os.path.join(run.ROOT, cell["config"]["file"])) as f:
        published = {k: v["published"]
                     for k, v in json.load(f)["reduced"].items()}
    whole = {k: v for k, v in dict(hf, **published).items()
             if k != "expert_share"}
    assert round(family.n_params(whole) / 1e9, 1) == 16.0
    # the next sizes up do not fit: a fifth sparse layer, or 16 experts
    assert round(family.n_matrix_params(
        dict(hf, num_hidden_layers=6)) * 20 / 1e9, 1) == 13.4
    assert round(family.n_matrix_params(
        dict(hf, n_routed_experts=16)) * 20 / 1e9, 1) == 16.9
    seqlens = [4096] * 32
    tokens = sum(seqlens)
    flops = family.forward_flops(hf, seqlens) / tokens
    assert round(flops / 1e6) == 656
    h = hf["hidden_size"]
    projections = 2 * (h * 16 * 192 + h * 576 + 512 * 16 * 256 + 16 * 128 * h)
    assert projections == 2 * 13_762_560 and round(projections / 1e6, 1) == 27.5
    scores = 2 * family.visible_pairs(4096) * 16 * (192 + 128) / 4096
    assert round(scores / 1e6) == 21
    assert round(100 * 5 * (projections + scores) / flops) == 37
    experts = 4 * 2 * (h * 64 + 3 * h * 2816 + 3 * h * 1408 * 6 * 8 / 64)
    assert round(100 * experts / flops) == 29
    assert round(100 * 6 * h * hf["intermediate_size"] / flops) == 21
    assert round(100 * 2 * h * hf["vocab_size"] / flops) == 13
    active = family.forward_flops(whole, [1]) / 2 - h * 163840
    assert 2.0e9 < active < 3.5e9  # "A3B": parameters a token touches
    work = cell["kind"].work(family, hf, cell["meta"], cell["traffic"])
    assert work["tokens_per_step"] == 131072
    assert round(work["train_flops"] / tokens / 1e9, 2) == 1.97
    assert family.routed_pairs(hf, seqlens) == tokens * 6 * 4
    assert family.held_pairs(hf, seqlens) == tokens * 6 * 4 / 8
    assert family.held_pairs(hf, [4096]) / 4 / 8 == 384  # an expert a row
    # the kernels' blocks: 16 x 8 of 256 x 512 in a row of 4096
    assert family.flash_blocks(4096) == (72, 256, 512)
    assert family.flash_blocks(256) == (1, 256, 256)
    kernels = family.flash_flops(hf, [4096])
    pair = 2 * 256 * 512 * 72 * 16 * 5
    assert kernels == dict(fwd=pair * (192 + 128),
                           dq=pair * (192 + 128 + 192),
                           dkv=pair * (192 + 128 + 128 + 192))
    # the cache the program keeps against the latent row it could keep
    assert family.kv_bytes_per_token(hf) == 5 * 16 * (192 + 128) * 2
    assert family.kv_bytes_per_token(hf, latent=True) == 5 * 576 * 2
    assert family.decode_bytes(hf, 4, 1024, 1) == \
        2 * family.n_params(hf) + 4 * 1024 * 5 * 16 * 320 * 2
    names = family.shapes(hf)
    assert not any("{}" in n for n in names)
    assert sum(int(np.prod(s)) for s, _ in names.values()) == \
        family.n_params(hf)
    assert sum(n.endswith("e_score_correction_bias") for n in names) == 4
    assert "model.layers.0.mlp.gate.weight" not in names  # the dense lead


def test_the_kernels_count_the_blocks_the_family_counts():
    """``mla.flash_mxu_share`` divides the family's products by the
    kernels' seconds, so the family's count of block pairs, made from
    the mask's definition, has to be the kernels' own
    (``block_counts``)."""
    from benchmark.families import deepseek_v3 as family
    from realhf_tpu.ops import flash_attention as fa
    for row in (4096, 2048, 1024, 512, 256):
        want, bq, bk = family.flash_blocks(row)
        seg = np.ones((1, row), np.int32)
        assert fa.block_counts(seg)[0] == want
        assert (bq, bk) == fa._blocks(row, fa.DEFAULT_BQ, fa.DEFAULT_BK)
        (q_lo, q_hi) = fa.block_ranges(seg, bq, bk, xp=np)[1]
        assert int((q_hi - q_lo).sum()) == want  # the dkv pass too
    assert (family.FLASH_BQ, family.FLASH_BK) == (fa.DEFAULT_BQ,
                                                  fa.DEFAULT_BK)


def test_the_references_shares_add_up_to_the_uncut_model(tmp_path):
    """The guide's tie of the share to the model, on the reference's
    side: one sparse layer's ROUTED output under each of EIGHT shares
    of 2 experts adds up to the routed output with all 16 held; the
    shared experts, which every share computes alike, are counted
    once."""
    from benchmark.families import deepseek_v3 as family
    cell = run.load_cell(MANIFEST, CELL)
    hf = dict(cell["hf"], num_hidden_layers=2, n_routed_experts=16)
    del hf["expert_share"]
    ckpt = str(tmp_path / "ckpt")
    generate.write_checkpoint(ckpt, family, hf, seed=5)
    tensors = reference.load_tensors(ckpt)
    ids = generate.fixed_batch(hf, seed=5, rows=2, length=32)
    get = family._getter(tensors, None)

    def last_layer_adds(hf_, wrong=()):
        """x after the model less x before its last layer's experts
        (routed and shared)."""
        import jax
        with jax.default_matmul_precision("highest"):
            x, _ = family._blocks(hf_, get, ids, wrong=wrong)
            neither, _ = family._blocks(
                dict(hf_, n_routed_experts=0,
                     expert_share={"of": 16, "first": 0}), get, ids,
                wrong=("shared_experts_left_out",))
        return np.asarray(x - neither)

    whole = last_layer_adds(hf)
    shares = [dict(hf, n_routed_experts=2,
                   expert_share={"of": 16, "first": f})
              for f in range(0, 16, 2)]
    routed = sum(last_layer_adds(s, wrong=("shared_experts_left_out",))
                 for s in shares)
    shared = whole - last_layer_adds(hf, wrong=("shared_experts_left_out",))
    assert np.abs(whole).max() > 1e-3 and np.abs(shared).max() > 1e-4
    assert np.abs(routed + shared - whole).max() < 2e-5 * np.abs(whole).max()
    # every share adds the whole shared experts: summed as they are,
    # the eight shares count them eight times
    naive = sum(last_layer_adds(s) for s in shares)
    assert np.abs(naive - whole - 7 * shared).max() \
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
            "moe.pairs_per_s", "mfc.train_s", "train.mfu",
            "interface.host_s", "engine.program_gb",
            "tokens_per_s"} <= set(m)
    # the CPU's trace holds no device operation and its rows go to no
    # flash kernel: both new readers, and the parts' readers, leave
    # their metric out of the line without raising
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
            assert (a["layer_pattern"], a["latent_layers"],
                    a["kv_lora_rank"], a["qk_dim"], a["v_dim"],
                    a["shared_expert"], a["experts_held"], a["experts"],
                    a["router"]) == (
                "l l l l l", 5, 24, 24, 12, 32, 4, 16, "sigmoid_bias")
        routed = capture.counter("moe_routed_pairs_total", role="default",
                                 dispatch="ragged")
        assert routed == run.TRACE_STEPS * cell["family"].routed_pairs(
            cell["hf"], seqlens)
        held = capture.counter("moe_held_pairs_total", role="default")
        assert 0.1 < held / routed < 0.4
    # the program names the latent's sub-part in the facts the capture
    # carries: what ``mla.latent_s`` will find on the chip
    parts = {row[0] for facts in profiled.programs.values()
             for row in facts["ops"].values()}
    assert {"attn_proj/latent", "attn_proj", "attn"} <= parts


def _capture(counters, profile_dir="x", programs=None):
    from realhf_tpu.obs import tracing
    capture = tracing.Capture(
        spans=[dict(name="step", start=0.0, end=1.0, span_id="a",
                    parent_id=None, trace_id="t", thread=0, attributes={})],
        counters=counters, start=0.0, end=1.0, sync=("compute:",),
        profile_dir=profile_dir)
    capture.programs = programs or {}
    return capture


def test_latent_s_reads_the_sub_part_alone(monkeypatch, tmp_path):
    """``mla.latent_s`` against a constructed trace: operations of the
    train program under ``attn_proj/latent`` in three passes count,
    those of ``attn_proj`` itself, of another program and of an
    operation the text does not name do not; ``train.attn_proj_s``
    holds both. Nothing where the capture has no ``programs`` (the
    parent commit under these files); 0 where the program has no such
    sub-part."""
    from benchmark import program_parts, trace_reduce
    from realhf_tpu.obs import tracing
    cell = run.load_cell(MANIFEST, CELL)
    reader = cell["readers"]["mla.latent_s"]
    whole = cell["readers"]["train.attn_proj_s"]
    ops = {"dot.1": ["attn_proj/latent", "fwd", "dot", "forward_backward", ""],
           "dot.2": ["attn_proj/latent", "remat", "dot", "forward_backward", ""],
           "dot.3": ["attn_proj/latent", "bwd", "dot", "forward_backward", ""],
           "dot.4": ["attn_proj", "fwd", "dot", "forward_backward", ""],
           "dot.5": ["mlp", "fwd", "dot", "forward_backward", ""]}
    programs = {
        "train": dict(module="jit_train_step", ops=ops, memory={}),
        "other": dict(module="jit_logprobs", memory={}, ops={
            "dot.1": ["attn_proj/latent", "fwd", "dot", "prefill", ""]})}
    names = [("jit_train_step", f"dot.{i}", float(i)) for i in range(1, 7)] \
        + [("jit_logprobs", "dot.1", 100.0)]
    t, events, modules = 0.0, [], []
    for module, op, secs in names:
        events.append((f"%{op} = f32[] dot(%x)", t, t + secs))
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
    record = dict(chips=1)
    assert reader.read(record) == pytest.approx(1.0 + 2.0 + 3.0)
    assert whole.read(record) == pytest.approx(1.0 + 2.0 + 3.0 + 4.0)
    # a program without the sub-part: 0, not nothing
    for row in ops.values():
        row[0] = row[0].split("/")[0]
    program_parts._CACHE.clear()
    assert reader.read(record) == 0.0
    # nothing to read: no programs in the capture, no capture
    program_parts._CACHE.clear()
    monkeypatch.setattr(tracing, "captures", lambda: [
        _capture({}, str(tmp_path))])
    assert reader.read(record) is None
    monkeypatch.setattr(tracing, "captures", lambda: [])
    assert reader.read(record) is None
    program_parts._CACHE.clear()


def test_flash_mxu_share_counts_the_mathematics(monkeypatch, tmp_path):
    """``mla.flash_mxu_share`` against a constructed trace: two steps
    of two rows through a two-layer latent stack, the forward kernel
    once a backward; the share is the family's FLOPs at 192 for a score
    and 128 for a value over the kernels' own seconds. Nothing where
    the trace holds no kernel, there is no trace or no capture."""
    from benchmark import trace_reduce
    from realhf_tpu.obs import tracing
    cell = run.load_cell(MANIFEST, CELL)
    reader = cell["readers"]["mla.flash_mxu_share"]
    hf = dict(cell["hf"], num_hidden_layers=2)
    traffic = dict(doc_len=256, docs_per_row=1, docs_per_step=2)
    ops, t = [], 0.0
    for _ in range(2 * 2 * 2):  # steps x rows x layers
        for name, secs in (("jvp_flash_fwd_", 1.0), ("flash_bwd_dq", 2.0),
                           ("flash_bwd_dkv", 3.0), ("fusion", 5.0)):
            ops.append((f"%{name}.7 = f32[] custom-call(%flash_bwd_dq.6)",
                        t, t + secs))
            t += secs
    trace = dict(devices={0: dict(ops=ops, modules=[])}, spans=[])
    profile = tmp_path / "plugins" / "profile" / "x"
    profile.mkdir(parents=True)
    (profile / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda path: trace)
    monkeypatch.setattr(tracing, "captures",
                        lambda: [_capture({}, str(tmp_path))])
    record = dict(family=cell["family"], hf=hf, traffic=traffic,
                  peaks=dict(flops=1e9), chips=1)
    step = cell["family"].flash_flops(hf, [256, 256])
    width = dict(fwd=24 + 12, dq=24 + 12 + 24, dkv=24 + 12 + 12 + 24)
    assert step == {k: 2 * 256 * 256 * 1 * 4 * 2 * 2 * w
                    for k, w in width.items()}
    want = 2 * (step["fwd"] + step["dq"] + step["dkv"])
    assert reader.read(record) == pytest.approx(
        100.0 * want / (8 * 6.0 * 1e9))
    trace["devices"][0]["ops"] = []
    assert reader.read(record) is None
    (profile / "host.xplane.pb").unlink()
    assert reader.read(record) is None
    monkeypatch.setattr(tracing, "captures", lambda: [])
    assert reader.read(record) is None
    assert reader.read(dict(record, family=object())) is None


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
    against the family's float32 forward; the same forward with the
    gates not scaled, or with every matrix rounded to float8, is
    outside the family's tolerance (toy widths: the chip run sizes it,
    ``scripts/chip_check.py deepseek_v3``)."""
    import jax
    import jax.numpy as jnp

    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.models.hf import registry
    from realhf_tpu.parallel import mesh as mesh_lib

    cell = run.load_cell(MANIFEST, CELL)
    hf, family = cell["hf"], cell["family"]
    ckpt = str(tmp_path / "ckpt")
    generate.write_checkpoint(ckpt, family, hf, seed=7)
    ids = generate.fixed_batch(hf, seed=7, rows=2, length=64)
    tensors = reference.load_tensors(ckpt)
    want = family.logprobs(hf, tensors, ids)

    cfg, params = registry.load_hf_checkpoint(ckpt, "deepseek_v3")
    cfg.param_dtype = "bfloat16"
    par = mesh_lib.ParallelismConfig()
    ctx = mesh_lib.MeshContext(
        ModelName("default", 0),
        mesh_lib.make_mesh(par, jax.devices()[:1]), par)
    got = np.asarray(Engine(cfg, ctx, params).forward_logprobs(
        ids, np.ones_like(ids)), np.float32)[:, :-1]
    assert got.shape == want.shape == (2, 63)
    assert reference.within_tolerance(got, want, family.TOLERANCE)
    gap, spread = reference.gap(got, want)
    assert gap < 0.01 * spread  # toy widths: far inside

    assert not reference.within_tolerance(
        family.logprobs(hf, tensors, ids, wrong=("gates_not_scaled",)),
        want, family.TOLERANCE)
    float8 = family.logprobs(
        hf, tensors, ids,
        cast=lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32))
    assert not reference.within_tolerance(float8, want, family.TOLERANCE)
