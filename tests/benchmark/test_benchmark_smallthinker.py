"""The ``smallthinker`` family in the benchmark, on the CPU at toy
widths: the twelfth cell's entries and configuration file against the
issue and the catalog row, its arithmetic at published widths (FLOPs,
visited block pairs, the bytes the stream kernels must move, each
against a count by hand), the reference's shares tied to the uncut
model, a tiny cell (its own manifest and configuration under
``tests/benchmark/smallthinker/``, the tests' ``tiny-sft`` traffic)
whole through ``run_cell``, and the two readers the family brings on a
recorded trace."""

import json
import os

import numpy as np
import pytest
from tiny_cells import PEAKS, check_line

from benchmark import generate, reference, run

MANIFEST = os.path.join(run.ROOT, "tests", "benchmark", "smallthinker",
                        "manifest.json")
CELL = "tiny-smallthinker.sft"
REAL = "smallthinker-21b-a3b-l4-ep8.sft-16k"
#: the cell before this one, and the per-layer metrics the manifest
#: had then: what the stale test below was last true of
BEFORE, METRICS_BEFORE = "ouro-2.6b-l6.sft-4k-x4", 59
NEW = ("flash.stream_s", "flash.stream_hbm_share")
#: the readers that were there and had this cell appended to their lists
APPENDED = (
    "train.attn_s", "train.attn_proj_s", "train.experts_s", "train.head_s",
    "train.accum_s", "train.unscoped_s", "engine.program_gb",
    "moe.held_pairs_per_s", "moe.held_load_max_over_mean",
    "flash.visited_share", "flash.mxu_share", "setup.program_s",
    "setup.import_s", "setup.data_s", "setup.weights_s",
    "setup.trace_lower_s", "setup.cache_misses", "setup.facts_s",
    "setup.first_step_s", "setup.unattributed_s")


def go(trace, tmp_path):
    cell = run.load_cell(MANIFEST, CELL)
    return cell, run.run_cell(cell, seed=2 ** 31 + 77, seconds=0.3,
                              trace=trace, work=str(tmp_path),
                              peaks=PEAKS, expect_kernels=False)


def real_cell():
    return run.load_cell(os.path.join(run.ROOT, "BENCHMARK.json"), REAL)


def test_real_manifest_names_the_cell_as_the_issue_does():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = real_cell()
    assert cell["chips"] == 1 and cell["meta"]["family"] == "smallthinker"
    assert cell["config"]["name"] == "smallthinker-21b-a3b-l4-ep8"
    assert cell["config"]["reduced"] == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout",
        "moe_num_primary_experts", "vocab_size"] \
        == list(cell["meta"]["reduced"])
    hf, t = cell["hf"], cell["traffic"]
    assert (hf["num_hidden_layers"], hf["rope_layout"],
            hf["sliding_window_layout"], hf["moe_num_primary_experts"],
            hf["expert_share"], hf["vocab_size"]) == (
        4, [0, 1, 1, 1], [0, 1, 1, 1], 8, {"of": 64, "first": 0}, 18992)
    assert hf["vocab_size"] * 8 == 151936
    assert (t["kind"], t["docs_per_step"], t["doc_len"], t["prompt_len"],
            t["docs_per_row"], t["lr"], t["steps_of_data"]) == (
        "sft", 8, 16384, 512, 1, 1e-4, 8)
    assert t["doc_len"] == hf["max_position_embeddings"]
    assert cell["meta"]["layout"] == {"chips": 1, "roles": "d1t1"}
    by = {m["name"]: m for m in manifest["per_layer"]}
    for name in APPENDED:
        assert by[name]["workloads"][-1] == REAL, name
        assert name in cell["readers"]
    for name in NEW:
        assert by[name]["workloads"] == [REAL]
        assert (by[name]["layer"], by[name]["moves"], by[name]["source"]) \
            == ("kernels", "tokens_per_s", "device_trace")
    assert (by[NEW[0]]["unit"], by[NEW[0]]["better"]) == ("s/step", "lower")
    assert (by[NEW[1]]["unit"], by[NEW[1]]["better"]) == ("%", "higher")
    # what every cell reports, and what only other cells do
    assert {"train.mfu", "mfc.train_s", "interface.host_s",
            "device.idle_share", "engine.window_compiles"} \
        <= set(cell["readers"])
    assert not {"moe.pairs_per_s", "train.mlp_s", "mfc.gen_s",
                "gen.hbm_share", "train.conv_s"} & set(cell["readers"])
    # the new entries stand at the END of their lists
    assert manifest["workloads"][-1]["name"] == REAL
    assert manifest["configs"][-1]["name"] == cell["config"]["name"]
    assert [m["name"] for m in manifest["per_layer"][-2:]] == list(NEW)
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert four == ["mistral-7b-v0.3-l4.grpo-realloc"]
    assert 0 <= hf["eos_token_id"] < hf["vocab_size"]
    assert len(manifest["workloads"][-1]["why"]) <= 200


def _as_it_stood_before(monkeypatch):
    """``json.load`` that hands out BENCHMARK.json as it stood when
    ``BEFORE`` was its newest cell: this PR's cell and configuration
    gone from every list, its two per-layer metrics cut."""
    load = json.load

    def earlier(f, **kw):
        loaded = load(f, **kw)
        if isinstance(loaded, dict) and "workloads" in loaded \
                and "configs" in loaded:
            names = [w["name"] for w in loaded["workloads"]]
            assert names[names.index(BEFORE) + 1:] == [REAL]  # appended
            kept = names[:names.index(BEFORE) + 1]
            loaded["workloads"] = [w for w in loaded["workloads"]
                                   if w["name"] in kept]
            used = {w["config"] for w in loaded["workloads"]}
            loaded["configs"] = [c for c in loaded["configs"]
                                 if c["name"] in used]
            assert [m["name"] for m in
                    loaded["per_layer"][METRICS_BEFORE:]] == list(NEW)
            del loaded["per_layer"][METRICS_BEFORE:]
            for m in loaded["per_layer"]:
                if "workloads" in m:
                    m["workloads"] = [c for c in m["workloads"]
                                      if c in kept]
        return loaded

    monkeypatch.setattr(json, "load", earlier)


def test_lagunas_manifest_test_holds_as_far_as_its_entries(monkeypatch):
    """``test_benchmark_laguna.py::
    test_real_manifest_names_the_cell_as_the_issue_does`` asserts that
    every ``flash.*`` metric lists Laguna's cell ALONE; this PR
    appended its cell to ``flash.visited_share`` and
    ``flash.mxu_share`` (the stream kernels are found by the names the
    readers look for) and added two ``flash.*`` metrics of its own.
    That file is not this PR's to edit, and ``tests/conftest.py``
    expects that one failure by name. So that nothing it held goes
    unheld, its whole body runs here on the manifest as it stood
    before this PR's entries."""
    import test_benchmark_laguna as laguna

    _as_it_stood_before(monkeypatch)
    laguna.test_real_manifest_names_the_cell_as_the_issue_does()


def test_the_stale_test_is_expected_by_name(request):
    """It is in ``tests/conftest.py``'s list (strict: the day a
    ``benchmark`` PR repairs the line its entry fails the run until it
    is taken out), and stale for the reason written there: run as it is
    it fails on the ``flash.`` line's assertion."""
    import test_benchmark_laguna as laguna
    stale = next(
        plugin._STALE_BENCHMARK_TESTS
        for plugin in request.config.pluginmanager.get_plugins()
        if hasattr(plugin, "_STALE_BENCHMARK_TESTS"))
    name = ("tests/benchmark/test_benchmark_laguna.py::"
            "test_real_manifest_names_the_cell_as_the_issue_does")
    assert name in stale and "flash." in stale[name]
    with pytest.raises(AssertionError, match="smallthinker"):
        laguna.test_real_manifest_names_the_cell_as_the_issue_does()


def test_every_width_is_the_published_one():
    """The configuration file against the catalog row the driver drew:
    every key of the row's ``config`` is in the file under the same
    name with the same value, but the five the file lists as reduced,
    and those say what was published."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    cell = real_cell()
    hf, meta = cell["hf"], cell["meta"]
    assert meta["source"] == row["source_url"] == cell["config"]["source"]
    for key, published in row["config"].items():
        if key in meta["reduced"]:
            assert meta["reduced"][key]["published"] == published, key
            assert meta["reduced"][key]["run"] == hf[key] != published
        else:
            assert hf[key] == published, key
    assert len(meta["reduced"]) == 5
    # no width is among them
    assert (hf["hidden_size"], hf["num_attention_heads"],
            hf["num_key_value_heads"], hf["head_dim"],
            hf["moe_ffn_hidden_size"], hf["sliding_window_size"],
            hf["rope_theta"], hf["max_position_embeddings"],
            hf["moe_num_active_primary_experts"]) == (
        2560, 28, 4, 128, 768, 4096, 1500000, 16384, 6)
    # the run's layers are the published layers 0 to 3: one period
    for key in ("rope_layout", "sliding_window_layout"):
        assert row["config"][key][:4] == hf[key] == [0, 1, 1, 1]
        assert row["config"][key] == [0, 1, 1, 1] * 13
    assumed = meta["assumed"]
    for n in range(1, 8):
        [text] = [v for k, v in assumed.items() if k.startswith(f"{n} ")]
        assert text.startswith(f"ASSUMED {n}:")
        assert "not confirmed against the published modelling code" in text
    for key in ("expert_share", "initializer_range", "eos_token_id",
                "model_type"):
        assert key in assumed, key
    assert "EIGHT chips share each layer" in meta["deployment"]


def test_arithmetic_at_published_widths():
    """The numbers the issue works the cell out from: 370.5 M
    parameters held (7.4 GB at 20 bytes); 573 MFLOP a token forward, of
    which attention's scores and values 271 (the NoPE full layer 117,
    each window layer 51, which would be 117 without the window), the
    projections 168, the head 97, the held experts 35; the uncut model
    is the published 21.5 B with about 3 B active."""
    cell = real_cell()
    family, hf = cell["family"], cell["hf"]
    assert family.n_params(hf) == 370_547_200
    assert round(family.n_params(hf) * 20 / 1e9, 1) == 7.4
    h, hd = hf["hidden_size"], hf["head_dim"]
    attention = h * (28 + 4 + 4) * hd + 28 * hd * h
    assert attention == 20_971_520
    assert 8 * 3 * h * 768 == 47_185_920 and h * 64 == 163_840
    with open(os.path.join(run.ROOT, cell["config"]["file"])) as f:
        published = {k: v["published"]
                     for k, v in json.load(f)["reduced"].items()}
    whole = {k: v for k, v in dict(hf, **published).items()
             if k != "expert_share"}
    assert round(family.n_params(whole) / 1e9, 1) == 21.5
    active = family.forward_flops(whole, [1]) / 2 - h * 151936
    assert 2.5e9 < active < 3.5e9  # "A3B"
    seqlens = [16384] * 8
    tokens = sum(seqlens)
    flops = family.forward_flops(hf, seqlens) / tokens
    assert round(flops / 1e6) == 573
    full = 4 * family.visible_pairs(16384) * 28 * hd / 16384
    window = 4 * family.visible_pairs(16384, 4096) * 28 * hd / 16384
    assert family.visible_pairs(16384, 4096) \
        == 4096 * 4097 // 2 + 12288 * 4096
    assert (round(full / 1e6), round(window / 1e6)) == (117, 51)
    assert int((full + 3 * window) / 1e6) == 271  # 271.6
    assert round(100 * (full + 3 * window) / flops) == 47
    assert round(4 * 2 * attention / 1e6) == 168
    assert round(2 * h * hf["vocab_size"] / 1e6) == 97
    assert round(4 * 2 * 3 * h * 768 * 6 * 8 / 64 / 1e6) == 35
    # a window layer attends 44% of a document's causal pairs
    assert round(100 * family.visible_pairs(16384, 4096)
                 / family.visible_pairs(16384)) == 44
    work = cell["kind"].work(family, hf, cell["meta"], cell["traffic"])
    assert work["tokens_per_step"] == 131072
    assert work["train_flops"] == 3 * family.forward_flops(hf, seqlens)
    assert family.routed_pairs(hf, seqlens) == tokens * 6 * 4
    assert family.held_pairs(hf, seqlens) == tokens * 6 * 4 / 8
    assert family.held_pairs(hf, [16384]) / 4 / 8 == 1536  # a held expert
    assert family.kv_bytes_per_token(hf) == 2 * 4 * 4 * 128 * 2
    assert family.decode_bytes(hf, 2, 8192, 1) == \
        2 * family.n_params(hf) + 2 * (8192 + 3 * 4096) * 2 * 4 * 128 * 2
    names = family.shapes(hf)
    assert not any("{}" in n for n in names)
    assert sum(int(np.prod(s)) for s, _ in names.values()) == \
        family.n_params(hf)


def test_block_pairs_and_stream_bytes_against_a_count_by_hand():
    """``flash_flops`` and ``flash_stream_bytes`` of ONE 16,384-token
    document through the cell's four layers, each term written out. A
    full layer visits the whole causal triangle of 64 x 32 blocks of
    256 x 512: a query block i ends in key block i // 2, 1 + i // 2
    pairs, 1,056 in all; under the window of 4096 a query block
    reaches back 4,095 tokens from its first row: 504.
    ``flash.visited_share`` reads (1,056 + 3 x 504) / (4 x 1,056) =
    60.8% there."""
    cell = real_cell()
    family, hf = cell["family"], cell["hf"]
    full = sum(1 + i // 2 for i in range(64))
    window = sum(1 + i // 2 - max(0, (256 * i - 4095) // 512)
                 for i in range(64))
    assert (full, window) == (1056, 504)
    assert family.flash_blocks(16384) == (1056, 256, 512)
    assert family.flash_blocks(16384, 4096) == (504, 256, 512)
    assert round(100 * (full + 3 * window) / (4 * full), 1) == 60.8
    pairs = full + 3 * window
    kernels = family.flash_flops(hf, [16384])
    product = 2 * 256 * 512 * 128  # one [256, 128] x [128, 512]
    assert kernels == dict(fwd=2 * product * pairs * 28,
                           dq=3 * product * pairs * 28,
                           dkv=4 * product * pairs * 28)
    assert family.flash_flops(hf, [16384] * 8)["fwd"] == 8 * kernels["fwd"]
    # bytes: 28 query heads in 4 groups of 7 (one a key/value head), a
    # group's visit of a key block fetches K and V once
    assert family.stream_heads(7) == 7 and family.stream_heads(16) == 8
    n, nq, nkv, hd, layers = 16384, 28, 4, 128, 4
    q_once = n * nq * hd * 2           # Q, O or dO in bf16
    # lse or delta as the forward writes and the dq pass reads them:
    # float32 over the kernels' 128 lanes, [B, heads, L, 128]
    stats = n * nq * 4 * 128
    kv_visit = 2 * 512 * hd * 2        # a block of K and one of V
    got = family.flash_stream_bytes(hf, [n])
    assert got["fwd"] == layers * (2 * q_once + stats) \
        + pairs * 4 * kv_visit
    assert got["dq"] == layers * (2 * q_once + 2 * stats + n * nq * hd * 4) \
        + pairs * 4 * kv_visit
    q_visit = 7 * 256 * (2 * hd * 2 + 2 * 4)  # Q, dO, lse, delta: 7 heads
    assert got["dkv"] == layers * (2 * n * nkv * hd * 2
                                   + 2 * n * nkv * hd * 4) \
        + pairs * 4 * q_visit
    # the forward: 1,055 FLOP a byte, four times the chip's ridge (240)
    assert round(kernels["fwd"] / got["fwd"]) == 1055
    # a row the whole-row kernels take streams nothing
    assert family.flash_stream_bytes(hf, [4096]) == dict(fwd=0, dq=0, dkv=0)
    assert family.flash_stream_bytes(hf, [4096, n]) == got


def test_the_kernels_count_the_blocks_the_family_counts():
    """``flash.mxu_share`` and ``flash.stream_hbm_share`` divide the
    family's products and bytes by the kernels' seconds, so the
    family's count of block pairs, made from the mask's definition, has
    to be the kernels' own (``block_counts``; the dkv pass's ranges
    too), and its heads a step and its streaming limit theirs."""
    from benchmark.families import smallthinker as family
    from realhf_tpu.ops import flash_attention as fa
    for row, window in ((16384, 4096), (16384, None), (8192, 4096),
                        (4096, 4096), (1024, 100)):
        want, bq, bk = family.flash_blocks(row, window)
        seg = np.ones((1, row), np.int32)
        assert fa.block_counts(seg, sliding_window=window)[0] == want
        assert (bq, bk) == fa._blocks(row, fa.DEFAULT_BQ, fa.DEFAULT_BK)
        (q_lo, q_hi) = fa.block_ranges(seg, bq, bk, xp=np,
                                       sliding_window=window)[1]
        assert int((q_hi - q_lo).sum()) == want
    assert (family.STREAM_ABOVE, family.STREAM_HEADS) == (
        fa.FLASH_MAX_LEN, fa.STREAM_HEADS)
    for group in (1, 2, 6, 7, 8, 14, 16):
        assert family.stream_heads(group) == fa.stream_heads(group)


def test_the_references_shares_add_up_to_the_uncut_model(tmp_path):
    """The guide's tie of the share to the model, on the reference's
    side: one layer's routed output under each of eight shares of 1
    expert adds up to the routed output with all 8 held (what every
    share computes alike, the attention and the residual, counted
    once)."""
    from benchmark.families import smallthinker as family
    cell = run.load_cell(MANIFEST, CELL)
    hf = dict(cell["hf"], num_hidden_layers=1, rope_layout=[0],
              sliding_window_layout=[0], moe_num_primary_experts=8)
    del hf["expert_share"]
    ckpt = str(tmp_path / "ckpt")
    generate.write_checkpoint(ckpt, family, hf, seed=5)
    tensors = reference.load_tensors(ckpt)
    ids = generate.fixed_batch(hf, seed=5, rows=2, length=32)
    get = family._getter(tensors, None)

    def layer(hf_):
        import jax
        with jax.default_matmul_precision("highest"):
            x, _ = family._blocks(hf_, get, ids)
        return np.asarray(x)

    whole = layer(hf)
    alike = layer(dict(hf, moe_num_primary_experts=0,
                       expert_share={"of": 8, "first": 0}))
    parts = [layer(dict(hf, moe_num_primary_experts=1,
                        expert_share={"of": 8, "first": f})) - alike
             for f in range(8)]
    assert np.abs(whole - alike).max() > 1e-4
    assert np.abs(alike + sum(parts) - whole).max() \
        < 2e-5 * np.abs(whole).max()
    # summed as they are, the shares count what they share eight times
    naive = sum(p + alike for p in parts)
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
            "moe.pairs_per_s", "mfc.train_s", "train.mfu",
            "interface.host_s", "tokens_per_s"} <= set(m)
    # the CPU's rows go to no flash kernel: the engine counts no
    # blocks, the trace holds no kernel, and the four readers leave
    # their metric out of the line without raising
    flash = {"flash.visited_share", "flash.mxu_share", *NEW}
    assert flash <= set(cell["readers"]) and not flash & set(m)
    profiled, synced = tracing.captures()[-2:]
    assert profiled.profile_dir is not None and synced.sync is True
    t = cell["traffic"]
    seqlens = [t["doc_len"]] * t["docs_per_step"]
    for capture in (profiled, synced):
        trains = capture.named("engine:train")
        assert len(trains) == run.TRACE_STEPS
        for span in trains:
            a = span["attributes"]
            assert (a["layer_pattern"], a["window"], a["window_layers"],
                    a["nope_layers"], a["router_input"], a["router"],
                    a["experts_held"], a["experts"]) == (
                "a w w w", 8, 3, 1, "layer_input", "softmax", 2, 8)
        routed = capture.counter("moe_routed_pairs_total", role="default",
                                 dispatch="dense")
        assert routed == run.TRACE_STEPS * cell["family"].routed_pairs(
            cell["hf"], seqlens)
        held = capture.counter("moe_held_pairs_total", role="default")
        assert 0.1 < held / routed < 0.45
    assert not any(k.startswith("flash_") for k in profiled.counters)


def _capture(counters, profile_dir="x", steps=2):
    from realhf_tpu.obs import tracing
    spans = [dict(name="step", start=float(i), end=i + 1.0,
                  span_id=f"s{i}", parent_id=None, trace_id="t", thread=0,
                  attributes={}) for i in range(steps)]
    return tracing.Capture(
        spans=spans, counters=counters, start=0.0, end=float(steps),
        sync=("compute:",), profile_dir=profile_dir)


def test_the_stream_readers_on_a_recorded_trace(monkeypatch, tmp_path):
    """Both new readers against a constructed trace: two steps of two
    rows of 16,384 through the four layers, each kernel once a (layer,
    row) and the forward kernel a second time in HALF of them (as a
    backward that made it again would), a whole-row kernel beside them
    (another model's, not streamed), a kernel nested in a ``while``
    whose own time must not count, a fusion that names a stream kernel
    among its OPERANDS, as a v5e trace's whole-HLO-line names do."""
    from benchmark import trace_reduce
    from realhf_tpu.obs import tracing
    cell = run.load_cell(MANIFEST, CELL)
    seconds, share = (cell["readers"][name] for name in NEW)
    hf = real_cell()["hf"]
    traffic = dict(doc_len=16384, docs_per_row=1, docs_per_step=2)
    ops, t = [("%while.1 = while(...)", 0.0, 1000.0)], 0.0
    calls = 2 * 2 * 4  # steps x rows x layers
    for i in range(calls):
        for name, secs in (("flash_fwd_stream", 1.0),
                           ("flash_bwd_dq_stream", 2.0),
                           ("flash_bwd_dkv_stream", 3.0),
                           ("flash_fwd", 7.0), ("fusion", 5.0)) \
                + ((("jvp_flash_fwd_stream_", 1.0),) if i % 2 else ()):
            ops.append((f"%{name}.7 = f32[] custom-call("
                        "%flash_bwd_dq_stream.6)", t, t + secs))
            t += secs
    trace = dict(devices={0: dict(ops=ops, modules=[])}, spans=[])
    profile = tmp_path / "plugins" / "profile" / "x"
    profile.mkdir(parents=True)
    (profile / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda path: trace)
    monkeypatch.setattr(tracing, "captures",
                        lambda: [_capture({}, str(tmp_path))])
    record = dict(family=cell["family"], hf=hf, traffic=traffic,
                  peaks=dict(hbm_bw=1e9, flops=1e12), chips=1)
    secs = calls * (1.0 + 2.0 + 3.0) + calls // 2 * 1.0
    assert seconds.read(record) == pytest.approx(secs / 2)
    step = cell["family"].flash_stream_bytes(hf, [16384, 16384])
    moved = 2 * (1.5 * step["fwd"] + step["dq"] + step["dkv"])
    assert share.read(record) == pytest.approx(
        100.0 * moved / (secs * 1e9))
    assert 0 < share.read(record) < 100
    # flash.mxu_share beside them finds the stream kernels by the names
    # it looks for (and the whole-row kernel too: in a real cell a row
    # takes one kind or the other)
    mxu = cell["readers"]["flash.mxu_share"]
    assert mxu.read(record) > 0
    # nothing to read: a family that counts no such bytes, no stream
    # kernel in the trace, no trace file, no capture, no control
    assert share.read(dict(record, family=object())) is None
    assert seconds.read(dict(record, family=object())) == \
        pytest.approx(secs / 2)
    trace["devices"][0]["ops"] = [
        op for op in ops if "_stream" not in op[0].partition(" = ")[0]]
    assert seconds.read(record) is None and share.read(record) is None
    (profile / "host.xplane.pb").unlink()
    assert seconds.read(record) is None and share.read(record) is None
    monkeypatch.setattr(tracing, "captures", lambda: [])
    assert seconds.read(record) is None and share.read(record) is None
    monkeypatch.delattr(tracing, "captures")
    assert seconds.read(record) is None and share.read(record) is None


def test_reference_holds_the_engine_and_a_wrong_model_fails(tmp_path):
    """The tiny cell's checkpoint through the program's loader in bf16
    against the family's float32 forward; the same forward with the
    router after attention, or with every matrix rounded to float8, is
    outside the family's tolerance (toy widths: the chip run sizes it,
    ``scripts/chip_check.py smallthinker``)."""
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

    cfg, params = registry.load_hf_checkpoint(ckpt, "smallthinker")
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
        family.logprobs(hf, tensors, ids, wrong=("router_after_attention",)),
        want, family.TOLERANCE)
    float8 = family.logprobs(
        hf, tensors, ids,
        cast=lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32))
    assert not reference.within_tolerance(float8, want, family.TOLERANCE)
    # the reference's own block-of-queries path (a document past
    # QUERY_BLOCK) is the one-block path's function
    long = generate.fixed_batch(hf, seed=8, rows=1,
                                length=2 * family.QUERY_BLOCK)
    blocked = family.logprobs(hf, tensors, long)
    old, family.QUERY_BLOCK = family.QUERY_BLOCK, 4 * family.QUERY_BLOCK
    try:
        whole = family.logprobs(hf, tensors, long)
    finally:
        family.QUERY_BLOCK = old
    assert np.abs(blocked - whole).max() < 2e-5
