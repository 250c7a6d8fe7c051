"""The ``laguna`` family in the benchmark, on the CPU at toy widths:
the sixth cell's entries and configuration file against the issue and
the catalog row, its arithmetic at published widths, the reference's
shares tied to the uncut model with the shared expert counted once, a
tiny cell (its own manifest and configuration under
``tests/benchmark/laguna/``, the tests' ``tiny-sft`` traffic) whole
through ``run_cell``, and the two readers the family brings."""

import json
import os

import numpy as np
import pytest
from tiny_cells import PEAKS, check_line

from benchmark import generate, reference, run

MANIFEST = os.path.join(run.ROOT, "tests", "benchmark", "laguna",
                        "manifest.json")
CELL = "tiny-laguna.sft"
REAL = "laguna-xs.2-l5-ep16.sft-4k"


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
    assert cell["chips"] == 1 and cell["meta"]["family"] == "laguna"
    assert cell["config"]["name"] == "laguna-xs.2-l5-ep16"
    assert cell["config"]["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size"] \
        == list(cell["meta"]["reduced"])
    hf, t = cell["hf"], cell["traffic"]
    assert (hf["num_hidden_layers"], hf["layer_types"],
            hf["mlp_layer_types"], hf["num_attention_heads_per_layer"],
            hf["num_experts"], hf["expert_share"], hf["vocab_size"]) == (
        5, ["full_attention"] + ["sliding_attention"] * 3
        + ["full_attention"], ["dense"] + ["sparse"] * 4,
        [48, 64, 64, 64, 48], 16, {"of": 256, "first": 0}, 12544)
    assert (t["kind"], t["docs_per_step"], t["doc_len"], t["prompt_len"],
            t["docs_per_row"], t["lr"], t["steps_of_data"]) == (
        "sft", 32, 4096, 512, 1, 1e-4, 8)
    assert cell["meta"]["layout"] == {"chips": 1, "roles": "d1t1"}
    assert {"flash.visited_share", "flash.mxu_share", "train.mfu",
            "mfc.train_s", "interface.host_s",
            "device.idle_share"} <= set(cell["readers"])
    # the held experts' two readers had this cell appended to their
    # lists (16 of 256 held: the counters run here); OLMoE's, which
    # holds every expert, and generation's did not
    assert {"moe.held_pairs_per_s", "moe.held_load_max_over_mean"} \
        <= set(cell["readers"])
    assert not {"moe.pairs_per_s", "moe.load_max_over_mean", "mfc.gen_s",
                "gen.hbm_share"} & set(cell["readers"])
    for metric in manifest["per_layer"]:
        if metric["name"].startswith("flash."):
            assert metric["workloads"] == [REAL]
            assert (metric["layer"], metric["moves"]) == (
                "kernels", "tokens_per_s")
        if metric["name"].startswith("moe.held_"):
            assert metric["workloads"][:2] == [
                "lfm2-24b-a2b-l5-ep8.sft", REAL]
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert four == ["mistral-7b-v0.3-l4.grpo-realloc"]
    # (nothing here says where in its lists the cell stands or how long
    # they are: a later PR appends to them)
    assert 0 <= hf["eos_token_id"] < hf["vocab_size"]


def test_lfm2s_manifest_test_holds_as_far_as_its_cell(monkeypatch):
    """``test_benchmark_lfm2.py``'s test of PR 31's entries ends on
    "its cell is the manifest's LAST", which stopped being so when this
    PR appended the sixth, where a new entry has to go; that file is
    not this PR's to edit, and ``tests/conftest.py`` expects that one
    failure by name. So that nothing else the test held goes unheld,
    its whole body runs here on the manifest as far as PR 31's cell:
    every assertion of it, the two after the stale line too."""
    import test_benchmark_lfm2 as lfm2

    load = json.load

    def as_far_as_lfm2s_cell(f, **kw):
        loaded = load(f, **kw)
        if isinstance(loaded, dict) and "workloads" in loaded \
                and "configs" in loaded:
            names = [w["name"] for w in loaded["workloads"]]
            assert names[-2:] == [lfm2.REAL, REAL]  # appended, in order
            del loaded["workloads"][names.index(lfm2.REAL) + 1:]
        return loaded

    monkeypatch.setattr(json, "load", as_far_as_lfm2s_cell)
    lfm2.test_real_manifest_names_the_cell_as_the_issue_does()


def test_every_width_is_the_published_one():
    """The configuration file against the catalog row the driver drew:
    every key of the row's ``config`` is in the file under the same
    name with the same value, but the six the file lists as reduced,
    and those say what was published."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-XS.2")
    cell = real_cell()
    hf, meta = cell["hf"], cell["meta"]
    assert meta["source"] == row["source_url"] == cell["config"]["source"]
    for key, published in row["config"].items():
        if key in meta["reduced"]:
            assert meta["reduced"][key]["published"] == published, key
            assert meta["reduced"][key]["run"] == hf[key] != published
        else:
            assert hf[key] == published, key
    assert len(meta["reduced"]) == 6
    # the run's layers are the published layers 0 to 4: the dense lead
    # and one whole period
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert row["config"][key][:5] == hf[key]
    assumed = meta["assumed"]
    for n in range(1, 7):
        [text] = [v for k, v in assumed.items() if k.startswith(f"{n} ")]
        assert text.startswith(f"ASSUMED {n}:")
        assert "not confirmed against the published modelling code" in text
    for key in ("expert_share", "initializer_range", "eos_token_id"):
        assert key in assumed, key
    assert "SIXTEEN chips share each layer" in meta["deployment"]


def test_arithmetic_at_published_widths():
    """The numbers the issue works the cell out from: 490.3 M
    parameters held (9.8 GB at 20 bytes), 687 MFLOP a token forward of
    which attention's projections 50%, its scores 21.5% (the three
    window layers 6.9%), the dense lead 14.7%, the head 7.5%, the
    sparse feed-forwards 6.1%; a window layer visits 30 of a row's 72
    causal block pairs; the uncut model is the published 33 B with 3 B
    active."""
    cell = real_cell()
    family, hf = cell["family"], cell["hf"]
    assert family.n_params(hf) == 490_297_344
    assert round(family.n_params(hf) * 20 / 1e9, 1) == 9.8
    with open(os.path.join(run.ROOT, cell["config"]["file"])) as f:
        published = {k: v["published"]
                     for k, v in json.load(f)["reduced"].items()}
    whole = {k: v for k, v in dict(hf, **published).items()
             if k != "expert_share"}
    assert round(family.n_params(whole) / 1e9, 1) == 33.4
    seqlens = [4096] * 32
    tokens = sum(seqlens)
    flops = family.forward_flops(hf, seqlens) / tokens
    assert round(flops / 1e6) == 687
    h, hd = hf["hidden_size"], hf["head_dim"]
    heads = hf["num_attention_heads_per_layer"]
    projections = sum(2 * (h * (n + 16) * hd + n * hd * h) for n in heads)
    assert round(100 * projections / flops, 1) == 50.1
    window = 4 * family.visible_pairs(4096, 512) * 64 * hd / 4096
    full = 4 * family.visible_pairs(4096) * 48 * hd / 4096
    assert family.visible_pairs(4096, 512) == 512 * 513 // 2 + 3584 * 512
    assert round(100 * (3 * window + 2 * full) / flops, 1) == 21.5
    assert round(100 * 3 * window / flops, 1) == 6.9
    assert round(100 * 6 * h * hf["intermediate_size"] / flops, 1) == 14.7
    assert round(100 * 2 * h * hf["vocab_size"] / flops, 1) == 7.5
    sparse = 4 * 2 * (h * 256 + 3 * h * 512 + 3 * h * 512 * 8 * 16 / 256)
    assert round(100 * sparse / flops, 1) == 6.1
    active = family.forward_flops(whole, [1]) / 2 - h * 100352
    assert 2.5e9 < active < 3.5e9  # "A3B": parameters a token touches
    work = cell["kind"].work(family, hf, cell["meta"], cell["traffic"])
    assert work["tokens_per_step"] == 131072
    assert round(work["train_flops"] / tokens / 1e9, 2) == 2.06
    assert family.routed_pairs(hf, seqlens) == tokens * 8 * 4
    assert family.held_pairs(hf, seqlens) == tokens * 8 * 4 / 16
    # the kernels' blocks: 16 x 8 of 256 x 512 in a row of 4096
    assert family.flash_blocks(4096) == (72, 256, 512)
    assert family.flash_blocks(4096, 512) == (30, 256, 512)
    assert family.flash_blocks(4096, 4096)[0] == 72
    assert family.flash_blocks(256, 512) == (1, 256, 256)
    kernels = family.flash_flops(hf, [4096])
    pair = 2 * 256 * 512 * hd
    assert kernels["fwd"] == 2 * pair * (3 * 30 * 64 + 2 * 72 * 48)
    assert (kernels["dq"], kernels["dkv"]) == (
        kernels["fwd"] * 3 // 2, kernels["fwd"] * 2)
    # every layer keeps K and V; a window layer streams 512 rows of them
    assert family.kv_bytes_per_token(hf) == 2 * 5 * 8 * 128 * 2
    assert family.decode_bytes(hf, 4, 1024, 1) == \
        2 * family.n_params(hf) + 4 * (2 * 1024 + 3 * 512) * 2 * 8 * 128 * 2
    names = family.shapes(hf)
    assert not any("{}" in n for n in names)
    assert sum(int(np.prod(s)) for s, _ in names.values()) == \
        family.n_params(hf)


def test_the_kernels_count_the_blocks_the_family_counts():
    """``flash.mxu_share`` divides the family's products by the
    kernels' seconds, so the family's count of block pairs, made from
    the mask's definition, has to be the kernels' own
    (``block_counts``), with and without a window."""
    from benchmark.families import laguna as family
    from realhf_tpu.ops import flash_attention as fa
    for row, window in ((4096, 512), (4096, None), (2048, 512),
                        (1024, 100), (512, 1), (256, 512)):
        want, bq, bk = family.flash_blocks(row, window)
        seg = np.ones((1, row), np.int32)
        assert fa.block_counts(seg, sliding_window=window)[0] == want
        assert (bq, bk) == fa._blocks(row, fa.DEFAULT_BQ, fa.DEFAULT_BK)
        (q_lo, q_hi) = fa.block_ranges(seg, bq, bk, xp=np,
                                       sliding_window=window)[1]
        assert int((q_hi - q_lo).sum()) == want  # the dkv pass too


def test_the_references_shares_add_up_to_the_uncut_model(tmp_path):
    """The guide's tie of the share to the model, on the reference's
    side: one sparse layer's ROUTED output under each of four shares of
    4 experts adds up to the routed output with all 16 held; the shared
    expert, which every share computes alike, is counted once."""
    from benchmark.families import laguna as family
    cell = run.load_cell(MANIFEST, CELL)
    hf = dict(cell["hf"], num_hidden_layers=2, num_experts=16,
              layer_types=["full_attention", "sliding_attention"],
              mlp_layer_types=["dense", "sparse"],
              num_attention_heads_per_layer=[4, 6])
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
                dict(hf_, num_experts=0,
                     expert_share={"of": 16, "first": 0}), get, ids,
                wrong=("shared_expert_left_out",))
        return np.asarray(x - neither)

    whole = last_layer_adds(hf)
    shares = [dict(hf, num_experts=4, expert_share={"of": 16, "first": f})
              for f in (0, 4, 8, 12)]
    routed = sum(last_layer_adds(s, wrong=("shared_expert_left_out",))
                 for s in shares)
    shared = whole - last_layer_adds(hf, wrong=("shared_expert_left_out",))
    assert np.abs(whole).max() > 1e-3 and np.abs(shared).max() > 1e-4
    assert np.abs(routed + shared - whole).max() < 2e-5 * np.abs(whole).max()
    # every share adds the whole shared expert: summed as they are, the
    # shares count it four times
    naive = sum(last_layer_adds(s) for s in shares)
    assert np.abs(naive - whole - 3 * shared).max() \
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
    # blocks, the trace holds no kernel, and both readers leave their
    # metric out of the line without raising
    assert {"flash.visited_share", "flash.mxu_share"} <= set(cell["readers"])
    assert not {"flash.visited_share", "flash.mxu_share"} & set(m)
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
                    a["q_heads"], a["shared_expert"], a["experts_held"],
                    a["experts"], a["router"]) == (
                "a w w w a", 8, 3, "4 6 6 6 4", 32, 4, 16, "sigmoid")
        routed = capture.counter("moe_routed_pairs_total", role="default",
                                 dispatch="ragged")
        assert routed == run.TRACE_STEPS * cell["family"].routed_pairs(
            cell["hf"], seqlens)
        held = capture.counter("moe_held_pairs_total", role="default")
        assert 0.1 < held / routed < 0.4
    assert not any(k.startswith("flash_kv_blocks_total")
                   for k in profiled.counters)


def _capture(counters, profile_dir="x"):
    from realhf_tpu.obs import tracing
    return tracing.Capture(
        spans=[dict(name="engine:train", start=0.0, end=1.0, span_id="a",
                    parent_id=None, trace_id="t", thread=0, attributes={})],
        counters=counters, start=0.0, end=1.0, sync=("compute:",),
        profile_dir=profile_dir)


def test_visited_share_reads_the_counters_growth(monkeypatch):
    from realhf_tpu.obs import tracing
    reader = run.load_cell(MANIFEST, CELL)["readers"]["flash.visited_share"]
    counters = {
        "flash_kv_blocks_total{kind=visited,role=default}": 2 * 234.0,
        "flash_kv_blocks_total{kind=causal,role=default}": 2 * 360.0,
        "moe_routed_pairs_total{dispatch=ragged,role=x}": 64.0}
    monkeypatch.setattr(tracing, "captures", lambda: [_capture(counters)])
    # (3 x 30 + 2 x 72) / (5 x 72): the sixth cell's expected reading
    assert reader.read(dict(chips=1)) == pytest.approx(65.0)


def test_mxu_share_counts_calls_and_own_seconds(monkeypatch, tmp_path):
    """The reader against a constructed trace: two steps of two rows
    through a two-layer stack, the forward kernel run twice a layer
    (rematerialisation, once under ``jvp_flash_fwd_``'s name), a kernel
    nested in a ``while`` whose own time must not count, an unrelated
    fusion that names a kernel among its operands, as a v5e trace's
    whole-HLO-line names do."""
    from benchmark import trace_reduce
    from realhf_tpu.obs import tracing
    cell = run.load_cell(MANIFEST, CELL)
    reader = cell["readers"]["flash.mxu_share"]
    hf = dict(cell["hf"], num_hidden_layers=2,
              layer_types=["full_attention", "sliding_attention"],
              mlp_layer_types=["dense", "sparse"],
              num_attention_heads_per_layer=[4, 6], sliding_window=64)
    traffic = dict(doc_len=128, docs_per_row=2, docs_per_step=4)
    ops, t = [("%while.1 = while(...)", 0.0, 100.0)], 0.0
    for _ in range(2 * 2 * 2):  # steps x rows x layers
        for name, secs in (("jvp_flash_fwd_", 1.0), ("flash_fwd", 1.0),
                           ("flash_bwd_dq", 2.0), ("flash_bwd_dkv", 3.0),
                           ("fusion", 5.0)):
            # a consumer names the kernel among its OPERANDS: not a call
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
    want = 2 * (2 * step["fwd"] + step["dq"] + step["dkv"])
    assert reader.read(record) == pytest.approx(
        100.0 * want / (8 * 7.0 * 1e9))
    # nothing to read: no kernel in the trace, no trace, no capture, a
    # family that counts no such FLOPs
    trace["devices"][0]["ops"] = ops[:1]
    assert reader.read(record) is None
    (profile / "host.xplane.pb").unlink()
    assert reader.read(record) is None
    monkeypatch.setattr(tracing, "captures", lambda: [])
    assert reader.read(record) is None
    assert reader.read(dict(record, family=object())) is None
    monkeypatch.delattr(tracing, "captures")
    assert reader.read(record) is None
    visited = cell["readers"]["flash.visited_share"]
    assert visited.read(dict(chips=1)) is None


def test_reference_holds_the_engine_and_a_wrong_model_fails(tmp_path):
    """The tiny cell's checkpoint through the program's loader in bf16
    against the family's float32 forward; the same forward with the
    gate left out, or with every matrix rounded to float8, is outside
    the family's tolerance (toy widths: the chip run sizes it,
    ``scripts/chip_check.py laguna``)."""
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

    cfg, params = registry.load_hf_checkpoint(ckpt, "laguna")
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
        family.logprobs(hf, tensors, ids, wrong=("gate_left_out",)),
        want, family.TOLERANCE)
    float8 = family.logprobs(
        hf, tensors, ids,
        cast=lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32))
    assert not reference.within_tolerance(float8, want, family.TOLERANCE)
