"""The ``lfm2_moe`` family in the benchmark, on the CPU at toy widths:
the reference held to ``transformers``' ``Lfm2ForCausalLM`` for what
that file carries (conv, attention, dense feed-forward, layer order),
the shares of a sparse layer tied to the uncut layer, the cell's
arithmetic at published widths, a tiny cell (its own manifest and
configuration under ``tests/benchmark/lfm2/``, the tests' ``tiny-sft``
traffic) whole through ``run_cell``, and the two readers the family
brings."""

import json
import os

import numpy as np
import pytest
from tiny_cells import PEAKS, check_line

from benchmark import generate, reference, run

MANIFEST = os.path.join(run.ROOT, "tests", "benchmark", "lfm2",
                        "manifest.json")
CELL = "tiny-lfm2.sft"
REAL = "lfm2-24b-a2b-l5-ep8.sft"


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
    assert cell["chips"] == 1 and cell["meta"]["family"] == "lfm2_moe"
    assert cell["config"]["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"] == list(cell["meta"]["reduced"])
    hf, t = cell["hf"], cell["traffic"]
    assert (hf["num_hidden_layers"], hf["layer_types"],
            hf["num_dense_layers"], hf["num_experts"], hf["expert_share"],
            hf["vocab_size"]) == (
        5, ["conv", "full_attention", "conv", "conv", "conv"], 1, 8,
        {"of": 64, "first": 0}, 8192)
    assert (t["kind"], t["docs_per_step"], t["doc_len"], t["prompt_len"],
            t["docs_per_row"], t["lr"]) == ("sft", 64, 1024, 256, 4, 1e-4)
    assert {"moe.held_pairs_per_s", "moe.held_load_max_over_mean",
            "train.mfu", "mfc.train_s", "interface.host_s",
            "device.idle_share"} <= set(cell["readers"])
    assert not {"moe.pairs_per_s", "moe.load_max_over_mean", "mfc.gen_s",
                "gen.hbm_share"} & set(cell["readers"])
    assert [w["name"] for w in manifest["workloads"]][-1] == REAL
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert four == ["mistral-7b-v0.3-l4.grpo-realloc"]
    assert 0 <= hf["eos_token_id"] < hf["vocab_size"]


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
                   if r["name"] == "LFM2-24B-A2B")
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
    # the run's layers are the published layers 1 to 5, one period
    assert row["config"]["layer_types"][1:6] == hf["layer_types"]
    for key in ("expert_share", "tie_word_embeddings", "initializer_range",
                "eos_token_id", "sparse block", "tensor names"):
        assert key in meta["assumed"], key
    assert "EIGHT chips share each layer" in meta["deployment"]


def test_arithmetic_at_published_widths():
    """The numbers the issue works the cell out from: 469 M parameters
    held (9.4 GB at 20 bytes), 377 MFLOP a token forward of which the
    conv operators 36%, the dense lead 38%, the held experts 10%; the
    uncut model is the published 24 B with 2.3 B active."""
    cell = real_cell()
    family, hf = cell["family"], cell["hf"]
    assert family.n_params(hf) == 469_285_248
    assert round(family.n_params(hf) * 20 / 1e9, 1) == 9.4
    with open(os.path.join(run.ROOT, cell["config"]["file"])) as f:
        published = {k: v["published"]
                     for k, v in json.load(f)["reduced"].items()}
    whole = {k: v for k, v in dict(hf, **published).items()
             if k != "expert_share"}
    assert round(family.n_params(whole) / 1e9, 1) == 23.8
    seqlens = [1024] * 64
    flops = family.forward_flops(hf, seqlens) / sum(seqlens)
    assert round(flops / 1e6) == 376
    h = hf["hidden_size"]
    assert round(100 * 4 * 8 * h * h / flops) == 36
    assert round(100 * 6 * h * hf["intermediate_size"] / flops) == 38
    experts = 4 * 6 * h * hf["moe_intermediate_size"] * 4 * 8 / 64
    assert round(100 * experts / flops) == 10
    active = family.forward_flops(whole, [1]) / 2 - h * 65536
    assert 2.0e9 < active < 2.6e9  # "A2B": parameters a token touches
    work = cell["kind"].work(family, hf, cell["meta"], cell["traffic"])
    assert work["tokens_per_step"] == 65536
    assert round(work["train_flops"] / 1e12, 1) == 74.0
    assert family.routed_pairs(hf, seqlens) == 65536 * 4 * 4
    assert family.held_pairs(hf, seqlens) == 65536 * 4 * 4 / 8
    # K and V for one layer of five; two rows of state a conv layer
    assert family.kv_bytes_per_token(hf) == 2 * 1 * 8 * 64 * 2
    assert family.conv_state_bytes(hf, 128) == 4 * 128 * 2 * 2048 * 2
    assert family.decode_bytes(hf, 128, 256, 1) == \
        2 * family.n_params(hf) + 128 * 256 * family.kv_bytes_per_token(hf) \
        + 2 * family.conv_state_bytes(hf, 128)
    # explicit names, none standing for every layer
    names = family.shapes(hf)
    assert not any("{}" in n for n in names)
    assert sum(int(np.prod(s)) for s, _ in names.values()) == \
        family.n_params(hf)


DENSE = dict(
    hidden_size=64, intermediate_size=96, num_hidden_layers=4,
    layer_types=["conv", "full_attention", "conv", "conv"],
    num_attention_heads=4, num_key_value_heads=2, vocab_size=128,
    norm_eps=1e-5, rope_theta=1000000.0, conv_L_cache=3, conv_bias=False,
    max_position_embeddings=256, tie_word_embeddings=True,
    block_auto_adjust_ff_dim=False)


def test_reference_matches_transformers():
    """Conv, attention, dense feed-forward and the order of a layer are
    ``modeling_lfm2.py``'s, which the ``transformers`` installed here
    carries: the reference with every layer dense gives
    ``Lfm2ForCausalLM``'s logits on weights it saved under its own
    names (norm scales moved off 1). ``lfm2_moe`` itself is not in this
    ``transformers``: the sparse block is from memory, and is held to a
    loop over tokens in ``tests/ops/test_moe.py``."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from benchmark.families import lfm2_moe as family
    torch.manual_seed(3)
    model = transformers.Lfm2ForCausalLM(
        transformers.Lfm2Config(**DENSE)).eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if "norm" in name:
                p.add_(0.2 * torch.randn_like(p))
    state = {k: v.detach().numpy() for k, v in model.state_dict().items()
             if k != "lm_head.weight"}
    # as lfm2_moe's config would say the same model: every layer dense
    hf = dict(DENSE, model_type="lfm2_moe", num_dense_layers=4,
              moe_intermediate_size=32, num_experts=4,
              num_experts_per_tok=2)
    assert {k: v.shape for k, v in state.items()} == {
        k: shape for k, (shape, _) in family.shapes(hf).items()}
    docs = np.random.default_rng(4).integers(0, 128, size=(2, 24))
    with torch.no_grad():
        want = model(input_ids=torch.from_numpy(docs)).logits.numpy()
    got = family.logits(hf, state, docs.astype(np.int32))
    assert np.abs(got - want).max() < 1e-5
    assert want.std() > 0.1
    # and what the configuration file lists as assumed is what
    # transformers' Lfm2Config defaults to
    default = transformers.Lfm2Config()
    assert (default.tie_word_embeddings, default.initializer_range,
            default.norm_eps, default.conv_L_cache, default.conv_bias) == (
        True, 0.02, 1e-5, 3, False)


def test_the_references_shares_add_up_to_the_uncut_model(tmp_path):
    """The guide's tie of the share to the model, on the reference's
    side: one sparse layer's output under each of four shares of 4
    experts (what the embedding and the operator compute alike counted
    once) adds up to the layer's output with all 16 held."""
    from benchmark.families import lfm2_moe as family
    cell = run.load_cell(MANIFEST, CELL)
    hf = dict(cell["hf"], num_hidden_layers=2,
              layer_types=["conv", "full_attention"], num_dense_layers=1,
              num_experts=16)
    del hf["expert_share"]
    ckpt = str(tmp_path / "ckpt")
    generate.write_checkpoint(ckpt, family, hf, seed=5)
    tensors = reference.load_tensors(ckpt)
    ids = generate.fixed_batch(hf, seed=5, rows=2, length=32)
    get = family._getter(tensors, None)

    def last_layer_adds(hf_):
        """x after the model less x before its last layer's experts."""
        import jax
        with jax.default_matmul_precision("highest"):
            x, _ = family._blocks(hf_, get, ids)
            no_experts, _ = family._blocks(
                dict(hf_, num_experts=0,
                     expert_share={"of": 16, "first": 0}), get, ids)
        return np.asarray(x - no_experts)

    whole = last_layer_adds(hf)
    parts = sum(last_layer_adds(
        dict(hf, num_experts=4, expert_share={"of": 16, "first": first}))
        for first in (0, 4, 8, 12))
    assert np.abs(whole).max() > 1e-3
    # float32: each side is a difference of residual streams of 0.05
    assert np.abs(parts - whole).max() < 1e-5 * np.abs(whole).max()


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
    assert m["moe.held_pairs_per_s"]["unit"] == "Mpairs/s/chip"
    assert 0 < m["moe.held_pairs_per_s"]["value"] \
        < m["moe.pairs_per_s"]["value"]
    # 4 of 16 experts held, 4 a token: between nothing on the busiest
    # held expert and every pair of a row on it
    assert 0.0 < m["moe.held_load_max_over_mean"]["value"] <= 4.0

    profiled, synced = tracing.captures()[-2:]
    assert profiled.profile_dir is not None and synced.sync is True
    t = cell["traffic"]
    seqlens = [t["doc_len"]] * t["docs_per_step"]
    for capture in (profiled, synced):
        trains = capture.named("engine:train")
        assert len(trains) == run.TRACE_STEPS
        for span in trains:
            a = span["attributes"]
            assert (a["layer_pattern"], a["conv_layers"],
                    a["experts_held"], a["experts"], a["top_k"],
                    a["router"]) == ("c a c c c", 4, 4, 16, 4,
                                     "sigmoid_bias")
        routed = capture.counter("moe_routed_pairs_total", role="default",
                                 dispatch="ragged")
        assert routed == run.TRACE_STEPS * cell["family"].routed_pairs(
            cell["hf"], seqlens)
        assert capture.counter("conv_tokens_total", role="default") == \
            run.TRACE_STEPS * sum(seqlens) * 4
        held = capture.counter("moe_held_pairs_total", role="default")
        assert held == sum(s["attributes"]["moe_held_pairs"]
                           for s in trains)
        # a quarter of the experts: near a quarter of the pairs
        assert 0.1 < held / routed < 0.4
    secs = sum(s["end"] - s["start"] for s in synced.named("engine:train"))
    assert m["moe.held_pairs_per_s"]["value"] == pytest.approx(
        synced.counter("moe_held_pairs_total", role="default")
        / secs / 1e6)


def test_readers_return_nothing_without_a_capture(monkeypatch):
    """On a program without the counter (the parent commit, a model
    that holds every expert), or before anything was traced, the metric
    is left out of the line and nothing raises."""
    from realhf_tpu.obs import tracing
    cell = run.load_cell(MANIFEST, CELL)
    readers = [cell["readers"][n] for n in (
        "moe.held_pairs_per_s", "moe.held_load_max_over_mean")]
    record = dict(chips=1)
    monkeypatch.setattr(tracing, "captures", lambda: [])
    assert [r.read(record) for r in readers] == [None, None]
    whole = tracing.Capture(
        spans=[dict(name="engine:train", start=0.0, end=1.0, span_id="a",
                    parent_id=None, trace_id="t", thread=0,
                    attributes={"moe_load_max_over_mean": 2.0})],
        counters={"moe_routed_pairs_total{dispatch=ragged,role=x}": 64.0},
        start=0.0, end=1.0, sync=True, profile_dir="x")
    monkeypatch.setattr(tracing, "captures", lambda: [whole])
    assert [r.read(record) for r in readers] == [None, None]
    monkeypatch.delattr(tracing, "captures")
    assert [r.read(record) for r in readers] == [None, None]


def test_reference_holds_the_engine_and_a_wrong_model_fails(tmp_path):
    """The tiny cell's checkpoint through the program's loader in bf16
    against the family's float32 forward; the same forward with the
    query/key norm over the whole width, or with every matrix rounded
    to float8, is outside the family's tolerance (toy widths: the chip
    run sizes it, ``scripts/chip_check_lfm2.py``)."""
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

    cfg, params = registry.load_hf_checkpoint(ckpt, "lfm2_moe")
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
        family.logprobs(hf, tensors, ids, wrong=("whole_width_qk_norm",)),
        want, family.TOLERANCE)
    float8 = family.logprobs(
        hf, tensors, ids,
        cast=lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32))
    assert not reference.within_tolerance(float8, want, family.TOLERANCE)
