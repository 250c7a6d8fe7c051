"""The ``olmoe`` family in the benchmark, on the CPU at toy widths: a
tiny OLMoE cell (its own manifest and configuration under
``tests/benchmark/olmoe/``, the tests' ``tiny-sft`` traffic) runs whole
through ``run_cell``, the family's reference holds the engine, and the
two readers this family brings return a number from the program's
capture and nothing without one."""

import json
import os

import numpy as np
import pytest
from tiny_cells import PEAKS, check_line

from benchmark import generate, reference, run

MANIFEST = os.path.join(run.ROOT, "tests", "benchmark", "olmoe",
                        "manifest.json")
CELL = "tiny-olmoe.sft"


def go(trace, tmp_path):
    cell = run.load_cell(MANIFEST, CELL)
    return cell, run.run_cell(cell, seed=2 ** 31 + 77, seconds=0.3,
                              trace=trace, work=str(tmp_path),
                              peaks=PEAKS, expect_kernels=False)


def test_real_manifest_names_the_cell_as_the_issue_does():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = run.load_cell(os.path.join(run.ROOT, "BENCHMARK.json"),
                         "olmoe-1b-7b-0125-l1.sft-2k")
    assert cell["chips"] == 1 and cell["meta"]["family"] == "olmoe"
    assert cell["config"]["reduced"] == ["num_hidden_layers"] \
        == list(cell["meta"]["reduced"])
    hf, t = cell["hf"], cell["traffic"]
    assert (hf["hidden_size"], hf["intermediate_size"], hf["num_experts"],
            hf["num_experts_per_tok"], hf["num_attention_heads"],
            hf["num_key_value_heads"], hf["vocab_size"],
            hf["norm_topk_prob"], hf["num_hidden_layers"]) == (
        2048, 1024, 64, 8, 16, 16, 50304, False, 1)
    assert (t["kind"], t["docs_per_step"], t["doc_len"], t["prompt_len"],
            t["docs_per_row"], t["lr"], t["steps_of_data"]) == (
        "sft", 32, 2048, 256, 1, 1e-4, 8)
    assert {"moe.pairs_per_s", "moe.load_max_over_mean", "train.mfu",
            "mfc.train_s", "interface.host_s",
            "device.idle_share"} <= set(cell["readers"])
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert four == ["mistral-7b-v0.3-l4.grpo-realloc"]


def test_arithmetic_at_published_widths():
    """The numbers the issue works the cell out from: one layer of
    419.6 M parameters, 626 M with embedding and head; 349 MFLOP a
    token forward, of which the head 59%, 8 experts 29%."""
    cell = run.load_cell(os.path.join(run.ROOT, "BENCHMARK.json"),
                         "olmoe-1b-7b-0125-l1.sft-2k")
    family, hf = cell["family"], cell["hf"]
    assert family.n_params(hf) == 625_616_896
    assert family.n_params(dict(hf, num_hidden_layers=16)) \
        == 6_919_161_856  # the published 6.92 B
    seqlens = [2048] * 32
    flops = family.forward_flops(hf, seqlens)
    assert round(flops / sum(seqlens) / 1e6) == 349
    assert round(100 * family.head_share(hf, seqlens)) == 59
    work = cell["kind"].work(family, hf, cell["meta"], cell["traffic"])
    assert work["tokens_per_step"] == 65536
    assert round(work["train_flops"] / 1e12, 1) == 68.6
    assert family.routed_pairs(hf, seqlens) == 65536 * 8
    # decoding streams every expert once a step: the whole model
    assert family.decode_bytes(hf, 128, 256, 1) == \
        2 * family.n_params(hf) + 128 * 256 * family.kv_bytes_per_token(hf)


def test_cell_end_to_end(tmp_path):
    _, out = go(0, tmp_path)
    check_line(out, trace=False)


def test_cell_measured_then_traced(tmp_path):
    from realhf_tpu.obs import tracing
    cell, out = go(2, tmp_path)
    check_line(out, trace=True)
    m = out["metrics"]
    assert {"moe.pairs_per_s", "moe.load_max_over_mean", "mfc.train_s",
            "train.mfu", "interface.host_s", "tokens_per_s"} <= set(m)
    assert m["moe.pairs_per_s"]["value"] > 0
    assert m["moe.pairs_per_s"]["unit"] == "Mpairs/s/chip"
    # 8 experts, 2 a token, rows of 128 tokens: at least even, at most
    # every pair on one expert
    assert 1.0 <= m["moe.load_max_over_mean"]["value"] <= 4.0

    profiled, synced = tracing.captures()[-2:]
    assert profiled.profile_dir is not None and synced.sync is True
    for capture in (profiled, synced):
        trains = capture.named("engine:train")
        assert len(trains) == run.TRACE_STEPS
        for span in trains:
            a = span["attributes"]
            assert (a["moe_dispatch"], a["experts"], a["top_k"]) == (
                "ragged", 8, 2)
            assert a["moe_load_max_over_mean"] >= 1.0
        # what the program counted on the host is what the family's
        # arithmetic gives for the steps' documents
        t = cell["traffic"]
        want = run.TRACE_STEPS * cell["family"].routed_pairs(
            cell["hf"], [t["doc_len"]] * t["docs_per_step"])
        assert capture.counter("moe_routed_pairs_total", role="default",
                               dispatch="ragged") == want
        [load] = capture.gauges.values()
        assert load == trains[-1]["attributes"]["moe_load_max_over_mean"]
    secs = sum(s["end"] - s["start"] for s in synced.named("engine:train"))
    assert m["moe.pairs_per_s"]["value"] == pytest.approx(
        want / secs / 1e6)


def test_readers_return_nothing_without_a_capture(monkeypatch):
    """On a program without the counter, or before anything was
    traced, the metric is left out of the line and nothing raises."""
    from realhf_tpu.obs import tracing
    cell = run.load_cell(MANIFEST, CELL)
    monkeypatch.setattr(tracing, "captures", lambda: [])
    record = dict(chips=1)
    assert cell["readers"]["moe.pairs_per_s"].read(record) is None
    assert cell["readers"]["moe.load_max_over_mean"].read(record) is None
    # a capture of a dense model's steps: spans, but no pairs, no load
    dense = tracing.Capture(
        spans=[dict(name="engine:train", start=0.0, end=1.0, span_id="a",
                    parent_id=None, trace_id="t", thread=0,
                    attributes={})],
        counters={}, start=0.0, end=1.0, sync=True, profile_dir="x")
    monkeypatch.setattr(tracing, "captures", lambda: [dense])
    assert cell["readers"]["moe.pairs_per_s"].read(record) is None
    assert cell["readers"]["moe.load_max_over_mean"].read(record) is None


def test_reference_holds_the_engine_and_a_lower_precision_fails(tmp_path):
    """The tiny cell's checkpoint through the program's loader in bf16
    against the family's float32 forward; the same forward with the
    gates renormalised, or with every matrix rounded to float8, is
    outside the family's tolerance."""
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

    cfg, params = registry.load_hf_checkpoint(ckpt, "olmoe")
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

    renormed = family.logprobs(dict(hf, norm_topk_prob=True), tensors, ids)
    assert not reference.within_tolerance(renormed, want, family.TOLERANCE)
    float8 = family.logprobs(
        hf, tensors, ids,
        cast=lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32))
    assert not reference.within_tolerance(float8, want, family.TOLERANCE)
