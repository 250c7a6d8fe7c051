"""LFM2-MoE held to its plain reference
(``benchmark/families/lfm2_moe.py``) on the CPU: small widths that keep
the shape of the benchmark's cell (hidden 64, 4/2 heads of 16, a dense
conv layer of width 96, then attention, conv, conv, conv with 16
experts of width 32, 4 a token), seeded random weights under Hugging
Face's names (``benchmark/generate.py`` makes them, the program's own
loader reads them), everything in float32. Two checkpoints: one that
holds every expert (the uncut model) and one expert-parallel rank's
share (experts 4 to 7 of 16).

Every comparison is float32 against float32 on the same values, so the
tolerances are those of two orders of summation: the packed forward's
largest |delta logit| is 3.6e-7 on logits of spread 0.18. ``LOGIT_TOL``
is 30 times that and 80 to 10,000 times under what each wrong equation
gives (tried once, PR 31, largest |delta logit| at the share): softmax
in place of sigmoid 0.021, the bias left out of the choice 0.022, gates
not renormalised 0.030, whole-width query/key norm 0.10, the taps
reversed 0.0044, the convolution crossing a document boundary 0.00083.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import generate, reference
from benchmark.families import lfm2_moe as family
from realhf_tpu.api.config import ModelName
from realhf_tpu.engine.engine import Engine
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.interfaces import sft
from realhf_tpu.models import hf as hf_models
from realhf_tpu.models import transformer as T
from realhf_tpu.models.hf import registry
from realhf_tpu.parallel import mesh as mesh_lib
from realhf_tpu.models.operators import n_params

#: max |delta logit| allowed between the program and the reference
LOGIT_TOL = 1e-5

_BASE = dict(
    model_type="lfm2_moe", hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=5,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
    num_experts_per_tok=4, norm_topk_prob=True, use_expert_bias=True,
    routed_scaling_factor=1.0, vocab_size=128, norm_eps=1e-5,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    conv_L_cache=3, conv_bias=False, max_position_embeddings=256,
    tie_word_embeddings=True, initializer_range=0.02, eos_token_id=1)
CONFIGS = {
    "whole": dict(_BASE, num_experts=16),
    "share": dict(_BASE, num_experts=4,
                  expert_share={"of": 16, "first": 4}),
}
DOC = 20  # tokens a document; three to a packed row of 64


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """name -> a checkpoint the benchmark's generator wrote, read by
    the program's loader (float32 parameters and compute) and, file by
    file, by the reference; each made once a module."""
    made = {}

    def get(name):
        if name not in made:
            hf = CONFIGS[name]
            ckpt = str(tmp_path_factory.mktemp(name))
            generate.write_checkpoint(ckpt, family, hf, seed=11)
            cfg, params = registry.load_hf_checkpoint(ckpt, "lfm2_moe")
            cfg.param_dtype = cfg.compute_dtype = "float32"
            params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                                  params)
            docs = np.random.default_rng(3).integers(
                2, hf["vocab_size"], size=(3, DOC)).astype(np.int32)
            tensors = reference.load_tensors(ckpt)
            made[name] = dict(
                hf=hf, ckpt=ckpt, cfg=cfg, params=params, docs=docs,
                tensors=tensors, want=family.logits(hf, tensors, docs))
        return made[name]
    return get


@pytest.fixture(params=sorted(CONFIGS))
def model(request, built):
    return built(request.param)


def _packed(docs):
    """Three documents and four pads a row of 64."""
    ids = np.zeros((1, 64), np.int32)
    seg = np.zeros((1, 64), np.int32)
    for j, doc in enumerate(docs):
        ids[0, j * DOC:(j + 1) * DOC] = doc
        seg[0, j * DOC:(j + 1) * DOC] = j + 1
    return ids, seg


def _engine(cfg, params, dp=1, tp=1, **kwargs):
    par = mesh_lib.ParallelismConfig(data_parallel_size=dp,
                                     tensor_parallel_size=tp)
    ctx = mesh_lib.MeshContext(
        ModelName(f"lfm2-d{dp}t{tp}", 0),
        mesh_lib.make_mesh(par, jax.devices()[:dp * tp]), par)
    return Engine(cfg, ctx, jax.tree.map(np.asarray, params), **kwargs)


def _engine_logits(engine, ids, seg):
    hidden = engine.forward_hidden(ids, seg)
    return np.asarray(T.lm_logits(engine.cfg, engine.params, hidden),
                      np.float32)


def test_config_is_read_from_the_published_keys(model):
    cfg, hf = model["cfg"], model["hf"]
    assert cfg.layer_pattern == (
        ("conv", "dense"), ("attention", "moe"), ("conv", "moe"),
        ("conv", "moe"), ("conv", "moe"))
    assert cfg.pattern_string == "c a c c c"
    assert (cfg.layers_of("conv"), cfg.layers_of("attention"), cfg.kv_layers,
            cfg.n_moe_layers) == ((0, 2, 3, 4), (1,), 1, 4)
    assert cfg.qk_norm == "head" and cfg.mlp_type == "llama"
    assert cfg.tied_embedding and cfg.rotary_base == 1e6
    moe = cfg.moe
    assert (moe.num_experts, moe.top_k, moe.score_fn, moe.use_expert_bias,
            moe.norm_topk_prob, moe.routing_type, moe.intermediate_dim) == (
        16, 4, "sigmoid", True, True, "none", 32)
    assert moe.experts_held == ((4, 4) if "expert_share" in hf else None)
    assert moe.n_held == hf["num_experts"]
    back = hf_models.config_to_hf("lfm2_moe", cfg)
    for key in sorted(set(hf) - {"initializer_range", "eos_token_id"}):
        assert back[key] == hf[key], key
    assert ("expert_share" in back) == ("expert_share" in hf)
    n = sum(x.size for x in jax.tree.leaves(model["params"]))
    assert n == family.n_params(hf)
    # the program's estimate leaves the layer norms' scales out
    assert n_params(cfg) == n - (2 * cfg.n_layers + 1) * cfg.hidden_dim
    init = T.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(
        jnp.shape, model["params"])


def test_conv_bias_is_refused_not_ignored():
    with pytest.raises(NotImplementedError, match="conv_bias"):
        hf_models.config_from_hf("lfm2_moe",
                                 dict(CONFIGS["whole"], conv_bias=True))


def test_packed_row_of_three_documents_equals_the_documents_alone(model):
    """The boundary: a token's convolution window and its attention
    stop at its own document's first token, so three documents packed
    into one row (and four pads behind them) give each document the
    logits the reference gives it alone."""
    ids, seg = _packed(model["docs"])
    got = _engine_logits(_engine(model["cfg"], model["params"]), ids, seg)
    got = got[0, :3 * DOC].reshape(3, DOC, -1)
    assert np.abs(got - model["want"]).max() < LOGIT_TOL
    assert model["want"].std() > 0.1  # the logits are not all alike
    # and a window that DID reach into the previous document is seen
    crossed = family.logits(model["hf"], model["tensors"], model["docs"],
                            wrong=("conv_crosses_documents",))
    assert np.abs(crossed - model["want"])[1:, :2].max() > 50 * LOGIT_TOL
    assert np.abs(crossed - model["want"])[0].max() == 0.0


@pytest.mark.parametrize("wrong", family.WRONG + (
    "bias_left_out", "gates_not_renormalised"))
def test_a_wrong_equation_is_outside_the_tolerance(built, wrong):
    model = built("share")
    hf = model["hf"]
    if wrong == "bias_left_out":
        got = family.logits(dict(hf, use_expert_bias=False),
                            model["tensors"], model["docs"])
    elif wrong == "gates_not_renormalised":
        got = family.logits(dict(hf, norm_topk_prob=False),
                            model["tensors"], model["docs"])
    else:
        got = family.logits(hf, model["tensors"], model["docs"],
                            wrong=(wrong,))
    assert np.abs(got - model["want"]).max() > 50 * LOGIT_TOL


@pytest.mark.parametrize("dp,tp", [(1, 2), (2, 1)])
def test_sharded_forward_matches_one_device(model, dp, tp):
    """The convolution's three parts and taps under tensor parallelism,
    the per-head norm's one scale on every shard, the held experts'
    ragged products under data parallelism: the same logits as on one
    device, and so the reference's."""
    docs = model["docs"]
    ids = np.concatenate([_packed(docs)[0], _packed(docs[::-1])[0]])
    seg = np.concatenate([_packed(docs)[1]] * 2)
    got = _engine_logits(_engine(model["cfg"], model["params"], dp, tp),
                         ids, seg)
    assert np.abs(got[0, :3 * DOC].reshape(3, DOC, -1)
                  - model["want"]).max() < LOGIT_TOL
    assert np.abs(got[1, :3 * DOC].reshape(3, DOC, -1)
                  - model["want"][::-1]).max() < LOGIT_TOL


@pytest.mark.parametrize("n_pre", [12, 1])
def test_prefill_then_decode_matches_full_forward(model, n_pre):
    """``engine/generation.py``'s two steps, teacher-forced, through
    BOTH kinds of state: K and V for the one attention layer, two rows
    of the convolution's input for each of the four conv layers. A
    prefill of one token leaves a conv state whose older row is the
    document's start (zero)."""
    cfg, params, docs = model["cfg"], model["params"], model["docs"]
    ids = jnp.asarray(docs[:, :n_pre])
    hidden, cache = jax.jit(
        lambda p, i: T.prefill(cfg, p, i, jnp.ones_like(i),
                               total_len=DOC))(params, ids)
    assert cache["k"].shape[0] == 1 and cache["conv"].shape == (
        4, len(docs), 2, cfg.hidden_dim)
    got = [np.asarray(T.lm_logits(cfg, params, hidden))]
    step = jax.jit(lambda p, c, t, pos: T.decode_step(
        cfg, p, c, t, pos, uniform_slot=True))
    for t in range(n_pre, DOC):
        h, cache = step(params, cache, jnp.asarray(docs[:, t]),
                        jnp.full((len(docs),), t, jnp.int32))
        got.append(np.asarray(T.lm_logits(cfg, params, h))[:, None])
    got = np.concatenate(got, axis=1)
    assert np.abs(got - model["want"]).max() < LOGIT_TOL


def test_left_padded_prompts_generate_as_unpadded_ones(model):
    """``generate``'s prompts are left-padded: the pads' rows must not
    reach the conv state, nor a prompt's first tokens their windows."""
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    cfg, docs = model["cfg"], model["docs"]
    engine = _engine(cfg, model["params"])
    lens = [7, 2, 5]
    lp = 8
    ids = np.zeros((3, lp), np.int32)
    seg = np.zeros((3, lp), np.int32)
    pos = np.zeros((3, lp), np.int32)
    for r, n in enumerate(lens):
        ids[r, lp - n:], seg[r, lp - n:] = docs[r, :n], 1
        pos[r, lp - n:] = np.arange(n)
    out = engine.generate(
        ids, seg, pos, jax.random.PRNGKey(0),
        GenerationHyperparameters(max_new_tokens=4, greedy=True,
                                  force_no_logits_mask=True),
        eos_token_id=None, pad_token_id=0).to_host()
    for r, n in enumerate(lens):
        seq = np.concatenate([docs[r, :n], out.tokens[r]])[None]
        want = family.logprobs(model["hf"], model["tensors"], seq)[0, -4:]
        assert np.abs(out.logprobs[r] - want).max() < LOGIT_TOL


def _sft_case(model, n_docs, prompt_len):
    """One SFT microbatch: (program's loss, stats, gradient under HF's
    names), (reference's loss, parts, gradient)."""
    cfg, params = model["cfg"], model["params"]
    docs = model["docs"][:n_docs]
    ids, seg = _packed(docs)
    prompt = np.zeros((1, 64), bool)
    for j in range(n_docs):
        prompt[0, j * DOC:j * DOC + prompt_len] = True
    mb = dict(input_ids=jnp.asarray(ids), seg_ids=jnp.asarray(seg),
              prompt_mask=jnp.asarray(prompt))
    objective = _engine(cfg, params)._objective(sft._make_loss_fn(cfg))
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        objective, has_aux=True))(params, mb)
    got = hf_models.params_to_hf(
        "lfm2_moe", jax.tree.map(np.asarray, grads), cfg)
    want = family.sft_loss_and_grad(model["hf"], model["tensors"], docs,
                                    prompt_len)
    return (float(loss), {k: float(v) for k, v in stats.items()}, got), want


def test_sft_loss_and_gradient_match_reference(model):
    """Loss and the gradient of every leaf against ``jax.grad`` of the
    reference, three documents and four pads a row; no gradient
    reaches ``expert_bias``, in the program or in the reference."""
    (loss, stats, grads), (ref_loss, parts, ref_grads) = _sft_case(
        model, n_docs=3, prompt_len=5)
    assert abs(loss - ref_loss) < 1e-5
    assert abs(stats["nll"] - parts["nll"]) < 1e-5
    assert "moe_aux_loss" not in stats and parts["aux"] == 0.0
    assert stats["moe_load_max_over_mean"] >= 1.0
    assert set(grads) == set(ref_grads)
    for name in sorted(grads):
        scale = np.abs(ref_grads[name]).max()
        gap = np.abs(grads[name] - ref_grads[name]).max()
        assert gap <= 2e-5 * scale + 1e-12, (name, gap, scale)
        if name.endswith("expert_bias"):
            assert not grads[name].any() and not ref_grads[name].any()


def test_held_statistics_are_the_reference_routings_counts(built):
    """What the train step returns beside the loss for a share: the
    pairs routed to HELD experts, over the sparse layers, and the
    busiest held expert over the mean of all, on a row with no pads,
    against the counts of the reference's own routing."""
    model = built("share")
    cfg, hf = model["cfg"], model["hf"]
    docs = np.random.default_rng(9).integers(
        2, hf["vocab_size"], size=(2, 32)).astype(np.int32)
    mb = dict(input_ids=jnp.asarray(docs.reshape(1, 64)),
              seg_ids=jnp.asarray(np.repeat([[1, 2]], 32, axis=1)),
              prompt_mask=jnp.zeros((1, 64), bool))
    objective = _engine(cfg, model["params"])._objective(
        sft._make_loss_fn(cfg))
    _, stats = jax.jit(objective)(model["params"], mb)
    held = list(family.dims(hf)["held"])
    pairs, worst_held, worst = 0, 0.0, 0.0
    for layer in range(1, cfg.n_layers):
        routed = family.top_k_sets(hf, model["tensors"], docs, layer)
        counts = routed.reshape(-1, 16).sum(0)
        assert counts.sum() == 64 * 4
        pairs += counts[held].sum()
        worst_held = max(worst_held, counts[held].max() / counts.mean())
        worst = max(worst, counts.max() / counts.mean())
    assert float(stats["moe_held_pairs"]) == pairs
    assert float(stats["moe_held_load_max_over_mean"]) == \
        pytest.approx(worst_held)
    assert float(stats["moe_load_max_over_mean"]) == pytest.approx(worst)
    assert 0 < pairs < 4 * 64 * 4


def test_train_step_counts_held_pairs_and_leaves_the_bias(built):
    """One optimizer step through ``Engine.train_batch``: the counters
    and the span's attributes a share brings, and ``expert_bias`` bit
    for bit as loaded (no gradient, no decay: not the optimizer's)."""
    from realhf_tpu.obs import metrics, tracing
    model = built("share")
    cfg = model["cfg"]
    engine = _engine(cfg, model["params"], optimizer=OptimizerConfig(
        lr=1e-2, warmup_steps_proportion=0.0,
        lr_scheduler_type="constant"), total_train_steps=10)
    ids, seg = _packed(model["docs"])
    mb = dict(input_ids=ids, seg_ids=seg,
              prompt_mask=np.zeros((1, 64), bool))
    before = jax.tree.map(np.asarray, engine.params)
    tracing.start()
    stats = engine.train_batch([mb, mb], sft._make_loss_fn(cfg),
                               loss_fn_key="sft")
    capture = tracing.stop()
    after = jax.tree.map(np.asarray, engine.params)
    for i in range(1, 5):
        m0, m1 = before["layers"][str(i)]["mlp"], after["layers"][str(i)]["mlp"]
        assert np.array_equal(m0["expert_bias"], m1["expert_bias"])
        assert not np.array_equal(m0["router"], m1["router"])
        assert not np.array_equal(m0["wg"], m1["wg"])
    [span] = capture.named("engine:train")
    a = span["attributes"]
    assert (a["layer_pattern"], a["conv_layers"], a["experts_held"],
            a["experts"], a["top_k"], a["router"], a["moe_dispatch"]) == (
        "c a c c c", 4, 4, 16, 4, "sigmoid_bias", "ragged")
    tokens = 2 * 3 * DOC
    assert capture.counter("conv_tokens_total",
                           role="lfm2-d1t1") == tokens * 4
    assert capture.counter("moe_routed_pairs_total", role="lfm2-d1t1",
                           dispatch="ragged") == tokens * 4 * 4
    held = capture.counter("moe_held_pairs_total", role="lfm2-d1t1")
    # two equal microbatches; pads are routed and multiplied too
    assert held == stats["moe_held_pairs"] == a["moe_held_pairs"]
    assert 0 < held < 2 * 64 * 4 * 4
    # four sparse layers, two microbatches: how many took the slow path
    slow = capture.counter("moe_share_overflow_total", role="lfm2-d1t1")
    assert slow == stats["moe_share_overflows"] == a["moe_share_overflows"]
    assert slow in range(9)
    assert a["moe_held_load_max_over_mean"] == \
        stats["moe_held_load_max_over_mean"] <= a["moe_load_max_over_mean"]
    assert metrics.snapshot()["moe_held_load_max_over_mean"]["values"]


def test_generate_span_carries_both_kinds_of_state(built):
    from realhf_tpu.obs import tracing
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    model = built("share")
    engine = _engine(model["cfg"], model["params"])
    ids = model["docs"][:, :8]
    tracing.start()
    engine.generate(ids, np.ones_like(ids),
                    np.broadcast_to(np.arange(8, dtype=np.int32), ids.shape),
                    jax.random.PRNGKey(0),
                    GenerationHyperparameters(max_new_tokens=3, greedy=True,
                                              force_no_logits_mask=True),
                    eos_token_id=None, pad_token_id=0)
    [span] = tracing.stop().named("engine:generate")
    a = span["attributes"]
    assert a["kv_layers"] == 1
    assert a["conv_state_bytes"] == 4 * 3 * 2 * 64 * 4  # float32 here
    assert a["layer_pattern"] == "c a c c c"


def test_hf_round_trip_is_bit_equal(model, tmp_path):
    state, cfg = model["tensors"], model["cfg"]
    back = hf_models.params_to_hf(
        "lfm2_moe", hf_models.params_from_hf("lfm2_moe", state, cfg), cfg)
    assert set(back) == set(state)
    for name in state:
        assert back[name].dtype == state[name].dtype
        assert back[name].shape == state[name].shape, name
        assert np.array_equal(back[name].view(np.uint16),
                              state[name].view(np.uint16)), name
    # and through the files: the critic variant keeps the body
    path = str(tmp_path / "saved")
    registry.save_hf_checkpoint(
        path, "lfm2_moe", cfg, jax.tree.map(np.asarray, model["params"]))
    with open(os.path.join(path, "config.json")) as f:
        saved = json.load(f)
    assert saved["model_type"] == "lfm2_moe"
    assert saved.get("expert_share") == model["hf"].get("expert_share")
    assert registry.detect_family(path) == "lfm2_moe"
    ccfg, critic = registry.load_hf_checkpoint(path, "lfm2_moe",
                                               is_critic=True)
    assert ccfg.is_critic and critic["head"]["w"].shape == (64, 1)
    np.testing.assert_array_equal(
        critic["layers"]["2"]["conv"]["w"],
        np.asarray(model["params"]["layers"]["2"]["conv"]["w"]))


@pytest.mark.parametrize("tp", [1, 2])
def test_streamed_load_and_save_round_trip(model, tmp_path, tp):
    """A checkpoint whose layers do not all hold the same tensors, a
    layer at a time: onto a mesh, and back into one file a layer, bit
    for bit what the generator wrote."""
    par = mesh_lib.ParallelismConfig(tensor_parallel_size=tp)
    mesh = mesh_lib.make_mesh(par, jax.devices()[:tp])
    cfg, params = registry.load_hf_checkpoint_streamed(
        model["ckpt"], mesh, "lfm2_moe", param_dtype="bfloat16")
    whole = registry.load_hf_checkpoint(model["ckpt"], "lfm2_moe")[1]
    assert jax.tree.structure(params) == jax.tree.structure(whole)
    for got, want in zip(jax.tree.leaves(params), jax.tree.leaves(whole)):
        assert got.dtype == jnp.bfloat16 and got.sharding.mesh == mesh
        assert np.array_equal(np.asarray(got).view(np.uint16),
                              np.asarray(want).view(np.uint16))
    assert params["layers"]["0"]["mlp"]["wg"].sharding.spec == \
        jax.sharding.PartitionSpec(None, "model")
    path = str(tmp_path / "streamed")
    registry.save_hf_checkpoint_streamed(path, "lfm2_moe", cfg, params)
    files = sorted(f for f in os.listdir(path) if f.endswith(".safetensors"))
    assert len(files) == cfg.n_layers + 1
    back = reference.load_tensors(path)
    assert set(back) == set(model["tensors"])
    for name, want in model["tensors"].items():
        assert np.array_equal(back[name].view(np.uint16),
                              want.view(np.uint16)), name


def test_what_does_not_run_a_pattern_refuses_by_name(built):
    """The slot engine, the paged pool, pipeline stages and the
    allocation search's cost model know one kind of block: under a
    layer pattern they raise, naming it, and do not run wrong."""
    from realhf_tpu.engine import inflight, kv_pool
    from realhf_tpu.models import sharding
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    model = built("share")
    cfg, params = model["cfg"], model["params"]
    g = GenerationHyperparameters(max_new_tokens=2, greedy=True,
                                  force_no_logits_mask=True)
    named = r"layer pattern \(layer_pattern 'c a c c c'"
    with pytest.raises(NotImplementedError, match="slot engine.*" + named):
        inflight.InflightBatchingGenerator(
            cfg, params, g, n_slots=2, max_prompt_len=8,
            eos_token_id=None, pad_token_id=0)
    with pytest.raises(NotImplementedError, match="slot engine.*" + named):
        _engine(cfg, params).inflight_generator(
            g, n_slots=2, max_prompt_len=8, eos_token_id=None,
            pad_token_id=0)
    with pytest.raises(NotImplementedError, match="KV pool.*" + named):
        kv_pool.KVPool(cfg, n_blocks=4, block_len=8)
    with pytest.raises(NotImplementedError, match="pipeline.*" + named):
        sharding.param_pspecs(cfg, pipeline_parallel=True)
    par = mesh_lib.ParallelismConfig(pipeline_parallel_size=2)
    ctx = mesh_lib.MeshContext(
        ModelName("lfm2-pp2", 0),
        mesh_lib.make_mesh(par, jax.devices()[:2]), par)
    with pytest.raises(NotImplementedError, match="pipeline.*" + named):
        Engine(cfg, ctx, jax.tree.map(np.asarray, params))

    class _Stages:
        n_stages = 2
    with pytest.raises(NotImplementedError, match="pipeline.*" + named):
        T.forward(cfg, params, jnp.zeros((1, 8), jnp.int32),
                  jnp.ones((1, 8), jnp.int32), pipeline=_Stages())
    # a share needs an EXACT mode, the ragged one or (PR 62) the dense
    # one over its held experts; a capacity that drops pairs is refused
    import dataclasses
    capacity = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=1.25))
    with pytest.raises(NotImplementedError, match="experts_held"):
        T.forward(capacity, params, jnp.zeros((1, 8), jnp.int32),
                  jnp.ones((1, 8), jnp.int32))
    dense = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, use_grouped_gemm=False))
    ids = jnp.arange(8, dtype=jnp.int32)[None] + 2
    seg = jnp.ones((1, 8), jnp.int32)
    want, got = (np.asarray(T.forward(c, params, ids, seg)[0], np.float32)
                 for c in (cfg, dense))
    assert np.abs(got - want).max() <= 1e-5 * max(np.abs(want).max(), 1.0)
