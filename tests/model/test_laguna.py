"""Laguna held to its plain reference (``benchmark/families/laguna.py``)
on the CPU: small widths that keep every mechanism of the benchmark's
cell (hidden 64, 2 key/value heads of 16; five layers: full attention
with 4 query heads and a dense feed-forward of 96, three window layers
of 6 heads and a window of 8, a full layer of 4, the last four with 16
experts of width 32, 4 a token, and a shared expert of 32; YaRN over
half of a head in the full layers, plain rotary over all of it in the
window layers, an output gate a head), seeded random weights under
Hugging Face's names (``benchmark/generate.py`` makes them, the
program's own loader reads them), everything in float32. Two
checkpoints: one that holds every expert (the uncut model) and one
expert-parallel rank's share (experts 4 to 7 of 16). Documents are 20
tokens, so every window layer's window (8) ends inside a document, and
three are packed into a row of 64, so a document's edge lies inside a
window's reach.

Every comparison is float32 against float32 on the same values, so the
tolerances are those of two orders of summation. ``LOGIT_TOL`` is far
over what the packed forward shows and 50 times under the mildest of
the wrong equations (``test_a_wrong_equation_is_outside_the_tolerance``
holds each to that).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import generate, reference
from benchmark.families import laguna as family
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
    model_type="laguna", vocab_size=128, hidden_size=64,
    intermediate_size=96, num_hidden_layers=5, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, max_position_embeddings=4096,
    attention_bias=False, rms_norm_eps=1e-6, num_experts_per_tok=4,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    tie_word_embeddings=False, gating=True, sliding_window=8,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 2, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    layer_types=["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4,
    num_attention_heads_per_layer=[4, 6, 6, 6, 4],
    moe_apply_router_weight_on_input=False,
    moe_routed_scaling_factor=2.5, norm_topk_prob=True,
    initializer_range=0.02, eos_token_id=1)
CONFIGS = {
    "whole": dict(_BASE, num_experts=16),
    "share": dict(_BASE, num_experts=4,
                  expert_share={"of": 16, "first": 4}),
}
DOC = 20  # tokens a document; three to a packed row of 64
NAME = "laguna"


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
            cfg, params = registry.load_hf_checkpoint(ckpt, NAME)
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
        ModelName(f"laguna-d{dp}t{tp}", 0),
        mesh_lib.make_mesh(par, jax.devices()[:dp * tp]), par)
    return Engine(cfg, ctx, jax.tree.map(np.asarray, params), **kwargs)


def _engine_logits(engine, ids, seg):
    hidden = engine.forward_hidden(ids, seg)
    return np.asarray(T.lm_logits(engine.cfg, engine.params, hidden),
                      np.float32)


def test_config_is_read_from_the_published_keys(model):
    cfg, hf = model["cfg"], model["hf"]
    assert cfg.layer_pattern == (
        ("attention", "dense"), ("window", "moe"), ("window", "moe"),
        ("window", "moe"), ("attention", "moe"))
    assert cfg.pattern_string == "a w w w a"
    assert (cfg.layers_of("attention", "window"), cfg.layers_of("window"),
            cfg.layers_of("conv"), cfg.kv_layers, cfg.n_moe_layers) == (
        (0, 1, 2, 3, 4), (1, 2, 3), (), 5, 4)
    assert [cfg.layer_window(i) for i in range(5)] == [None, 8, 8, 8, None]
    assert [cfg.q_heads(i) for i in range(5)] == [4, 6, 6, 6, 4]
    assert cfg.attn_output_gate and cfg.qk_norm is None
    assert not cfg.tied_embedding and cfg.mlp_type == "llama"
    full, window = (cfg.rotary_by_operator[op]
                    for op in ("attention", "window"))
    assert (full.scaling_type, full.base, full.partial_factor, full.factor,
            full.original_max_positions, full.beta_fast, full.beta_slow,
            full.attention_factor) == (
        "yarn", 500000.0, 0.5, 64.0, 16, 2.0, 1.0, 1.4158883083359672)
    assert (window.scaling_type, window.base, window.partial_factor) == (
        None, 10000.0, 1.0)
    assert (full.rotated(16), window.rotated(16)) == (8, 16)
    moe = cfg.moe
    assert (moe.num_experts, moe.top_k, moe.score_fn, moe.use_expert_bias,
            moe.norm_topk_prob, moe.routing_type, moe.intermediate_dim,
            moe.shared_intermediate_dim, moe.routed_scaling_factor,
            moe.norm_topk_eps) == (
        16, 4, "sigmoid", False, True, "none", 32, 32, 2.5, 1e-20)
    assert moe.experts_held == ((4, 4) if "expert_share" in hf else None)
    assert moe.n_held == hf["num_experts"]
    back = hf_models.config_to_hf(NAME, cfg)
    for key in sorted(set(hf) - {"initializer_range", "eos_token_id"}):
        assert back[key] == hf[key], key
    assert ("expert_share" in back) == ("expert_share" in hf)
    n = sum(x.size for x in jax.tree.leaves(model["params"]))
    assert n == family.n_params(hf)
    # the program's estimate leaves the layer norms' scales out; it
    # counts heads by layer, the gate and the shared expert
    assert n_params(cfg) == n - (2 * cfg.n_layers + 1) * cfg.hidden_dim
    init = T.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(
        jnp.shape, model["params"])


@pytest.mark.parametrize("key,value,match", [
    ("attention_bias", True, "attention_bias"),
    ("moe_apply_router_weight_on_input", True, "router_weight_on_input"),
    ("layer_types", ["linear_attention"] * 5, "layer_types"),
])
def test_what_the_family_cannot_run_is_refused_not_ignored(key, value,
                                                            match):
    with pytest.raises(NotImplementedError, match=match):
        hf_models.config_from_hf(NAME, dict(CONFIGS["whole"],
                                            **{key: value}))


def test_a_window_layer_needs_a_window():
    with pytest.raises(ValueError, match="sliding_window is None"):
        hf_models.config_from_hf(NAME, dict(CONFIGS["whole"],
                                            sliding_window=None))


def test_packed_row_of_three_documents_equals_the_documents_alone(model):
    """Both edges at once: a window of 8 ends inside every document of
    20, and a document's first tokens have another document inside
    their window's reach in the packed row. Each document gets the
    logits the reference gives it alone, and the reference given the
    packed row says the same."""
    ids, seg = _packed(model["docs"])
    got = _engine_logits(_engine(model["cfg"], model["params"]), ids, seg)
    got = got[0, :3 * DOC].reshape(3, DOC, -1)
    assert np.abs(got - model["want"]).max() < LOGIT_TOL
    assert model["want"].std() > 0.1  # the logits are not all alike
    packed = family.logits(model["hf"], model["tensors"], ids, seg)
    assert np.abs(packed[0, :3 * DOC].reshape(3, DOC, -1)
                  - model["want"]).max() < LOGIT_TOL


@pytest.mark.parametrize("wrong", family.WRONG + ("positions_of_the_row",))
def test_a_wrong_equation_is_outside_the_tolerance(built, wrong):
    model = built("share")
    hf = model["hf"]
    if wrong == "positions_of_the_row":
        # rotary positions and window distances of the ROW, documents
        # ignored: what a packed row gives a model that forgot them
        ids, _ = _packed(model["docs"])
        got = family.logits(hf, model["tensors"], ids[:, :3 * DOC])
        got = got[0].reshape(3, DOC, -1)
    else:
        got = family.logits(hf, model["tensors"], model["docs"],
                            wrong=(wrong,))
    assert np.abs(got - model["want"]).max() > 50 * LOGIT_TOL


@pytest.mark.parametrize("dp,tp", [(1, 2), (2, 1)])
def test_sharded_forward_matches_one_device(model, dp, tp):
    """Unequal heads a layer, the gate by head and the shared expert
    under tensor parallelism, the held experts' ragged products under
    data parallelism: the same logits as on one device, and so the
    reference's."""
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
    """``engine/generation.py``'s two steps, teacher-forced: one K/V
    stack for all five layers (window layers keep every row, the decode
    attention masks what is past the window), each layer's own heads,
    rotary table and gate. A prefill of 12 leaves the window's edge (8)
    inside the prompt; decoding to 20 moves it through the cache."""
    cfg, params, docs = model["cfg"], model["params"], model["docs"]
    ids = jnp.asarray(docs[:, :n_pre])
    hidden, cache = jax.jit(
        lambda p, i: T.prefill(cfg, p, i, jnp.ones_like(i),
                               total_len=DOC))(params, ids)
    assert cache["k"].shape[:3] == (5, len(docs), 2) and "conv" not in cache
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
    """``generate``'s prompts are left-padded: a window counts cache
    slots, pads among them, and a pad is masked as well as out of the
    window, so each stream generates what it would alone."""
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    cfg, docs = model["cfg"], model["docs"]
    engine = _engine(cfg, model["params"])
    lens = [11, 2, 9]
    lp = 12
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
        NAME, jax.tree.map(np.asarray, grads), cfg)
    want = family.sft_loss_and_grad(model["hf"], model["tensors"], docs,
                                    prompt_len)
    return (float(loss), {k: float(v) for k, v in stats.items()}, got), want


def test_sft_loss_and_gradient_match_reference(model):
    """Loss and the gradient of every leaf (the gate's, the shared
    expert's and each layer's own count of heads among them) against
    ``jax.grad`` of the reference, three documents and four pads a
    row."""
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
        assert scale > 0, name
        assert gap <= 2e-5 * scale + 1e-12, (name, gap, scale)


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


def test_train_step_spans_say_what_ran(built):
    """One optimizer step through ``Engine.train_batch``: the span's
    attributes a mixed stack brings, and every new leaf moved."""
    from realhf_tpu.obs import tracing
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
    for i in range(5):
        a0, a1 = before["layers"][str(i)]["attn"], after["layers"][str(i)]["attn"]
        assert not np.array_equal(a0["w_gate"], a1["w_gate"])
        assert a0["w_gate"].shape == (64, cfg.q_heads(i))
    for i in range(1, 5):
        m0, m1 = before["layers"][str(i)]["mlp"], after["layers"][str(i)]["mlp"]
        assert not np.array_equal(m0["shared"]["wd"], m1["shared"]["wd"])
        assert not np.array_equal(m0["router"], m1["router"])
    [span] = capture.named("engine:train")
    a = span["attributes"]
    assert (a["layer_pattern"], a["window"], a["window_layers"],
            a["q_heads"], a["shared_expert"], a["experts_held"],
            a["experts"], a["top_k"], a["router"], a["moe_dispatch"],
            a["conv_layers"], a["dense_layers"]) == (
        "a w w w a", 8, 3, "4 6 6 6 4", 32, 4, 16, 4, "sigmoid", "ragged",
        0, 1)
    assert a["rotary"] == "a:yarn64@500000/0.5 w:plain@10000/1"
    tokens = 2 * 3 * DOC
    assert capture.counter("moe_routed_pairs_total", role="laguna-d1t1",
                           dispatch="ragged") == tokens * 4 * 4
    assert capture.counter("conv_tokens_total", role="laguna-d1t1") == 0
    held = capture.counter("moe_held_pairs_total", role="laguna-d1t1")
    assert held == stats["moe_held_pairs"] == a["moe_held_pairs"]
    assert 0 < held < 2 * 64 * 4 * 4


def test_hf_round_trip_is_bit_equal(model, tmp_path):
    state, cfg = model["tensors"], model["cfg"]
    back = hf_models.params_to_hf(
        NAME, hf_models.params_from_hf(NAME, state, cfg), cfg)
    assert set(back) == set(state)
    for name in state:
        assert back[name].dtype == state[name].dtype
        assert back[name].shape == state[name].shape, name
        assert np.array_equal(back[name].view(np.uint16),
                              state[name].view(np.uint16)), name
    # and through the files: the critic variant keeps the body
    path = str(tmp_path / "saved")
    registry.save_hf_checkpoint(
        path, NAME, cfg, jax.tree.map(np.asarray, model["params"]))
    with open(os.path.join(path, "config.json")) as f:
        saved = json.load(f)
    assert saved["model_type"] == "laguna"
    assert saved.get("expert_share") == model["hf"].get("expert_share")
    assert saved["rope_parameters"]["full_attention"]["rope_type"] == "yarn"
    assert registry.detect_family(path) == NAME
    ccfg, critic = registry.load_hf_checkpoint(path, NAME, is_critic=True)
    assert ccfg.is_critic and critic["head"]["w"].shape == (64, 1)
    np.testing.assert_array_equal(
        critic["layers"]["2"]["attn"]["w_gate"],
        np.asarray(model["params"]["layers"]["2"]["attn"]["w_gate"]))


@pytest.mark.parametrize("tp", [1, 2])
def test_streamed_load_and_save_round_trip(model, tmp_path, tp):
    """A checkpoint whose layers differ in their tensors and widths, a
    layer at a time: onto a mesh, and back into one file a layer, bit
    for bit what the generator wrote."""
    par = mesh_lib.ParallelismConfig(tensor_parallel_size=tp)
    mesh = mesh_lib.make_mesh(par, jax.devices()[:tp])
    cfg, params = registry.load_hf_checkpoint_streamed(
        model["ckpt"], mesh, NAME, param_dtype="bfloat16")
    whole = registry.load_hf_checkpoint(model["ckpt"], NAME)[1]
    assert jax.tree.structure(params) == jax.tree.structure(whole)
    for got, want in zip(jax.tree.leaves(params), jax.tree.leaves(whole)):
        assert got.dtype == jnp.bfloat16 and got.sharding.mesh == mesh
        assert np.array_equal(np.asarray(got).view(np.uint16),
                              np.asarray(want).view(np.uint16))
    spec = jax.sharding.PartitionSpec
    assert params["layers"]["1"]["attn"]["w_gate"].sharding.spec == \
        spec(None, "model")
    assert params["layers"]["1"]["mlp"]["shared"]["wd"].sharding.spec == \
        spec("model", None)
    path = str(tmp_path / "streamed")
    registry.save_hf_checkpoint_streamed(path, NAME, cfg, params)
    files = sorted(f for f in os.listdir(path) if f.endswith(".safetensors"))
    assert len(files) == cfg.n_layers + 1
    back = reference.load_tensors(path)
    assert set(back) == set(model["tensors"])
    for name, want in model["tensors"].items():
        assert np.array_equal(back[name].view(np.uint16),
                              want.view(np.uint16)), name


def test_what_does_not_run_a_pattern_refuses_by_name(built):
    """The slot engine, the paged pool and pipeline stages know one
    kind of block: under this pattern too they raise, naming it."""
    from realhf_tpu.engine import inflight, kv_pool
    from realhf_tpu.models import sharding
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    model = built("share")
    cfg, params = model["cfg"], model["params"]
    g = GenerationHyperparameters(max_new_tokens=2, greedy=True,
                                  force_no_logits_mask=True)
    named = (r"layer pattern \(layer_pattern 'a w w w a': 2 attention "
             r"layers, 3 window layers")
    with pytest.raises(NotImplementedError, match="slot engine.*" + named):
        inflight.InflightBatchingGenerator(
            cfg, params, g, n_slots=2, max_prompt_len=8,
            eos_token_id=None, pad_token_id=0)
    with pytest.raises(NotImplementedError, match="KV pool.*" + named):
        kv_pool.KVPool(cfg, n_blocks=4, block_len=8)
    with pytest.raises(NotImplementedError, match="pipeline.*" + named):
        sharding.param_pspecs(cfg, pipeline_parallel=True)


def test_one_block_models_refuse_the_per_layer_fields():
    """Heads, rotary tables and the gate a layer belong to a model that
    declares its layers; a window alone does not (Mistral's path)."""
    from realhf_tpu.models.config import TransformerConfig
    base = dict(n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=64,
                intermediate_dim=96, vocab_size=128)
    windowed = TransformerConfig(**base, sliding_window=8)
    assert [windowed.layer_window(i) for i in range(2)] == [8, 8]
    for extra in (dict(layer_q_heads=(4, 6)), dict(attn_output_gate=True)):
        with pytest.raises(NotImplementedError, match="layer_pattern"):
            TransformerConfig(**base, **extra)


def test_mixed_stack_through_the_flash_kernels_counts_each_layers_blocks(
        interpreted_kernels):
    """Heads of 64 and rows of 1024, so that the packed rows meet the
    flash kernels' gate: with the kernels engaged (interpret mode) the
    stack of window and full layers gives the XLA path's hidden
    states, and ``flash_kv_blocks_total`` adds up each layer by its own
    rule: a full layer visits the 6 block pairs (256 x 512) under a
    row's diagonal, a layer with a window of 128 visits 5 of them."""

    from realhf_tpu.obs import tracing
    from realhf_tpu.ops.flash_attention import block_counts
    hf = dict(CONFIGS["share"], hidden_size=128, head_dim=64,
              num_key_value_heads=1, num_attention_heads=1,
              num_attention_heads_per_layer=[1, 2, 2, 2, 1],
              sliding_window=128)
    cfg = hf_models.config_from_hf(NAME, hf)
    cfg.param_dtype = cfg.compute_dtype = "float32"
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(
        2, 128, size=(1, 1024)).astype(np.int32)
    seg = np.ones((1, 1024), np.int32)
    assert block_counts(seg) == (6, 6, 2)
    assert block_counts(seg, sliding_window=128) == (5, 6, 0)

    def run():
        engine = _engine(cfg, params)
        tracing.start()
        hidden = np.asarray(engine.forward_hidden(ids, seg))
        return hidden, tracing.stop()

    want, xla = run()
    assert not any(k.startswith("flash_kv_blocks_total")
                   for k in xla.counters)
    with interpreted_kernels():
        got, capture = run()
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    [span] = capture.named("engine:hidden")
    assert span["attributes"]["flash_block_share"] == \
        (3 * 5 + 2 * 6) / (5 * 6)
    role = "laguna-d1t1"
    assert capture.counter("flash_kv_blocks_total", role=role,
                           kind="visited") == 3 * 5 + 2 * 6
    assert capture.counter("flash_kv_blocks_total", role=role,
                           kind="causal") == 5 * 6
    # no mask is built for a full layer's two pairs off the diagonal
    assert span["attributes"]["flash_unmasked_share"] == \
        2 * 2 / (3 * 5 + 2 * 6)
    assert capture.counter("flash_kv_blocks_total", role=role,
                           kind="unmasked") == 2 * 2
