"""DeepSeek-V3 (Moonlight-16B-A3B's family) held to its plain reference
(``benchmark/families/deepseek_v3.py``) and to the PUBLISHED modelling
code (``transformers``' ``DeepseekV3ForCausalLM``, which 4.57.6
carries) on the CPU: small widths that keep every mechanism of the
benchmark's cell (hidden 64, 4 heads; latent attention in all five
layers: a latent of 24, keys 16 + 8 rotary wide, values 12 wide, one
rotary key for all heads, the interleaved-pairs convention; a dense
lead of 96, then four layers with 16 experts of width 16, 3 a token by
sigmoid score + selection bias, gates renormalised and times 2.446, and
two shared experts as one SwiGLU of 32), seeded random weights under
Hugging Face's names (``benchmark/generate.py`` makes them, the
program's own loader reads them), everything in float32. Two
checkpoints: one that holds every expert (the uncut model) and one
expert-parallel rank's share (experts 4 to 7 of 16). Documents are 20
tokens, three to a packed row of 64.

Every comparison is float32 against float32 on the same values, so the
tolerances are those of two orders of summation. ``LOGIT_TOL`` is far
over what the packed forward shows and 50 times under the mildest of
the wrong equations (``test_a_wrong_equation_is_outside_the_tolerance``
holds each to that).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import generate, reference
from benchmark.families import deepseek_v3 as family
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
    model_type="deepseek_v3", vocab_size=128, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=16, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=None,
    kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=12, first_k_dense_replace=1, moe_layer_freq=1,
    n_shared_experts=2, num_experts_per_tok=3, n_group=1, topk_group=1,
    norm_topk_prob=True, routed_scaling_factor=2.446,
    scoring_func="sigmoid", topk_method="noaux_tc",
    num_nextn_predict_layers=0, hidden_act="silu", attention_bias=False,
    max_position_embeddings=4096, rms_norm_eps=1e-5, rope_theta=50000,
    rope_interleave=True, tie_word_embeddings=False,
    initializer_range=0.02, eos_token_id=1)
CONFIGS = {
    "whole": dict(_BASE, n_routed_experts=16),
    "share": dict(_BASE, n_routed_experts=4,
                  expert_share={"of": 16, "first": 4}),
}
DOC = 20  # tokens a document; three to a packed row of 64
NAME = "deepseek_v3"
ROLE = "deepseek-d1t1"


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
        ModelName(f"deepseek-d{dp}t{tp}", 0),
        mesh_lib.make_mesh(par, jax.devices()[:dp * tp]), par)
    return Engine(cfg, ctx, jax.tree.map(np.asarray, params), **kwargs)


def _engine_logits(engine, ids, seg):
    hidden = engine.forward_hidden(ids, seg)
    return np.asarray(T.lm_logits(engine.cfg, engine.params, hidden),
                      np.float32)


def test_config_is_read_from_the_published_keys(model):
    cfg, hf = model["cfg"], model["hf"]
    assert cfg.layer_pattern == (("latent", "dense"),) \
        + (("latent", "moe"),) * 4
    assert cfg.pattern_string == "l l l l l"
    assert (cfg.kv_layers, cfg.layers_of("latent"), cfg.layers_of("window"),
            cfg.layers_of("conv"), cfg.n_moe_layers) == (
        5, (0, 1, 2, 3, 4), (), (), 4)
    lat = cfg.latent
    from realhf_tpu.models.config import LATENT_NORM_EPS
    assert (cfg.head_dim, cfg.v_head_dim, lat.kv_rank, lat.rope_dim,
            lat.v_dim, LATENT_NORM_EPS, cfg.layer_norm_epsilon) == (
        24, 12, 24, 8, 12, 1e-6, 1e-5)
    assert cfg.n_kv_heads == cfg.n_q_heads == 4
    rc = cfg.rotary_of("latent")
    assert (rc.base, rc.scaling_type, rc.interleaved) == (50000.0, None, True)
    assert cfg.rotated_dim("latent") == 8 and not cfg.rotary_interleaved
    assert cfg.qk_norm is None and not cfg.attn_output_gate
    assert not cfg.tied_embedding and cfg.mlp_type == "llama"
    moe = cfg.moe
    assert (moe.num_experts, moe.top_k, moe.score_fn, moe.use_expert_bias,
            moe.norm_topk_prob, moe.routing_type, moe.intermediate_dim,
            moe.shared_intermediate_dim, moe.routed_scaling_factor,
            moe.norm_topk_eps) == (
        16, 3, "sigmoid", True, True, "none", 16, 32, 2.446, 1e-20)
    assert moe.experts_held == ((4, 4) if "expert_share" in hf else None)
    assert moe.n_held == hf["n_routed_experts"]
    back = hf_models.config_to_hf(NAME, cfg)
    for key in sorted(set(hf) - {"initializer_range", "eos_token_id"}):
        assert back[key] == hf[key], key
    assert ("expert_share" in back) == ("expert_share" in hf)
    n = sum(x.size for x in jax.tree.leaves(model["params"]))
    assert n == family.n_params(hf)
    # the program's estimate leaves the layer norms' scales out; it
    # counts the latent's five leaves (its norm among them), the
    # selection bias and the shared experts
    assert n_params(cfg) == n - (2 * cfg.n_layers + 1) * cfg.hidden_dim
    init = T.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(
        jnp.shape, model["params"])
    assert set(init["layers"]["1"]["attn"]) == {
        "wq", "w_kv_a", "kv_a_norm", "w_kv_b", "wo"}


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 32), ("n_group", 2), ("topk_group", 2),
    ("rope_scaling", {"type": "yarn", "factor": 40, "mscale": 1.0,
                      "mscale_all_dim": 1.0}),
    ("num_nextn_predict_layers", 1), ("moe_layer_freq", 2),
    ("rope_interleave", False), ("attention_bias", True),
    ("scoring_func", "softmax"), ("topk_method", "greedy"),
])
def test_what_the_family_cannot_run_is_refused_by_name(key, value):
    """In the program's reading and in the reference's alike: query
    compression, YaRN and its ``mscale``, group-limited selection and
    multi-token prediction are named, not guessed."""
    hf = dict(CONFIGS["whole"], **{key: value})
    with pytest.raises(NotImplementedError, match=key):
        hf_models.config_from_hf(NAME, hf)
    with pytest.raises(NotImplementedError, match=key):
        family.dims(hf)


def test_the_published_modelling_code_gives_the_same_logits(built):
    """``transformers``' own ``DeepseekV3ForCausalLM`` (eager
    attention, float32) on the generator's checkpoint: the reference
    and the program both give ITS logits, so the equations are the
    published module's and not a reading of them (the latent norm's
    epsilon of 1e-6 and the de-interleaved rotary pairs among them)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "DeepseekV3ForCausalLM"):
        pytest.skip("this transformers has no deepseek_v3")
    model = built("whole")
    hf = {k: v for k, v in model["hf"].items() if k != "model_type"}
    conf = transformers.DeepseekV3Config(**hf)
    conf._attn_implementation = "eager"
    net = transformers.DeepseekV3ForCausalLM(conf).float().eval()
    state = {k: torch.tensor(np.asarray(v, np.float32))
             for k, v in model["tensors"].items()}
    loaded = net.load_state_dict(state, strict=False)
    assert not loaded.unexpected_keys and not loaded.missing_keys
    with torch.no_grad():
        theirs = net(torch.tensor(model["docs"], dtype=torch.long)
                     ).logits.numpy()
    assert np.abs(model["want"] - theirs).max() < LOGIT_TOL
    seg = np.ones_like(model["docs"])
    got = _engine_logits(_engine(model["cfg"], model["params"]),
                         model["docs"], seg)
    assert np.abs(got - theirs).max() < LOGIT_TOL


def test_packed_row_of_three_documents_equals_the_documents_alone(model):
    """Positions, and so the rotary key every head shares, restart at
    each document of a packed row; no score crosses a boundary. Each
    document gets the logits the reference gives it alone, and the
    reference given the packed row says the same."""
    ids, seg = _packed(model["docs"])
    got = _engine_logits(_engine(model["cfg"], model["params"]), ids, seg)
    got = got[0, :3 * DOC].reshape(3, DOC, -1)
    assert np.abs(got - model["want"]).max() < LOGIT_TOL
    assert model["want"].std() > 0.1  # the logits are not all alike
    packed = family.logits(model["hf"], model["tensors"], ids, seg)
    assert np.abs(packed[0, :3 * DOC].reshape(3, DOC, -1)
                  - model["want"]).max() < LOGIT_TOL


@pytest.mark.parametrize("wrong", family.WRONG + (
    "positions_of_the_row", "latent_norm_at_rms_norm_eps"))
def test_a_wrong_equation_is_outside_the_tolerance(built, wrong):
    model = built("share")
    hf = model["hf"]
    tol = 50 * LOGIT_TOL
    if wrong == "positions_of_the_row":
        # rotary positions of the ROW, documents ignored: what a packed
        # row gives a model that forgot them
        ids, _ = _packed(model["docs"])
        got = family.logits(hf, model["tensors"], ids[:, :3 * DOC])
        got = got[0].reshape(3, DOC, -1)
    elif wrong == "latent_norm_at_rms_norm_eps":
        # the mildest misreading there is: kv_a_layernorm at 1e-5 where
        # the module norms at 1e-6. The latent's mean square is 0.03
        # here, so it moves c by 1.5e-4 of itself: 3 LOGIT_TOL, not 50
        tol = 3 * LOGIT_TOL
        old = family.LATENT_NORM_EPS
        family.LATENT_NORM_EPS = hf["rms_norm_eps"]
        try:
            got = family.logits(hf, model["tensors"], model["docs"])
        finally:
            family.LATENT_NORM_EPS = old
    else:
        got = family.logits(hf, model["tensors"], model["docs"],
                            wrong=(wrong,))
    assert np.abs(got - model["want"]).max() > tol


@pytest.mark.parametrize("dp,tp", [(1, 2), (2, 1)])
def test_sharded_forward_matches_one_device(model, dp, tp):
    """The expansion's columns and the heads under tensor parallelism
    (the compression on every shard), the shared experts, the held
    experts' ragged products under data parallelism: the same logits
    as on one device, and so the reference's."""
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
    """``engine/generation.py``'s two steps, teacher-forced: the cache
    holds the EXPANDED keys (nope + rope wide, the shared rotary key
    copied to every head) and the values at their own width, and the
    decode attention takes both."""
    cfg, params, docs = model["cfg"], model["params"], model["docs"]
    ids = jnp.asarray(docs[:, :n_pre])
    hidden, cache = jax.jit(
        lambda p, i: T.prefill(cfg, p, i, jnp.ones_like(i),
                               total_len=DOC))(params, ids)
    assert cache["k"].shape == (5, len(docs), 4, DOC, 24)
    assert cache["v"].shape == (5, len(docs), 4, DOC, 12)
    empty = T.init_kv_cache(cfg, len(docs), DOC)
    assert (empty["k"].shape, empty["v"].shape) == (
        cache["k"].shape, cache["v"].shape)
    grown = T.extend_kv_cache(empty, 4)
    assert (grown["k"].shape[3:], grown["v"].shape[3:]) == ((24, 24),
                                                            (24, 12))
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
    """``generate``'s prompts are left-padded and of unequal lengths:
    each stream generates what it would alone, through the program's
    own generate (prefill, the decode loop, sampling)."""
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


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_sft_loss_and_gradient_match_reference(model, remat):
    """Loss and the gradient of every leaf (the compression's, the
    latent norm's, the expansion's, the shared experts' among them)
    against ``jax.grad`` of the reference, three documents and four
    pads a row; rematerialised (the kept residuals at two widths) as
    the experiments run it, and not. No gradient reaches the selection
    bias, in the program as in the reference."""
    model = dict(model, cfg=dataclasses.replace(
        model["cfg"], gradient_checkpointing=remat))
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
        if name.endswith("e_score_correction_bias"):
            assert scale == 0 and gap == 0, name
            continue
        # (3 of 16 experts a token, 60 tokens: an expert may get none)
        assert scale > 0 or ".experts." in name, name
        assert gap <= 2e-5 * scale + 1e-12, (name, gap, scale)


def test_held_statistics_are_the_reference_routings_counts(built):
    """What the train step returns beside the loss for a share: the
    pairs routed to HELD experts, over the sparse layers, and the
    busiest held expert over the mean of all, on a row with no pads,
    against the counts of the reference's own routing (score + bias
    chooses)."""
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
        assert counts.sum() == 64 * 3
        pairs += counts[held].sum()
        worst_held = max(worst_held, counts[held].max() / counts.mean())
        worst = max(worst, counts.max() / counts.mean())
    assert float(stats["moe_held_pairs"]) == pairs
    assert float(stats["moe_held_load_max_over_mean"]) == \
        pytest.approx(worst_held)
    assert float(stats["moe_load_max_over_mean"]) == pytest.approx(worst)
    assert 0 < pairs < 4 * 64 * 3


def test_train_step_spans_say_what_ran(built):
    """One optimizer step through ``Engine.train_batch``: the span's
    attributes latent layers bring, every new leaf moved, the selection
    bias left as loaded."""
    from realhf_tpu.obs import tracing
    model = built("share")
    cfg = dataclasses.replace(
        model["cfg"], gradient_checkpointing=True)
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
        a0, a1 = (p["layers"][str(i)]["attn"] for p in (before, after))
        for leaf in ("wq", "w_kv_a", "kv_a_norm", "w_kv_b", "wo"):
            assert not np.array_equal(a0[leaf], a1[leaf]), (i, leaf)
    for i in range(1, 5):
        m0, m1 = (p["layers"][str(i)]["mlp"] for p in (before, after))
        assert not np.array_equal(m0["shared"]["wd"], m1["shared"]["wd"])
        assert not np.array_equal(m0["router"], m1["router"])
        assert np.array_equal(m0["expert_bias"], m1["expert_bias"])
        assert np.abs(m0["expert_bias"]).max() > 0
    [span] = capture.named("engine:train")
    a = span["attributes"]
    assert (a["layer_pattern"], a["latent_layers"], a["kv_lora_rank"],
            a["qk_dim"], a["v_dim"], a["shared_expert"],
            a["experts_held"], a["experts"], a["top_k"], a["router"],
            a["moe_dispatch"], a["conv_layers"], a["dense_layers"]) == (
        "l l l l l", 5, 24, 24, 12, 32, 4, 16, 3, "sigmoid_bias",
        "ragged", 0, 1)
    assert a["rotary"] == "l:plain@50000/0.333333/interleaved"
    assert "window" not in a and "q_heads" not in a
    tokens = 2 * 3 * DOC
    assert capture.counter("moe_routed_pairs_total", role=ROLE,
                           dispatch="ragged") == tokens * 3 * 4
    held = capture.counter("moe_held_pairs_total", role=ROLE)
    assert held == stats["moe_held_pairs"] == a["moe_held_pairs"]
    assert 0 < held < 2 * 64 * 3 * 4


def test_the_latents_projections_are_a_sub_part_of_attn_proj(built):
    """``obs/parts.py``: what makes keys and values from the latent
    lowers under ``attn_proj/latent`` (forward, rematerialised and
    backward), the query's and the output's products under
    ``attn_proj`` itself, and a reader of the whole part
    (``train.attn_proj_s``) still holds both."""
    from realhf_tpu.obs import parts
    model = built("share")
    cfg = dataclasses.replace(
        model["cfg"], gradient_checkpointing=True)
    loss_fn = sft._make_loss_fn(cfg)
    ids, seg = _packed(model["docs"])
    mb = dict(input_ids=jnp.asarray(ids), seg_ids=jnp.asarray(seg),
              prompt_mask=jnp.zeros((1, 64), bool))

    def objective(p):
        h, _ = T.forward(cfg, p, mb["input_ids"], mb["seg_ids"])
        return loss_fn(p, h, mb)[0]

    text = jax.jit(jax.grad(objective)).lower(
        model["params"]).compile().as_text()
    sub = f"{parts.ATTN_PROJ}/{parts.LATENT}"
    # a layer: the compression and the expansion, in every pass; the
    # backward holds two products for each (dW and dx) but the
    # compression's dx, which XLA may fuse with the query's
    assert parts.count_products(text, sub, parts.FWD) == 2 * 5
    assert parts.count_products(text, sub, parts.REMAT) == 2 * 5
    assert parts.count_products(text, sub, parts.BWD) >= 3 * 5
    # q and the projected output are kept: no product of the part
    # itself is run again
    assert parts.count_products(text, parts.ATTN_PROJ, parts.REMAT) == 0
    assert parts.count_products(text, parts.ATTN_PROJ, parts.FWD) == 2 * 5
    ops = parts.parse_program(text)
    named = {v[0] for v in ops.values()}
    assert {sub, parts.ATTN_PROJ, parts.ATTN} <= named
    assert parts.classify(
        "jit(f)/transpose(jvp(layers))/attn_proj/latent/dot_general"
    )[:2] == (sub, parts.BWD)
    assert parts.classify("jit(f)/attn_proj/rotary/mul")[0] == \
        parts.ATTN_PROJ
    # a scope named `latent` outside attn_proj is nobody's sub-part
    assert parts.classify("jit(f)/mlp/latent/dot_general")[0] == parts.MLP


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
    assert saved["model_type"] == "deepseek_v3"
    assert saved.get("expert_share") == model["hf"].get("expert_share")
    assert (saved["kv_lora_rank"], saved["qk_rope_head_dim"],
            saved["v_head_dim"], saved["q_lora_rank"]) == (24, 8, 12, None)
    assert registry.detect_family(path) == NAME
    ccfg, critic = registry.load_hf_checkpoint(path, NAME, is_critic=True)
    assert ccfg.is_critic and critic["head"]["w"].shape == (64, 1)
    np.testing.assert_array_equal(
        critic["layers"]["2"]["attn"]["w_kv_b"],
        np.asarray(model["params"]["layers"]["2"]["attn"]["w_kv_b"]))


@pytest.mark.parametrize("tp", [1, 2])
def test_streamed_load_and_save_round_trip(model, tmp_path, tp):
    """A layer at a time onto a mesh, and back into one file a layer,
    bit for bit what the generator wrote; the new leaves' specs."""
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
    attn = params["layers"]["1"]["attn"]
    assert attn["w_kv_b"].sharding.spec == spec(None, "model")
    assert attn["wo"].sharding.spec == spec("model", None)
    assert attn["w_kv_a"].sharding.spec == spec(None, None)
    assert attn["kv_a_norm"].sharding.spec == spec(None)
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
    """The slot engine, the paged pool, pipeline stages and the
    allocation search know one kind of block: under latent layers too
    they raise, naming them."""
    from realhf_tpu.engine import inflight, kv_pool
    from realhf_tpu.models import sharding
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    model = built("share")
    cfg, params = model["cfg"], model["params"]
    g = GenerationHyperparameters(max_new_tokens=2, greedy=True,
                                  force_no_logits_mask=True)
    named = r"layer pattern \(layer_pattern 'l l l l l': 5 latent layers"
    with pytest.raises(NotImplementedError, match="slot engine.*" + named):
        inflight.InflightBatchingGenerator(
            cfg, params, g, n_slots=2, max_prompt_len=8,
            eos_token_id=None, pad_token_id=0)
    with pytest.raises(NotImplementedError, match="KV pool.*" + named):
        kv_pool.KVPool(cfg, n_blocks=4, block_len=8)
    with pytest.raises(NotImplementedError, match="pipeline.*" + named):
        sharding.param_pspecs(cfg, pipeline_parallel=True)


def test_the_config_says_what_a_latent_layer_may_be():
    """``TransformerConfig``: latent layers need their ``LatentConfig``
    and a rotary embedding of their kind, have a key a query head, and
    stand beside no other attention operator (one K/V shape a stack);
    a kind of layer may now say the interleaved convention, the
    model-wide switch beside ``rotary_by_operator`` still may not."""
    from realhf_tpu.models.config import (
        LatentConfig,
        RotaryConfig,
        TransformerConfig,
    )
    base = dict(n_layers=2, n_kv_heads=4, n_q_heads=4, hidden_dim=64,
                head_dim=24, intermediate_dim=96, vocab_size=128,
                layer_norm_type="rms", mlp_type="llama", apply_rotary=True,
                use_attention_bias=False, use_attn_proj_bias=False)
    lat = LatentConfig(kv_rank=24, rope_dim=8, v_dim=12)
    rot = {"latent": RotaryConfig(base=50000.0, interleaved=True)}
    two = (("latent", "dense"),) * 2
    cfg = TransformerConfig(**base, layer_pattern=two, latent=lat,
                            rotary_by_operator=rot)
    assert cfg.v_head_dim == 12 and cfg.rotated_dim("latent") == 8
    with pytest.raises(ValueError, match="latent is None"):
        TransformerConfig(**base, layer_pattern=two, rotary_by_operator=rot)
    with pytest.raises(ValueError, match="0 latent layers"):
        TransformerConfig(**base, layer_pattern=(("attention", "dense"),) * 2,
                          latent=lat)
    with pytest.raises(NotImplementedError, match="ONE shape"):
        TransformerConfig(
            **base, layer_pattern=(("latent", "dense"),
                                   ("attention", "dense")), latent=lat,
            rotary_by_operator=dict(rot, attention=RotaryConfig()))
    with pytest.raises(NotImplementedError, match="rotary_by_operator"):
        TransformerConfig(**base, layer_pattern=two, latent=lat)
    with pytest.raises(NotImplementedError, match="a key a query head"):
        TransformerConfig(**dict(base, n_kv_heads=2), layer_pattern=two,
                          latent=lat, rotary_by_operator=rot)
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        TransformerConfig(**base, latent=lat)
    # the convention a kind of layer: accepted; model-wide beside it: not
    plain = (("attention", "dense"),) * 2
    mixed = TransformerConfig(
        **base, layer_pattern=plain,
        rotary_by_operator={"attention": RotaryConfig(interleaved=True)})
    assert mixed.rotary_of("attention").interleaved
    with pytest.raises(ValueError, match="a kind of layer"):
        TransformerConfig(
            **base, layer_pattern=plain, rotary_interleaved=True,
            rotary_by_operator={"attention": RotaryConfig()})
    one = TransformerConfig(**{k: v for k, v in base.items()},
                            rotary_interleaved=True)
    assert one.rotary_of("attention").interleaved


def test_latent_stack_through_the_flash_kernels(interpreted_kernels):
    """Keys of 64 + 64 = 128 and values of 64, rows of 1024, so that
    the packed rows meet the flash kernels' gate: with the kernels
    engaged (interpret mode) at TWO widths the stack gives the XLA
    path's hidden states, and ``flash_kv_blocks_total`` counts every
    latent layer's blocks as a full layer's."""

    from realhf_tpu.obs import tracing
    hf = dict(CONFIGS["share"], hidden_size=128, num_attention_heads=2,
              num_key_value_heads=2, qk_nope_head_dim=64,
              qk_rope_head_dim=64, v_head_dim=64, num_hidden_layers=2)
    cfg = hf_models.config_from_hf(NAME, hf)
    cfg.param_dtype = cfg.compute_dtype = "float32"
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(
        2, 128, size=(1, 1024)).astype(np.int32)
    seg = np.ones((1, 1024), np.int32)
    seg[0, 700:] = 2

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
    assert capture.counter("flash_kv_blocks_total", role=ROLE,
                           kind="causal") == 2 * 6
    assert 0 < capture.counter("flash_kv_blocks_total", role=ROLE,
                               kind="visited") <= 2 * 6
