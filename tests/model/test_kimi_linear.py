"""Kimi-Linear (Kimi-Linear-48B-A3B-Instruct's family) held to its plain
reference (``benchmark/families/kimi_linear.py``, which takes the
delta-rule recurrence a TOKEN at a time) on the CPU: small widths that
keep every mechanism of the benchmark's cell (hidden 64; layers 1 to 5
of the published pattern, delta + dense, delta, delta, latent, delta,
the last four sparse; 4 delta heads of 16 with 4-tap convolutions and
gates through a rank of 16; latent attention WITHOUT a rotary at 4
heads, keys 16 + 8 wide, values 12, a latent of 24; 16 experts of 16, 3
a token by sigmoid score + selection bias, gates renormalised and times
2.446, one shared expert), seeded random weights under Hugging Face's
names (``benchmark/generate.py`` makes them, the program's own loader
reads them), everything in float32. Two checkpoints (every expert; one
expert-parallel rank's share, experts 4 to 7 of 16), each under two
initialisations of the decay: the harness's (``A_log`` and ``dt_bias``
near 0: a state halves every token) and the published one (a state
lives hundreds of tokens), where a dropped chunk boundary shows.
Documents are 150 tokens (two chunks of 64 and a part of a third), and
a packed row of 256 holds documents of 70, 101 and 60 tokens with
boundaries inside a chunk and inside a 16-token sub-block.

Every comparison is float32 against float32 on the same values, so the
tolerances are those of two orders of summation.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import generate, reference
from benchmark.families import kimi_linear as family
from realhf_tpu.api.config import ModelName
from realhf_tpu.engine.engine import Engine
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.interfaces import sft
from realhf_tpu.models import hf as hf_models
from realhf_tpu.models import transformer as T
from realhf_tpu.models.hf import registry
from realhf_tpu.parallel import mesh as mesh_lib

#: max |delta logit| allowed between the program and the reference
LOGIT_TOL = 2e-5

_BASE = dict(
    model_type="kimi_linear", vocab_size=128, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=16, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    linear_attn_config=dict(
        kda_layers=[1, 2, 3, 5], full_attn_layers=[4], num_heads=4,
        head_dim=16, short_conv_kernel_size=4),
    mla_use_nope=True, q_lora_rank=None, kv_lora_rank=24,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
    first_k_dense_replace=1, moe_layer_freq=1, num_shared_experts=1,
    num_experts_per_token=3, use_grouped_topk=True, num_expert_group=1,
    topk_group=1, moe_renormalize=True,
    moe_router_activation_func="sigmoid", routed_scaling_factor=2.446,
    num_nextn_predict_layers=0, hidden_act="silu",
    model_max_length=4096, rms_norm_eps=1e-5, rope_theta=10000,
    rope_scaling=None, tie_word_embeddings=False, initializer_range=0.02,
    eos_token_id=1)
CONFIGS = {
    "whole": dict(_BASE, num_experts=16),
    "share": dict(_BASE, num_experts=4,
                  expert_share={"of": 16, "first": 4}),
}
DECAYS = ("harness", "published")
DOC = 150
ROW, DOCS_IN_ROW = 256, (70, 101, 60)  # ends at 70, 171, 231
NAME = "kimi_linear"
ROLE = "kimi-d1t1"


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """(name, decay) -> a checkpoint the benchmark's generator wrote
    (under ``published`` its decay tensors overwritten by
    ``family.published_decay``), read by the program's loader (float32
    parameters and compute) and, file by file, by the reference; each
    made once a module."""
    import safetensors.numpy
    made = {}

    def get(name, decay="harness"):
        if (name, decay) not in made:
            hf = CONFIGS[name]
            ckpt = str(tmp_path_factory.mktemp(f"{name}-{decay}"))
            generate.write_checkpoint(ckpt, family, hf, seed=11)
            if decay == "published":
                path = os.path.join(ckpt, "model.safetensors")
                safetensors.numpy.save_file(family.published_decay(
                    hf, reference.load_tensors(ckpt), seed=5), path)
            cfg, params = registry.load_hf_checkpoint(ckpt, NAME)
            cfg.param_dtype = cfg.compute_dtype = "float32"
            params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                                  params)
            docs = np.random.default_rng(3).integers(
                2, hf["vocab_size"], size=(2, DOC)).astype(np.int32)
            tensors = reference.load_tensors(ckpt)
            made[name, decay] = dict(
                hf=hf, ckpt=ckpt, cfg=cfg, params=params, docs=docs,
                tensors=tensors, want=family.logits(hf, tensors, docs))
        return made[name, decay]
    return get


@pytest.fixture(params=[(n, d) for n in sorted(CONFIGS) for d in DECAYS],
                ids=lambda p: "-".join(p))
def model(request, built):
    return built(*request.param)


def _packed(rng_seed=4):
    """Documents of 70, 101 and 60 tokens and 25 pads a row of 256:
    (ids, seg, the documents)."""
    rng = np.random.default_rng(rng_seed)
    ids = np.zeros((1, ROW), np.int32)
    seg = np.zeros((1, ROW), np.int32)
    docs, at = [], 0
    for j, n in enumerate(DOCS_IN_ROW):
        docs.append(rng.integers(2, 128, size=(1, n)).astype(np.int32))
        ids[0, at:at + n], seg[0, at:at + n] = docs[-1][0], j + 1
        at += n
    return ids, seg, docs


def _engine(cfg, params, dp=1, tp=1, **kwargs):
    par = mesh_lib.ParallelismConfig(data_parallel_size=dp,
                                     tensor_parallel_size=tp)
    ctx = mesh_lib.MeshContext(
        ModelName(f"kimi-d{dp}t{tp}", 0),
        mesh_lib.make_mesh(par, jax.devices()[:dp * tp]), par)
    return Engine(cfg, ctx, jax.tree.map(np.asarray, params), **kwargs)


def _engine_logits(engine, ids, seg):
    hidden = engine.forward_hidden(ids, seg)
    return np.asarray(T.lm_logits(engine.cfg, engine.params, hidden),
                      np.float32)


def test_config_is_read_from_the_published_keys(built):
    from realhf_tpu.models.config import DeltaConfig, LatentConfig
    model = built("share")
    cfg, hf = model["cfg"], model["hf"]
    assert cfg.layer_pattern == (
        ("delta", "dense"), ("delta", "moe"), ("delta", "moe"),
        ("latent", "moe"), ("delta", "moe"))
    assert cfg.pattern_string == "d d d l d"
    assert (cfg.delta_layers, cfg.attention_layers, cfg.latent_layers,
            cfg.conv_layers, cfg.window_layers, cfg.n_moe_layers) == (
        (0, 1, 2, 4), (3,), (3,), (), (), 4)
    assert cfg.delta == DeltaConfig(n_heads=4, head_dim=16, conv_kernel=4)
    assert cfg.delta.gate_rank == 16
    assert cfg.latent == LatentConfig(kv_rank=24, rope_dim=8, v_dim=12)
    assert (cfg.head_dim, cfg.v_head_dim, cfg.n_kv_heads, cfg.n_q_heads,
            cfg.layer_norm_epsilon) == (24, 12, 4, 4, 1e-5)
    # NO rotary embedding anywhere: the latent layers say so
    assert cfg.rotary_by_operator == {"latent": None}
    assert cfg.rotary_of("latent") is None
    moe = cfg.moe
    assert (moe.num_experts, moe.n_held, moe.experts_held, moe.top_k,
            moe.score_fn, moe.use_expert_bias, moe.norm_topk_prob,
            moe.routed_scaling_factor, moe.norm_topk_eps,
            moe.intermediate_dim, moe.shared_intermediate_dim) == (
        16, 4, (4, 4), 3, "sigmoid", True, True, 2.446, 1e-20, 16, 16)
    back = hf_models.config_to_hf(NAME, cfg)
    for key, value in hf.items():
        if key not in ("initializer_range", "eos_token_id", "rope_theta"):
            assert back[key] == value, key
    again = hf_models.config_from_hf(NAME, back)
    again.param_dtype = again.compute_dtype = "float32"
    assert again == cfg
    # the family's count is the checkpoint's; the program's leaves out
    # the layers' norms and the final one
    held = sum(v.size for v in model["tensors"].values())
    assert family.n_params(hf) == held
    assert cfg.n_params() == held - (2 * 5 + 1) * 64


@pytest.mark.parametrize("key,value", [
    ("mla_use_nope", False), ("q_lora_rank", 32),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("num_expert_group", 4), ("topk_group", 2),
    ("moe_router_activation_func", "softmax"),
    ("num_nextn_predict_layers", 1), ("moe_layer_freq", 2),
    ("linear_attn_config", dict(_BASE["linear_attn_config"],
                                full_attn_layers=[3, 4])),
    ("linear_attn_config", dict(_BASE["linear_attn_config"],
                                kda_layers=[1, 2, 3])),
])
def test_what_the_family_cannot_run_is_refused_by_name(key, value):
    hf = dict(CONFIGS["whole"], **{key: value})
    with pytest.raises(NotImplementedError,
                       match="kda_layers" if key == "linear_attn_config"
                       else key):
        hf_models.config_from_hf(NAME, hf)
    with pytest.raises(NotImplementedError):
        family.dims(hf)


def test_whole_documents_equal_the_reference(model):
    """Rows of 150 tokens: the chunked scan over two whole chunks and a
    part of a third against the recurrence token by token, the NoPE
    latent layer, the router over 16 with the held experts."""
    docs = model["docs"]
    got = _engine_logits(_engine(model["cfg"], model["params"]), docs,
                         np.ones_like(docs))
    assert np.abs(got - model["want"]).max() < LOGIT_TOL
    assert model["want"].std() > 0.1  # the logits are not all alike


def test_packed_row_equals_its_documents_alone(model):
    """The state is reset, the convolutions stop and no score crosses
    at a document's first token: each document of a packed row gets the
    logits the reference gives it ALONE, with boundaries inside a chunk
    (70) and inside a 16-token sub-block (171), and the reference given
    the packed row says the same."""
    ids, seg, docs = _packed()
    got = _engine_logits(_engine(model["cfg"], model["params"]), ids, seg)
    packed = family.logits(model["hf"], model["tensors"], ids, seg)
    at = 0
    for doc in docs:
        alone = family.logits(model["hf"], model["tensors"], doc)
        n = doc.shape[1]
        assert np.abs(got[:, at:at + n] - alone).max() < LOGIT_TOL
        assert np.abs(packed[:, at:at + n] - alone).max() < LOGIT_TOL
        at += n


@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("wrong", family.WRONG)
def test_a_wrong_equation_is_outside_the_tolerance(built, wrong, decay):
    """Every near-miss of the list, under both initialisations, on the
    packed row where the entry is about documents and on whole
    documents where it is not: at least 50 tolerances away. (A state
    dropped at a chunk boundary shows under the harness's weights too:
    the state a boundary drops holds the LAST tokens at weights of a
    half, a quarter..., not only those 64 back.)"""
    model = built("share", decay)
    hf, tensors = model["hf"], model["tensors"]
    if wrong in ("state_over_documents", "conv_over_documents"):
        ids, seg, _ = _packed()
        want = family.logits(hf, tensors, ids, seg)
        got = family.logits(hf, tensors, ids, seg, wrong=(wrong,))
        valid = (seg != 0)[..., None]
        gap = np.abs(np.where(valid, got - want, 0)).max()
    else:
        got = family.logits(hf, tensors, model["docs"], wrong=(wrong,))
        gap = np.abs(got - model["want"]).max()
    assert gap > 50 * LOGIT_TOL, gap


@pytest.mark.parametrize("dp,tp", [(1, 2), (2, 1)])
def test_sharded_forward_matches_one_device(built, dp, tp):
    """The delta layers' heads, their convolutions' channels and the
    decay's leaves under tensor parallelism, the held experts' ragged
    products under data parallelism: the same logits as on one device,
    and so the reference's."""
    model = built("share", "published")
    docs = model["docs"]
    got = _engine_logits(_engine(model["cfg"], model["params"], dp, tp),
                         docs, np.ones_like(docs))
    assert np.abs(got - model["want"]).max() < LOGIT_TOL


@pytest.mark.parametrize("n_pre", [70, 1])
def test_prefill_then_decode_matches_full_forward(model, n_pre):
    """``engine/generation.py``'s two steps, teacher-forced: the THIRD
    kind of decode state (a float32 [hd, hd] a head a delta layer, and
    the last three rows of its convolutions' inputs) beside the K/V of
    the one latent layer; prefill leaves the state after its last
    token, a decode step moves it on by the recurrence itself."""
    cfg, params, docs = model["cfg"], model["params"], model["docs"]
    total = 96
    ids = jnp.asarray(docs[:, :n_pre])
    hidden, cache = jax.jit(
        lambda p, i: T.prefill(cfg, p, i, jnp.ones_like(i),
                               total_len=total))(params, ids)
    assert cache["k"].shape == (1, len(docs), 4, total, 24)
    assert cache["v"].shape == (1, len(docs), 4, total, 12)
    assert cache["delta"].shape == (4, len(docs), 4, 16, 16)
    assert cache["delta"].dtype == jnp.float32
    assert cache["delta_conv"].shape == (4, len(docs), 3, 3 * 64)
    assert "conv" not in cache
    empty = T.init_kv_cache(cfg, len(docs), total)
    assert {k: (v.shape, v.dtype) for k, v in empty.items()} == \
        {k: (v.shape, v.dtype) for k, v in cache.items()}
    assert T.delta_state_shapes(cfg, len(docs)) == (
        cache["delta_conv"].shape, cache["delta"].shape)
    got = [np.asarray(T.lm_logits(cfg, params, hidden))]
    step = jax.jit(lambda p, c, t, pos: T.decode_step(
        cfg, p, c, t, pos, uniform_slot=True))
    for t in range(n_pre, total):
        h, cache = step(params, cache, jnp.asarray(docs[:, t]),
                        jnp.full((len(docs),), t, jnp.int32))
        got.append(np.asarray(T.lm_logits(cfg, params, h))[:, None])
    got = np.concatenate(got, axis=1)
    assert np.abs(got - model["want"][:, :total]).max() < LOGIT_TOL


def test_left_padded_prompts_generate_as_unpadded_ones(built):
    """``generate``'s prompts are left-padded and of unequal lengths:
    the state stays 0 through the padding, the convolutions' tails hold
    0 where the row had padding, and each stream generates what it
    would alone, through the program's own generate."""
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    model = built("share", "published")
    cfg, docs = model["cfg"], model["docs"]
    engine = _engine(cfg, model["params"])
    lens = [70, 2]
    lp = 72
    ids = np.zeros((2, lp), np.int32)
    seg = np.zeros((2, lp), np.int32)
    pos = np.zeros((2, lp), np.int32)
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


def _sft_case(model, prompt_len):
    """One SFT microbatch, the packed row: (program's loss, stats,
    gradient under HF's names), (reference's loss, parts, gradient).
    The reference takes documents of one length: two of 70 tokens, a
    row of 160 with padding after them."""
    cfg, params = model["cfg"], model["params"]
    docs = model["docs"][:, :70]
    ids = np.zeros((1, 160), np.int32)
    seg = np.zeros((1, 160), np.int32)
    prompt = np.zeros((1, 160), bool)
    for j in range(2):
        ids[0, j * 70:(j + 1) * 70], seg[0, j * 70:(j + 1) * 70] = \
            docs[j], j + 1
        prompt[0, j * 70:j * 70 + prompt_len] = True
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
@pytest.mark.parametrize("decay", DECAYS)
def test_sft_loss_and_gradient_match_reference(built, remat, decay):
    """Loss and the gradient of EVERY leaf (the taps, ``A_log``,
    ``dt_bias``, the two gates' two-step projections, ``o_norm`` among
    them) against ``jax.grad`` of the reference's token-by-token
    recurrence, two documents and padding a row, a boundary inside a
    chunk; rematerialised (the scan's output kept, its backward a
    segment at a time) as the experiments run it, and not. No gradient
    reaches the selection bias."""
    model = built("share", decay)
    model = dict(model, cfg=dataclasses.replace(
        model["cfg"], gradient_checkpointing=remat))
    (loss, stats, grads), (ref_loss, parts, ref_grads) = _sft_case(
        model, prompt_len=5)
    assert abs(loss - ref_loss) < 1e-5
    assert abs(stats["nll"] - parts["nll"]) < 1e-5
    assert set(grads) == set(ref_grads)
    for name in sorted(grads):
        scale = np.abs(ref_grads[name]).max()
        gap = np.abs(grads[name].reshape(ref_grads[name].shape)
                     - ref_grads[name]).max()
        if name.endswith("e_score_correction_bias"):
            assert scale == 0 and gap == 0, name
            continue
        assert scale > 0 or ".experts." in name, name
        assert gap <= 5e-5 * scale + 1e-12, (name, gap, scale)


def test_the_shares_parts_add_up_to_the_uncut_layer(built):
    """The share: what the four shares of a sparse layer give (experts
    0-3, 4-7, 8-11, 12-15 of 16, each routing over all 16), with what
    every rank computes alike (the mixer, the shared expert) counted
    ONCE, adds up to what the uncut reference gives for the layer; and
    the program's share is the reference's share."""
    whole = built("whole", "published")
    tensors, ids = whole["tensors"], jnp.asarray(whole["docs"][:, :40])
    hf = dict(whole["hf"], num_hidden_layers=2, linear_attn_config=dict(
        whole["hf"]["linear_attn_config"], kda_layers=[1, 2],
        full_attn_layers=[]))
    get = family._getter(tensors, None)

    def after_the_layer(first, count):
        with jax.default_matmul_precision("highest"):
            return np.asarray(family._blocks(
                dict(hf, num_experts=count,
                     expert_share={"of": 16, "first": first}), get,
                ids)[0])

    uncut = after_the_layer(0, 16)
    alike = after_the_layer(0, 0)  # no expert held: mixer and shared
    routed = sum(after_the_layer(f, 4) - alike for f in (0, 4, 8, 12))
    assert np.abs(uncut - alike).max() > 1e-3
    assert np.abs(alike + routed - uncut).max() \
        < 2e-5 * np.abs(uncut).max()
    share = built("share", "published")
    got = _engine_logits(_engine(share["cfg"], share["params"]),
                         share["docs"], np.ones_like(share["docs"]))
    assert np.abs(got - share["want"]).max() < LOGIT_TOL


def test_train_step_spans_say_what_ran(built):
    """One optimizer step through ``Engine.train_batch``: the span's
    attributes delta layers bring, the counter, every new leaf moved,
    the selection bias left as loaded."""
    from realhf_tpu.obs import tracing
    from realhf_tpu.ops.delta_rule import CHUNK
    model = built("share", "published")
    cfg = dataclasses.replace(
        model["cfg"], gradient_checkpointing=True)
    engine = _engine(cfg, model["params"], optimizer=OptimizerConfig(
        lr=1e-2, warmup_steps_proportion=0.0,
        lr_scheduler_type="constant"), total_train_steps=10)
    ids, seg, _ = _packed()
    mb = dict(input_ids=ids, seg_ids=seg,
              prompt_mask=np.zeros((1, ROW), bool))
    before = jax.tree.map(np.asarray, engine.params)
    tracing.start()
    stats = engine.train_batch([mb, mb], sft._make_loss_fn(cfg),
                               loss_fn_key="sft")
    capture = tracing.stop()
    after = jax.tree.map(np.asarray, engine.params)
    for i in cfg.delta_layers:
        d0, d1 = (p["layers"][str(i)]["delta"] for p in (before, after))
        assert sorted(d0) == sorted(
            "wq wk wv conv_q conv_k conv_v a_log w_fa w_fb dt_bias w_b "
            "w_ga w_gb o_norm wo".split())
        for leaf in d0:
            assert not np.array_equal(d0[leaf], d1[leaf]), (i, leaf)
    for i in range(1, 5):
        m0, m1 = (p["layers"][str(i)]["mlp"] for p in (before, after))
        assert not np.array_equal(m0["router"], m1["router"])
        assert np.array_equal(m0["expert_bias"], m1["expert_bias"])
    [span] = capture.named("engine:train")
    a = span["attributes"]
    assert (a["layer_pattern"], a["delta_layers"], a["delta_heads"],
            a["delta_head_dim"], a["delta_chunk"], a["latent_layers"],
            a["kv_lora_rank"], a["qk_dim"], a["v_dim"],
            a["shared_expert"], a["experts_held"], a["experts"],
            a["top_k"], a["router"], a["conv_layers"],
            a["dense_layers"]) == (
        "d d d l d", 4, 4, 16, CHUNK, 1, 24, 24, 12, 16, 4, 16, 3,
        "sigmoid_bias", 0, 1)
    assert a["rotary"] == "l:none"
    tokens = 2 * sum(DOCS_IN_ROW)
    assert capture.counter("delta_tokens_total", role=ROLE) == tokens * 4
    assert capture.counter("moe_routed_pairs_total", role=ROLE,
                           dispatch="ragged") == tokens * 3 * 4
    assert np.isfinite(stats["loss"]) and np.isfinite(stats["grad_norm"])


def test_generate_span_says_the_delta_states_bytes(built):
    from realhf_tpu.obs import tracing
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    model = built("share")
    engine = _engine(model["cfg"], model["params"])
    ids = model["docs"][:, :8]
    tracing.start()
    engine.generate(
        ids, np.ones_like(ids), np.tile(np.arange(8), (2, 1)),
        jax.random.PRNGKey(0),
        GenerationHyperparameters(max_new_tokens=2, greedy=True,
                                  force_no_logits_mask=True),
        eos_token_id=None, pad_token_id=0)
    capture = tracing.stop()
    [span] = capture.named("engine:generate")
    a = span["attributes"]
    # four delta layers, two streams: 4 heads x 16 x 16 float32 and
    # three rows of 3 x 64 in float32 (the test's compute dtype)
    assert a["delta_state_bytes"] == 4 * 2 * (4 * 16 * 16 * 4
                                              + 3 * 192 * 4)
    assert (a["kv_layers"], a["conv_state_bytes"]) == (1, 0)
    assert capture.counter("delta_tokens_total", role=ROLE) == \
        (2 * 8 + 2 * 2) * 4


def test_the_scan_is_a_sub_part_of_delta(built):
    """``obs/parts.py``: the chunked recurrence lowers under
    ``delta/scan`` (forward, rematerialised and backward), the
    projections, convolutions, gates, the output's norm and ``wo``
    under ``delta`` itself; a reader of the whole part
    (``train.delta_s``) holds both."""
    from realhf_tpu.obs import parts
    assert parts.DELTA in parts.PARTS
    assert parts.SUB_STEPS[parts.DELTA] == (parts.SCAN,)
    model = built("share")
    cfg = dataclasses.replace(model["cfg"], gradient_checkpointing=True)
    ids, seg, _ = _packed()

    def loss(p):
        with jax.named_scope(parts.FORWARD_BACKWARD):
            h, _ = T.forward(cfg, p, jnp.asarray(ids), jnp.asarray(seg))
        return (h ** 2).sum()

    text = jax.jit(jax.grad(loss)).lower(model["params"]).compile().as_text()
    table = parts.parse_program(text)
    seen = {(part, pass_) for part, pass_, *_ in table.values()}
    for pass_ in (parts.FWD, parts.BWD):
        assert ("delta", pass_) in seen
        assert ("delta/scan", pass_) in seen
    assert parts.classify(
        "jit(f)/forward_backward/layers/delta/scan/while/body/dot_general"
    )[:1] == ("delta/scan",)
    assert parts.classify(
        "jit(f)/transpose(jvp(forward_backward))/layers/delta/mul"
    )[:2] == ("delta", parts.BWD)


def test_hf_round_trip_is_bit_equal(built, tmp_path):
    """Checkpoint -> program -> checkpoint: every tensor back under its
    name with its bits, ``A_log`` in its [1, 1, heads, 1] and the taps
    in Conv1d's [channels, 1, taps]."""
    model = built("share", "published")
    cfg, params = registry.load_hf_checkpoint(model["ckpt"], NAME)
    path = str(tmp_path / "out")
    registry.save_hf_checkpoint(path, NAME, cfg, params)
    back = reference.load_tensors(path)
    assert set(back) == set(model["tensors"])
    for name, want in model["tensors"].items():
        assert back[name].shape == want.shape, name
        assert np.array_equal(back[name].view(np.uint16),
                              want.view(np.uint16)), name
    with open(os.path.join(path, "config.json")) as f:
        written = json.load(f)
    assert written["linear_attn_config"]["kda_layers"] == [1, 2, 3, 5]
    assert written["expert_share"] == {"of": 16, "first": 4}


@pytest.mark.parametrize("tp", [1, 2])
def test_streamed_load_and_save_round_trip(built, tmp_path, tp):
    model = built("share")
    par = mesh_lib.ParallelismConfig(tensor_parallel_size=tp)
    mesh = mesh_lib.make_mesh(par, jax.devices()[:tp])
    cfg, params = registry.load_hf_checkpoint_streamed(
        model["ckpt"], mesh, NAME, param_dtype="float32")
    ref = model["params"]
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    path = str(tmp_path / "streamed")
    registry.save_hf_checkpoint_streamed(path, NAME, cfg, params)
    back = reference.load_tensors(path)
    assert set(back) == set(model["tensors"])


def test_what_does_not_run_a_pattern_refuses_by_name(built):
    """The slot engine, the paged pool, pipeline stages and the
    allocation search know one kind of block and one kind of decode
    state: under delta layers they raise, naming the layers that keep
    a state a head."""
    from realhf_tpu.engine import inflight, kv_pool
    from realhf_tpu.models import sharding
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    model = built("share")
    cfg, params = model["cfg"], model["params"]
    g = GenerationHyperparameters(max_new_tokens=2, greedy=True,
                                  force_no_logits_mask=True)
    named = (r"layer pattern \(layer_pattern 'd d d l d': 0 conv and 1 "
             r"attention layers, 0 of those with a window, 1 latent, 4 "
             r"delta layers that keep a state a head")
    with pytest.raises(NotImplementedError, match="slot engine.*" + named):
        inflight.InflightBatchingGenerator(
            cfg, params, g, n_slots=2, max_prompt_len=8,
            eos_token_id=None, pad_token_id=0)
    with pytest.raises(NotImplementedError, match="KV pool.*" + named):
        kv_pool.KVPool(cfg, n_blocks=4, block_len=8)
    with pytest.raises(NotImplementedError, match="pipeline.*" + named):
        sharding.param_pspecs(cfg, pipeline_parallel=True)
    with pytest.raises(NotImplementedError, match="slot engine.*" + named):
        _engine(cfg, params).inflight_generator(g)


def test_the_config_says_what_a_delta_layer_may_be():
    """``TransformerConfig``: delta layers need their ``DeltaConfig``
    and a pattern; only latent layers may say that they have no rotary
    embedding."""
    from realhf_tpu.models.config import (
        DeltaConfig,
        RotaryConfig,
        TransformerConfig,
    )
    base = dict(
        n_layers=2, n_kv_heads=2, n_q_heads=2, hidden_dim=32, head_dim=16,
        intermediate_dim=64, vocab_size=64, layer_norm_type="rms",
        mlp_type="llama", apply_rotary=True, use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu")
    delta = DeltaConfig(n_heads=2, head_dim=16)
    cfg = TransformerConfig(
        **base, layer_pattern=(("delta", "dense"), ("attention", "dense")),
        delta=delta)
    assert (cfg.delta_layers, cfg.attention_layers) == ((0,), (1,))
    assert delta.width == 32 and delta.conv_kernel == 4
    with pytest.raises(ValueError, match="1 delta layers, delta is None"):
        TransformerConfig(**base, layer_pattern=(
            ("delta", "dense"), ("attention", "dense")))
    with pytest.raises(ValueError, match="0 delta layers"):
        TransformerConfig(**base, delta=delta, layer_pattern=(
            ("attention", "dense"),) * 2)
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        TransformerConfig(**base, delta=delta)
    with pytest.raises(ValueError, match=r"lacks \['attention'\]"):
        TransformerConfig(
            **base, delta=delta, rotary_by_operator={"attention": None},
            layer_pattern=(("delta", "dense"), ("attention", "dense")))
    assert RotaryConfig().describe() == "plain@10000/1"
