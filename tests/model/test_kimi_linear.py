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
tolerances are those of two orders of summation. The family's plumbing
(configuration, spans, checkpoints, refusals) is
``test_kimi_linear_plumbing.py``, on this file's tiny model.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import generate, reference
from benchmark.families import kimi_linear as family
from realhf_tpu.api.config import ModelName
from realhf_tpu.engine.engine import Engine
from realhf_tpu.interfaces import sft
from realhf_tpu.models import hf as hf_models
from realhf_tpu.models import transformer as T
from realhf_tpu.models.hf import registry
from realhf_tpu.models.operators import OPERATORS
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
                name=name, hf=hf, ckpt=ckpt, cfg=cfg, params=params, docs=docs,
                tensors=tensors, want=family.logits(hf, tensors, docs),
                engine=_engine(cfg, params))
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


@pytest.fixture(scope="module")
def prefill_and_step():
    """``T.prefill`` and ``T.decode_step`` jitted once a checkpoint's
    shapes: the decay's draw changes values and no program."""
    jitted = {}

    def get(model, total):
        key = model["name"], total
        if key not in jitted:
            cfg = model["cfg"]
            jitted[key] = (
                jax.jit(lambda p, i: T.prefill(
                    cfg, p, i, jnp.ones_like(i), total_len=total)),
                jax.jit(lambda p, c, t, pos: T.decode_step(
                    cfg, p, c, t, pos, uniform_slot=True)))
        return jitted[key]
    return get


def _engine_logits(engine, ids, seg):
    hidden = engine.forward_hidden(ids, seg)
    return np.asarray(T.lm_logits(engine.cfg, engine.params, hidden),
                      np.float32)


def test_whole_documents_equal_the_reference(model):
    """Rows of 150 tokens: the chunked scan over two whole chunks and a
    part of a third against the recurrence token by token, the NoPE
    latent layer, the router over 16 with the held experts."""
    docs = model["docs"]
    got = _engine_logits(model["engine"], docs, np.ones_like(docs))
    assert np.abs(got - model["want"]).max() < LOGIT_TOL
    assert model["want"].std() > 0.1  # the logits are not all alike


def test_packed_row_equals_its_documents_alone(model):
    """The state is reset, the convolutions stop and no score crosses
    at a document's first token: each document of a packed row gets the
    logits the reference gives it ALONE, with boundaries inside a chunk
    (70) and inside a 16-token sub-block (171), and the reference given
    the packed row says the same."""
    ids, seg, docs = _packed()
    got = _engine_logits(model["engine"], ids, seg)
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
def test_prefill_then_decode_matches_full_forward(model, n_pre,
                                                  prefill_and_step):
    """``engine/generation.py``'s two steps, teacher-forced: the THIRD
    kind of decode state (a float32 [hd, hd] a head a delta layer, and
    the last three rows of its convolutions' inputs) beside the K/V of
    the one latent layer; prefill leaves the state after its last
    token, a decode step moves it on by the recurrence itself."""
    cfg, params, docs = model["cfg"], model["params"], model["docs"]
    total = 96
    ids = jnp.asarray(docs[:, :n_pre])
    prefill, step = prefill_and_step(model, total)
    hidden, cache = prefill(params, ids)
    assert cache["k"].shape == (1, len(docs), 4, total, 24)
    assert cache["v"].shape == (1, len(docs), 4, total, 12)
    assert cache["delta"].shape == (4, len(docs), 4, 16, 16)
    assert cache["delta"].dtype == jnp.float32
    assert cache["delta_conv"].shape == (4, len(docs), 3, 3 * 64)
    assert "conv" not in cache
    empty = T.init_kv_cache(cfg, len(docs), total)
    assert {k: (v.shape, v.dtype) for k, v in empty.items()} == \
        {k: (v.shape, v.dtype) for k, v in cache.items()}
    assert tuple((4, *st.shape(cfg, len(docs), total))
                 for st in OPERATORS["delta"].state) == (
        cache["delta_conv"].shape, cache["delta"].shape)
    got = [np.asarray(T.lm_logits(cfg, params, hidden))]
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
    docs, engine = model["docs"], model["engine"]
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


def _sft_case(model, remat, prompt_len):
    """One SFT microbatch, the packed row: (program's loss, stats,
    gradient under HF's names), (reference's loss, parts, gradient).
    The reference takes documents of one length: two of 70 tokens, a
    row of 160 with padding after them; its gradient is taken once a
    checkpoint."""
    params = model["params"]
    cfg = dataclasses.replace(model["cfg"], gradient_checkpointing=remat)
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
    if ("sft", prompt_len) not in model:
        model["sft", prompt_len] = family.sft_loss_and_grad(
            model["hf"], model["tensors"], docs, prompt_len)
    want = model["sft", prompt_len]
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
    (loss, stats, grads), (ref_loss, parts, ref_grads) = _sft_case(
        built("share", decay), remat, prompt_len=5)
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
    got = _engine_logits(share["engine"], share["docs"],
                         np.ones_like(share["docs"]))
    assert np.abs(got - share["want"]).max() < LOGIT_TOL
