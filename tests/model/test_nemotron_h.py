"""Nemotron-H (NVIDIA-Nemotron-3-Nano-30B-A3B-BF16's family) held to its
plain reference (``benchmark/families/nemotron_h.py``, which takes the
state-space recurrence a TOKEN at a time) on the CPU: small widths that
keep every mechanism of the benchmark's cell (hidden 64; ``E M E M E M
*``: three Mamba-2 layers of 8 heads of 8 with a state of 16, 2 groups
and a 4-tap convolution with a bias; three layers of ungated relu2
experts, 16 of 24, 3 a token by sigmoid score + selection bias, gates
renormalised and times 2.5, one shared expert of 48; one attention layer
of 4 query and 2 key heads of 16 without a rotary; every layer ONE part
with one norm), seeded random weights under Hugging Face's names
(``benchmark/generate.py`` makes them, the program's own loader reads
them), everything in float32. Two checkpoints (every expert; one
expert-parallel rank's share, experts 4 to 7 of 16), each under two
initialisations (the harness's: ``A_log`` and ``dt_bias`` near 0, a
state halves every token; ``published_init``: a state lives tens to
thousands of tokens). Documents are 300 tokens (two chunks of 128 and a
part of a third), and a packed row of 384 holds documents of 140, 150
and 70 tokens with every boundary inside a chunk.

Every comparison is float32 against float32 on the same values, so the
tolerances are those of two orders of summation. The mixer and the
router are also held to ``transformers``' own modules
(``Zamba2MambaMixer.torch_forward``, ``DeepseekV3TopkRouter``), of which
the published ones are copies.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import generate, reference
from benchmark.families import nemotron_h as family
from realhf_tpu.api.config import ModelName
from realhf_tpu.engine.engine import Engine
from realhf_tpu.interfaces import sft
from realhf_tpu.models import hf as hf_models
from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import ABSENT, SsmConfig
from realhf_tpu.models.operators import OPERATORS, Ctx, n_params
from realhf_tpu.models.hf import registry
from realhf_tpu.parallel import mesh as mesh_lib

#: max |delta logit| allowed between the program and the reference
LOGIT_TOL = 2e-5
FULL_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

_BASE = dict(
    model_type="nemotron_h", vocab_size=128, hidden_size=64,
    intermediate_size=24, num_hidden_layers=7,
    hybrid_override_pattern="EMEMEM*", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, attention_bias=False,
    mlp_bias=False, use_bias=False, mamba_proj_bias=False,
    use_conv_bias=True, mamba_num_heads=8, mamba_head_dim=8,
    ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=128,
    mamba_hidden_act="silu", mlp_hidden_act="relu2", n_shared_experts=1,
    num_experts_per_tok=3, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, n_group=1, topk_group=1,
    norm_topk_prob=True, routed_scaling_factor=2.5,
    layer_norm_epsilon=1e-5, norm_eps=1e-5, max_position_embeddings=4096,
    rope_theta=10000, partial_rotary_factor=1, time_step_min=0.001,
    time_step_max=0.1, time_step_floor=0.0001, tie_word_embeddings=False,
    # (0.02 at a hidden size of 64 leaves the state's part of a logit
    # under float32's noise: what is about the state's life would not
    # show)
    initializer_range=0.1, eos_token_id=1)
CONFIGS = {
    "whole": dict(_BASE, n_routed_experts=16),
    "share": dict(_BASE, n_routed_experts=4,
                  expert_share={"of": 16, "first": 4}),
}
INITS = ("harness", "published")
DOC = 300
ROW, DOCS_IN_ROW = 384, (140, 150, 70)  # ends at 140, 290, 360
NAME = "nemotron_h"


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """(name, init) -> a checkpoint the benchmark's generator wrote
    (under ``published`` its decay tensors overwritten by
    ``family.published_init``), read by the program's loader (float32
    parameters and compute) and, file by file, by the reference; each
    made once a module."""
    import safetensors.numpy
    made = {}

    def get(name, init="harness"):
        if (name, init) not in made:
            hf = CONFIGS[name]
            ckpt = str(tmp_path_factory.mktemp(f"{name}-{init}"))
            generate.write_checkpoint(ckpt, family, hf, seed=11)
            if init == "published":
                path = os.path.join(ckpt, "model.safetensors")
                safetensors.numpy.save_file(family.published_init(
                    hf, reference.load_tensors(ckpt), seed=5), path)
            cfg, params = registry.load_hf_checkpoint(ckpt, NAME)
            cfg.param_dtype = cfg.compute_dtype = "float32"
            params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                                  params)
            docs = np.random.default_rng(3).integers(
                2, hf["vocab_size"], size=(2, DOC)).astype(np.int32)
            tensors = reference.load_tensors(ckpt)
            made[name, init] = dict(
                name=name, hf=hf, ckpt=ckpt, cfg=cfg, params=params,
                docs=docs, tensors=tensors,
                want=family.logits(hf, tensors, docs),
                engine=_engine(cfg, params))
        return made[name, init]
    return get


@pytest.fixture(params=[(n, d) for n in sorted(CONFIGS) for d in INITS],
                ids=lambda p: "-".join(p))
def model(request, built):
    return built(*request.param)


def _packed(rng_seed=4):
    """Documents of 140, 150 and 70 tokens and 24 pads a row of 384:
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
        ModelName(f"nemotron-d{dp}t{tp}", 0),
        mesh_lib.make_mesh(par, jax.devices()[:dp * tp]), par)
    return Engine(cfg, ctx, jax.tree.map(np.asarray, params), **kwargs)


def _engine_logits(engine, ids, seg):
    hidden = engine.forward_hidden(ids, seg)
    return np.asarray(T.lm_logits(engine.cfg, engine.params, hidden),
                      np.float32)


def test_whole_documents_equal_the_reference(model):
    """Rows of 300 tokens: the chunked scan over two whole chunks and a
    part of a third against the recurrence token by token, the attention
    layer without a rotary at two query heads a key head, the router
    over 16 with the held experts' two products."""
    docs = model["docs"]
    got = _engine_logits(model["engine"], docs, np.ones_like(docs))
    assert np.abs(got - model["want"]).max() < LOGIT_TOL
    assert model["want"].std() > 0.1  # the logits are not all alike


def test_packed_row_equals_its_documents_alone(model):
    """The state is reset, the convolution stops and no score crosses at
    a document's first token: each document of a packed row gets the
    logits the reference gives it ALONE, with every boundary inside a
    chunk (140, 290, 360), and the reference given the packed row says
    the same."""
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


@pytest.mark.parametrize("init", INITS)
@pytest.mark.parametrize("wrong", family.WRONG)
def test_a_wrong_equation_is_outside_the_tolerance(built, wrong, init):
    """Every near-miss of the list, under both initialisations, on the
    packed row where the entry is about documents and on whole documents
    where it is not: at least 50 tolerances away."""
    model = built("share", init)
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


@pytest.mark.parametrize("dp,tp", [(2, 2), (1, 2)])
def test_sharded_forward_matches_one_device(built, dp, tp):
    """The ssm layers' heads and groups, their convolution's channels
    and the decay's leaves under tensor parallelism, the held experts'
    ragged products under data parallelism: the same logits as on one
    device, and so the reference's."""
    model = built("share", "published")
    docs = model["docs"]
    got = _engine_logits(_engine(model["cfg"], model["params"], dp, tp),
                         docs, np.ones_like(docs))
    assert np.abs(got - model["want"]).max() < LOGIT_TOL


@pytest.mark.parametrize("n_pre", [140, 1])
def test_prefill_then_decode_matches_full_forward(model, n_pre):
    """``engine/generation.py``'s two steps, teacher-forced: the FOURTH
    kind of decode state (a float32 [hd, state] a head an ssm layer,
    and the last three rows of its convolution's input) beside the K/V
    of the one attention layer; prefill leaves the state after its last
    token, a decode step moves it on by the recurrence itself."""
    cfg, params, docs = model["cfg"], model["params"], model["docs"]
    total = 256
    ids = jnp.asarray(docs[:, :n_pre])
    hidden, cache = jax.jit(lambda p, i: T.prefill(
        cfg, p, i, jnp.ones_like(i), total_len=total))(params, ids)
    step = jax.jit(lambda p, c, t, pos: T.decode_step(
        cfg, p, c, t, pos, uniform_slot=True))
    assert cache["k"].shape == cache["v"].shape \
        == (1, len(docs), 2, total, 16)
    assert cache["ssm"].shape == (3, len(docs), 8, 8, 16)
    assert cache["ssm"].dtype == jnp.float32
    assert cache["ssm_conv"].shape == (3, len(docs), 3, 64 + 2 * 2 * 16)
    assert not {"conv", "delta", "index_k"} & set(cache)
    empty = T.init_kv_cache(cfg, len(docs), total)
    assert {k: (v.shape, v.dtype) for k, v in empty.items()} == \
        {k: (v.shape, v.dtype) for k, v in cache.items()}
    assert tuple((3, *st.shape(cfg, len(docs), total))
                 for st in OPERATORS["ssm"].state) == (
        cache["ssm_conv"].shape, cache["ssm"].shape)
    got = [np.asarray(T.lm_logits(cfg, params, hidden))]
    for t in range(n_pre, total):
        h, cache = step(params, cache, jnp.asarray(docs[:, t]),
                        jnp.full((len(docs),), t, jnp.int32))
        got.append(np.asarray(T.lm_logits(cfg, params, h))[:, None])
    got = np.concatenate(got, axis=1)
    assert np.abs(got - model["want"][:, :total]).max() < LOGIT_TOL


def test_left_padded_prompts_generate_as_unpadded_ones(built):
    """``generate``'s prompts are left-padded and of unequal lengths:
    the state stays 0 through the padding, the convolution's tail holds
    0 where the row had padding, and each stream generates what it
    would alone, through the program's own generate; the span carries
    the fourth state's bytes."""
    from realhf_tpu.obs import tracing
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
    tracing.start(sync=True)
    out = engine.generate(
        ids, seg, pos, jax.random.PRNGKey(0),
        GenerationHyperparameters(max_new_tokens=4, greedy=True,
                                  force_no_logits_mask=True),
        eos_token_id=None, pad_token_id=0).to_host()
    capture = tracing.stop()
    for r, n in enumerate(lens):
        seq = np.concatenate([docs[r, :n], out.tokens[r]])[None]
        want = family.logprobs(model["hf"], model["tensors"], seq)[0, -4:]
        assert np.abs(out.logprobs[r] - want).max() < LOGIT_TOL
    (span,) = capture.named("engine:generate")
    a = span["attributes"]
    assert a["ssm_state_bytes"] == 3 * 2 * (8 * 8 * 16 * 4 + 3 * 128 * 4)
    assert a["ssm_state_bytes"] == family.ssm_state_bytes(
        model["hf"], 2, bytes_per_el=4)
    assert (a["layer_pattern"], a["ssm_layers"], a["ssm_heads"],
            a["ssm_head_dim"], a["ssm_state"], a["ssm_groups"],
            a["expert_ff"], a["rotary"], a["kv_layers"]) == (
        "- m - m - m a", 3, 8, 8, 16, 2, "relu2/ungated", "a:none", 1)
    assert capture.counter("ssm_tokens_total",
                           role=str(engine.ctx.model_name.role)) \
        == 3 * (sum(lens) + 2 * 4)


def _sft_case(model, remat, prompt_len):
    """One SFT microbatch, the packed row: (program's loss, stats,
    gradient under HF's names), (reference's loss, parts, gradient).
    The reference takes documents of one length: two of 140 tokens, a
    row of 300 with padding after them (the second document's first
    token inside a chunk); its gradient is taken once a checkpoint."""
    params = model["params"]
    cfg = dataclasses.replace(model["cfg"], gradient_checkpointing=remat)
    n = 140
    docs = model["docs"][:, :n]
    ids = np.zeros((1, 300), np.int32)
    seg = np.zeros((1, 300), np.int32)
    prompt = np.zeros((1, 300), bool)
    for j in range(2):
        ids[0, j * n:(j + 1) * n], seg[0, j * n:(j + 1) * n] = docs[j], j + 1
        prompt[0, j * n:j * n + prompt_len] = True
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
@pytest.mark.parametrize("init", INITS)
def test_sft_loss_and_gradient_match_reference(built, remat, init):
    """Loss and the gradient of EVERY leaf (the taps and their bias,
    ``A_log``, ``D``, ``dt_bias``, the grouped norm's scale, the
    experts' two matrices among them) against ``jax.grad`` of the
    reference's token-by-token recurrence, two documents and padding a
    row, a boundary inside a chunk; rematerialised (the scan's output
    kept, its backward a segment at a time) as the experiments run it,
    and not. No gradient reaches the selection bias."""
    (loss, stats, grads), (ref_loss, parts, ref_grads) = _sft_case(
        built("share", init), remat, prompt_len=5)
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


def test_the_sixteen_shares_add_up_to_the_uncut_layer(built):
    """The share: what the SIXTEEN shares of an E layer give (expert e
    of 16 alone, each routing over all 16), with what every rank
    computes alike (the shared expert) counted ONCE, adds up to what the
    uncut reference gives for the layer; and the program's share is the
    reference's share."""
    whole = built("whole", "published")
    tensors, ids = whole["tensors"], jnp.asarray(whole["docs"][:, :40])
    hf = dict(whole["hf"], num_hidden_layers=1, hybrid_override_pattern="E")
    get = family._getter(tensors, None)

    def after_the_layer(first, count):
        with jax.default_matmul_precision("highest"):
            return np.asarray(family._blocks(
                dict(hf, n_routed_experts=count,
                     expert_share={"of": 16, "first": first}), get,
                ids)[0])

    uncut = after_the_layer(0, 16)
    alike = after_the_layer(0, 0)  # no expert held: the shared one
    routed = sum(after_the_layer(f, 1) - alike for f in range(16))
    assert np.abs(uncut - alike).max() > 1e-3
    assert np.abs(alike + routed - uncut).max() \
        < 2e-5 * np.abs(uncut).max()
    # summed as they are, the sixteen shares count the shared expert
    # (and the residual) sixteen times
    naive = sum(after_the_layer(f, 1) for f in range(16))
    assert np.abs(naive - uncut - 15 * alike).max() \
        < 2e-5 * np.abs(uncut).max()
    share = built("share", "published")
    got = _engine_logits(share["engine"], share["docs"],
                         np.ones_like(share["docs"]))
    assert np.abs(got - share["want"]).max() < LOGIT_TOL


# ----------------------------------------------------------------------
# Against transformers' own modules, of which the published are copies
# ----------------------------------------------------------------------
def test_mixer_is_zamba2s(built):
    """``Zamba2MambaMixer.torch_forward`` (the grouped gated norm,
    ``n_groups``, a convolution with a bias; its ``clamp`` at
    ``time_step_min`` 0 is none) in float32 on one document against the
    reference's ``_mamba`` and the program's ``_ssm_op`` with the SAME
    tensors. ONE chunk of the module's own (``chunk_size`` 128 over 90
    tokens): with several, ``torch_forward``'s inter-chunk term disagrees
    with ITSELF at one chunk by 1e-3 of the output once a state outlives
    a chunk (``published_init``; chunk sizes 32 and 64 read 0.0035 where
    128 reads 0.0000011 against the recurrence token by token)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "Zamba2Config"):
        pytest.skip("this transformers has no zamba2")
    from transformers.models.zamba2.modeling_zamba2 import Zamba2MambaMixer
    model = built("share", "published")
    hf, tensors, cfg = model["hf"], model["tensors"], model["cfg"]
    conf = transformers.Zamba2Config(
        hidden_size=64, mamba_expand=1, n_mamba_heads=8, mamba_headdim=8,
        mamba_d_state=16, mamba_ngroups=2, mamba_d_conv=4, chunk_size=128,
        use_conv_bias=True, add_bias_linear=False, time_step_min=0.0,
        num_hidden_layers=1, vocab_size=128, num_attention_heads=4,
        use_mem_eff_path=False)
    mixer = Zamba2MambaMixer(conf, layer_idx=0).float().eval()
    pre = "backbone.layers.1.mixer."
    names = {"in_proj.weight": "in_proj.weight",
             "conv1d.weight": "conv1d.weight", "conv1d.bias": "conv1d.bias",
             "A_log": "A_log", "D": "D", "dt_bias": "dt_bias",
             "norm.weight": "norm.weight",
             "out_proj.weight": "out_proj.weight"}
    w = {k: np.asarray(tensors[pre + k], np.float32) for k in names}
    state = mixer.state_dict()
    for k, v in w.items():
        assert tuple(state[names[k]].shape) == v.shape, k
        state[names[k]] = torch.from_numpy(v.copy())
    mixer.load_state_dict(state)
    u = np.random.default_rng(0).standard_normal((2, 90, 64)).astype(
        np.float32)
    with torch.no_grad():
        want = mixer.torch_forward(torch.from_numpy(u)).numpy()
    seg = np.ones((2, 90), np.int32)
    d = family.dims(hf)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(family._mamba(
            d, jnp.asarray(u), {k: jnp.asarray(v) for k, v in w.items()},
            jnp.asarray(family.positions(seg)), jnp.asarray(seg)))
        got, _ = OPERATORS["ssm"].apply(
            cfg, model["params"]["layers"]["1"], jnp.asarray(u),
            Ctx({}, seg_ids=jnp.asarray(seg)))
    scale = np.abs(want).max()
    assert scale > 1e-3
    assert np.abs(ref - want).max() < 2e-5 * scale
    assert np.abs(np.asarray(got) - want).max() < 2e-5 * scale


def test_router_is_deepseek_v3s(built):
    """``DeepseekV3TopkRouter`` in float32 against the reference's
    ``_route`` and the program's ``router_probs``: the same experts a
    token, the same gates."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "DeepseekV3Config"):
        pytest.skip("this transformers has no deepseek_v3")
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import (
        DeepseekV3TopkRouter,
    )

    from realhf_tpu.ops.moe import router_probs
    model = built("whole", "harness")
    hf, tensors, cfg = model["hf"], model["tensors"], model["cfg"]
    conf = transformers.DeepseekV3Config(
        hidden_size=64, n_routed_experts=16, num_experts_per_tok=3,
        n_group=1, topk_group=1, norm_topk_prob=True,
        routed_scaling_factor=2.5)
    router = DeepseekV3TopkRouter(conf)
    pre = "backbone.layers.0.mixer.gate."
    weight = np.asarray(tensors[pre + "weight"], np.float32)
    # a bias large enough to move choices
    bias = np.random.default_rng(1).standard_normal(16).astype(
        np.float32) * 0.05
    router.weight.data = torch.from_numpy(weight.copy())
    router.e_score_correction_bias.data = torch.from_numpy(bias.copy())
    v = np.random.default_rng(2).standard_normal((50, 64)).astype(
        np.float32) * 4
    with torch.no_grad():
        idx, gates = router(torch.from_numpy(v))
    want = np.zeros((50, 16), np.float32)
    np.put_along_axis(want, idx.numpy(), gates.numpy(), axis=-1)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(family._route(
            family.dims(hf), jnp.asarray(v)[None], jnp.asarray(weight),
            jnp.asarray(bias)))[0]
        probs, top = router_probs(
            cfg.moe, jnp.asarray(v) @ jnp.asarray(weight).T,
            expert_bias=jnp.asarray(bias))
    got = np.zeros((50, 16), np.float32)
    np.put_along_axis(got, np.asarray(top), np.asarray(probs), axis=-1)
    assert (want > 0).sum() == 150
    assert np.abs(ref - want).max() < 1e-5
    assert np.abs(got - want).max() < 1e-5


# ----------------------------------------------------------------------
# Plumbing: the pattern, the counts, the round trip, the refusals
# ----------------------------------------------------------------------
def test_the_whole_pattern_parses_at_tiny_widths():
    """All 52 letters: 23 ``M``, 23 ``E``, 6 ``*``, each layer ONE part
    with one norm; the tiny model runs."""
    hf = dict(CONFIGS["whole"], num_hidden_layers=52,
              hybrid_override_pattern=FULL_PATTERN)
    cfg = hf_models.config_from_hf(NAME, hf)
    assert (len(cfg.layers_of("ssm")), cfg.n_moe_layers,
            len(cfg.layers_of("attention"))) == (23, 23, 6)
    assert cfg.pattern_string.split() == [
        {"M": "m", "E": "-", "*": "a"}[c] for c in FULL_PATTERN]
    assert all((op == ABSENT) != (ff == ABSENT)
               for op, ff in cfg.layer_pattern)
    assert cfg.ssm == SsmConfig(8, 8, 16, 2, 4)
    assert (cfg.ssm.width, cfg.ssm.conv_dim, cfg.ssm.in_dim) == (
        64, 128, 200)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    for i, letter in enumerate(FULL_PATTERN):
        assert sorted(params["layers"][str(i)]) == {
            "M": ["ln1", "ssm"], "E": ["ln2", "mlp"],
            "*": ["attn", "ln1"]}[letter]
    assert sorted(params["layers"]["1"]["mlp"]) == [
        "expert_bias", "router", "shared", "wd", "wu"]
    n_leaves = sum(a.size for a in jax.tree.leaves(params))
    # n_params leaves the 52 + 1 norms' scales out
    assert n_params(cfg) == n_leaves - 53 * 64 == family.n_params(hf) \
        - 53 * 64
    cfg.compute_dtype = "float32"
    ids = jnp.ones((1, 8), jnp.int32)
    hidden, _ = T.forward(cfg, params, ids, jnp.ones_like(ids))
    assert np.isfinite(np.asarray(hidden)).all()


def test_the_uncut_model_is_the_published_size():
    """The published config through the program's and the reference's
    counts: 31.6 B parameters to within 1%; the cell's cut 528,093,120
    less the program's uncounted norm scales."""
    from benchmark import run
    path = os.path.join(run.ROOT, "benchmark", "configs",
                        "nemotron-3-nano-30b-a3b-l7-ep16.json")
    hf, meta = generate.load_config(path)
    with open(path) as f:
        published = {k: v["published"]
                     for k, v in json.load(f)["reduced"].items()}
    whole = {k: v for k, v in dict(hf, **published).items()
             if k != "expert_share"}
    assert whole["hybrid_override_pattern"] == FULL_PATTERN
    assert abs(family.n_params(whole) / 31.6e9 - 1) < 0.01
    cfg = hf_models.config_from_hf(NAME, whole)
    assert abs(n_params(cfg) / 31.6e9 - 1) < 0.01
    assert (len(cfg.layers_of("ssm")), cfg.n_moe_layers,
            len(cfg.layers_of("attention"))) == (23, 23, 6)
    cut = hf_models.config_from_hf(NAME, hf)
    assert cut.pattern_string == "- m - m - m a"
    assert n_params(cut) == 528_093_120 - 8 * 2688
    assert (cut.ssm.width, cut.ssm.conv_dim, cut.ssm.in_dim) == (
        4096, 6144, 10304)
    assert (cut.moe.num_experts, cut.moe.n_held, cut.moe.top_k,
            cut.moe.intermediate_dim, cut.moe.shared_intermediate_dim,
            cut.moe.routed_scaling_factor, cut.moe.norm_topk_eps) == (
        128, 8, 6, 1856, 3712, 2.5, 1e-20)
    assert (cut.n_q_heads, cut.n_kv_heads, cut.head_dim,
            cut.rotary_of("attention"), cut.gated_mlp,
            cut.activation_function) == (32, 2, 128, None, False, "relu2")


def test_hf_round_trip(built, tmp_path):
    """The program's tree -> HF's names -> the program's tree, and the
    config both ways, bit for bit; a saved checkpoint loads."""
    model = built("share", "published")
    cfg, params = model["cfg"], jax.tree.map(np.asarray, model["params"])
    state = hf_models.params_to_hf(NAME, params, cfg)
    assert set(state) == set(model["tensors"])
    back = hf_models.params_from_hf(NAME, state, cfg)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(a, b)
    again = hf_models.config_from_hf(
        NAME, hf_models.config_to_hf(NAME, cfg))
    assert dataclasses.replace(
        again, param_dtype="float32", compute_dtype="float32",
        n_positions=cfg.n_positions) == cfg
    out = str(tmp_path / "saved")
    registry.save_hf_checkpoint(out, NAME, cfg, params)
    cfg2, params2 = registry.load_hf_checkpoint(out, NAME)
    assert cfg2.layer_pattern == cfg.layer_pattern
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
        np.testing.assert_array_equal(a, np.asarray(b, a.dtype))


@pytest.mark.parametrize("key,value", [
    ("hybrid_override_pattern", "EMEMEM-"), ("use_conv_bias", False),
    ("mamba_proj_bias", True), ("attention_bias", True),
    ("mlp_hidden_act", "silu"), ("n_group", 2),
    ("time_step_limit", [0.0, 1.0]), ("n_shared_experts", 2),
    ("norm_eps", 1e-6)])
def test_what_the_family_does_not_run_is_refused_by_name(key, value):
    hf = dict(CONFIGS["whole"], **{key: value})
    with pytest.raises(NotImplementedError, match="nemotron_h|reference"):
        hf_models.config_from_hf(NAME, hf)
    with pytest.raises(NotImplementedError):
        family.dims(hf)


@pytest.mark.parametrize("what", ["slot_engine", "kv_pool", "pipeline",
                                  "context", "search"])
def test_what_does_not_run_an_ssm_layer_is_refused_by_name(built, what):
    """The slot engine, the paged pool, pipeline stages, context
    parallelism and the allocation search say so, with the pattern and
    its ssm layers in the message."""
    model = built("share", "harness")
    cfg, params = model["cfg"], model["params"]
    with pytest.raises(NotImplementedError) as e:
        if what == "slot_engine":
            from realhf_tpu.ops.sampling import GenerationHyperparameters
            model["engine"].inflight_generator(
                GenerationHyperparameters(max_new_tokens=4))
        elif what == "kv_pool":
            cfg.require_one_block("the paged KV pool (engine/kv_pool.py)")
        elif what == "search":
            cfg.require_one_block("the allocation search")
        elif what == "pipeline":
            par = mesh_lib.ParallelismConfig(pipeline_parallel_size=2)
            ctx = mesh_lib.MeshContext(
                ModelName("nemotron-pp2", 0),
                mesh_lib.make_mesh(par, jax.devices()[:2]), par)
            Engine(cfg, ctx, jax.tree.map(np.asarray, params))
        else:
            par = mesh_lib.ParallelismConfig(context_parallel_size=2)
            ctx = mesh_lib.MeshContext(
                ModelName("nemotron-cp2", 0),
                mesh_lib.make_mesh(par, jax.devices()[:2]), par)
            Engine(cfg, ctx, jax.tree.map(np.asarray, params))
    assert "- m - m - m a" in str(e.value)
    assert "ssm layers" in str(e.value)
