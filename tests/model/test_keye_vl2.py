"""The ``keye_vl2`` family (Keye-VL-2.0-30B-A3B's language model) on the
CPU at toy widths, in float32: the program (``models/transformer.py``'s
operator "sparse", ``ops/sparse_index.py``, the selection as one more
operand of ``ops/attention.py`` and of the flash kernels) against the
benchmark's plain reference (``benchmark/families/keye_vl2.py``) on a
checkpoint the benchmark's generator wrote, with ``topk`` SMALLER than
the rows so that the selection is live everywhere: logits, loss and
every leaf's gradient, packed rows, prefill then decode through the
three caches, generation, tensor and data parallel CPU meshes, the HF
round trips, the spans, the refusals. With ``topk`` >= the row both are
held to ``transformers``' own ``Qwen3MoeForCausalLM``.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import generate, reference
from benchmark.families import keye_vl2 as family
from realhf_tpu.api.config import ModelName
from realhf_tpu.engine.engine import Engine
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.interfaces import sft
from realhf_tpu.models import hf as hf_models
from realhf_tpu.models import operators
from realhf_tpu.models.config import ATTENTION_OPERATORS
from realhf_tpu.models import transformer as T
from realhf_tpu.models.hf import registry
from realhf_tpu.ops import sparse_index
from realhf_tpu.parallel import mesh as mesh_lib

#: max |delta logit| allowed between the program and the reference
LOGIT_TOL = 1e-5

_BASE = dict(
    model_type="KeyeVL2", vocab_size=128, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=16, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    hidden_act="silu", attention_bias=False, decoder_sparse_step=1,
    mlp_only_layers=[], num_experts_per_tok=3, norm_topk_prob=True,
    rms_norm_eps=1e-6, rope_theta=10000000,
    rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default",
                  "type": "default"},
    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": 6},
    sliding_window=None, use_sliding_window=False,
    max_position_embeddings=4096, tie_word_embeddings=False,
    initializer_range=0.02, eos_token_id=1)
CONFIGS = {
    "whole": dict(_BASE, num_experts=16),
    "share": dict(_BASE, num_experts=4,
                  expert_share={"of": 16, "first": 4}),
}
DOC = 20  # tokens a document; three to a packed row of 64
TOPK = 6  # of at most 20 visible keys: every token past the sixth selects
NAME = "keye_vl2"
ROLE = "keye-d1t1"


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
        ModelName(f"keye-d{dp}t{tp}", 0),
        mesh_lib.make_mesh(par, jax.devices()[:dp * tp]), par)
    return Engine(cfg, ctx, jax.tree.map(np.asarray, params), **kwargs)


def _engine_logits(engine, ids, seg):
    hidden = engine.forward_hidden(ids, seg)
    return np.asarray(T.lm_logits(engine.cfg, engine.params, hidden),
                      np.float32)


def test_config_is_read_from_the_published_keys(model):
    cfg, hf = model["cfg"], model["hf"]
    assert cfg.layer_pattern == (("sparse", "moe"),) * 3
    assert cfg.pattern_string == "s s s"
    assert cfg.layers_of("sparse") == cfg.layers_of(*ATTENTION_OPERATORS) \
        == (0, 1, 2)
    assert (cfg.indexer.heads, cfg.indexer.head_dim,
            cfg.indexer.topk) == (4, 8, TOPK)
    assert (cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim, cfg.qk_norm,
            cfg.rotary_base) == (4, 2, 16, "head", 1e7)
    assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.score_fn,
            cfg.moe.norm_topk_prob, cfg.moe.shared_intermediate_dim) == (
        16, 3, "softmax", True, None)
    assert cfg.moe.experts_held == (
        (4, 4) if "expert_share" in hf else None)
    # every tensor the generator wrote is a leaf, the indexer's five
    # among them; the program's count leaves the norms out
    params = family.n_params(hf)
    assert params == sum(v.size for v in model["tensors"].values())
    assert params == sum(a.size for a in jax.tree.leaves(model["params"]))
    assert operators.n_params(cfg) \
        == family.n_matrix_params(hf) + 3 * (2 * 16 + 8)
    index = model["params"]["layers"]["1"]["index"]
    assert {k: v.shape for k, v in index.items()} == dict(
        wq=(64, 32), wk=(64, 8), k_norm=(8,), k_norm_bias=(8,),
        w_weights=(64, 4))
    back = hf_models.config_to_hf(NAME, cfg)
    for key in ("model_type", "sa_config", "rope_theta", "num_experts",
                "num_experts_per_tok", "norm_topk_prob", "head_dim",
                "num_key_value_heads", "mlp_only_layers"):
        assert back[key] == hf[key], key
    assert back.get("expert_share") == hf.get("expert_share")


@pytest.mark.parametrize("key,value,named", [
    ("attention_bias", True, "attention_bias"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("sa_config", dict(_BASE["sa_config"], indexer_num_kv_heads=2),
     "indexer_num_kv_heads=2"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_scaling")])
def test_what_the_family_cannot_run_is_refused_by_name(key, value, named):
    with pytest.raises(NotImplementedError, match=named):
        hf_models.config_from_hf(NAME, dict(CONFIGS["whole"], **{key: value}))


def test_program_and_reference_agree_with_the_selection_live(model):
    """Every token past the sixth of a document selects 6 of its
    visible keys: the program's logits are the reference's, and the
    reference's change when the selection is left out."""
    seg = np.ones_like(model["docs"])
    got = _engine_logits(_engine(model["cfg"], model["params"]),
                         model["docs"], seg)
    assert np.abs(got - model["want"]).max() < LOGIT_TOL
    plain = family.logits(model["hf"], model["tensors"], model["docs"],
                          wrong=("no_selection",))
    assert np.abs(plain - model["want"]).max() > 100 * LOGIT_TOL
    assert model["want"].std() > 0.05  # the logits are not all alike


def test_the_published_modelling_code_gives_the_same_logits(built):
    """``transformers``' own ``Qwen3MoeForCausalLM`` (eager attention,
    float32) on the generator's checkpoint with the indexer's tensors
    ignored: with ``topk`` >= the row the selection is every visible
    key, and the reference and the program both give ITS logits, so
    attention (the norm a head on q and k, the rotate-half rotary at
    base 1e7), the softmax router with renormalised gates and the
    experts are the published module's and not a reading of them."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "Qwen3MoeForCausalLM"):
        pytest.skip("this transformers has no qwen3_moe")
    model = built("whole")
    hf = dict(model["hf"], sa_config=dict(model["hf"]["sa_config"],
                                          topk=DOC))
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "hidden_act", "attention_bias", "decoder_sparse_step",
            "mlp_only_layers", "num_experts", "num_experts_per_tok",
            "norm_topk_prob", "rms_norm_eps", "rope_theta",
            "max_position_embeddings", "tie_word_embeddings")
    conf = transformers.Qwen3MoeConfig(**{k: hf[k] for k in keys})
    conf._attn_implementation = "eager"
    net = transformers.Qwen3MoeForCausalLM(conf).float().eval()
    state = {k: torch.tensor(np.asarray(v, np.float32))
             for k, v in model["tensors"].items() if ".indexer." not in k}
    loaded = net.load_state_dict(state, strict=False)
    assert not loaded.unexpected_keys and not loaded.missing_keys
    with torch.no_grad():
        theirs = net(torch.tensor(model["docs"], dtype=torch.long)
                     ).logits.numpy()
    want = family.logits(hf, model["tensors"], model["docs"])
    assert np.abs(want - theirs).max() < LOGIT_TOL
    cfg = dataclasses.replace(model["cfg"], indexer=dataclasses.replace(
        model["cfg"].indexer, topk=DOC))
    got = _engine_logits(_engine(cfg, model["params"]), model["docs"],
                         np.ones_like(model["docs"]))
    assert np.abs(got - theirs).max() < LOGIT_TOL


def test_packed_row_of_three_documents_equals_the_documents_alone(model):
    """Positions, and so both rotary embeddings, restart at each
    document of a packed row; no attention score and no INDEX score
    crosses a boundary, so no token selects a key of another document:
    each document gets the logits the reference gives it alone, and the
    reference given the packed row says the same."""
    ids, seg = _packed(model["docs"])
    got = _engine_logits(_engine(model["cfg"], model["params"]), ids, seg)
    got = got[0, :3 * DOC].reshape(3, DOC, -1)
    assert np.abs(got - model["want"]).max() < LOGIT_TOL
    packed = family.logits(model["hf"], model["tensors"], ids, seg)
    assert np.abs(packed[0, :3 * DOC].reshape(3, DOC, -1)
                  - model["want"]).max() < LOGIT_TOL
    # the selection itself: inside the document, under the diagonal,
    # six a token once there are six, and the program's is the
    # reference's in every layer
    cfg, params = model["cfg"], model["params"]
    _, states = T.forward(cfg, params, jnp.asarray(ids), jnp.asarray(seg),
                          return_kv=True)
    assert states["index_k"].shape == (3, 1, 64, 8)
    for layer in range(3):
        want = family.selection(model["hf"], model["tensors"], ids, layer,
                                seg)
        same = (seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] != 0)
        assert not (want & ~same).any() and not np.triu(want[0], 1).any()
        assert np.array_equal(
            want.sum(-1)[0, :3 * DOC],
            np.tile(np.minimum(np.arange(DOC) + 1, TOPK), 3))
        assert not want[0, 3 * DOC:].any()
    selected, causal = sparse_index.pair_counts(seg, TOPK)
    assert selected == want.sum() and causal == 3 * DOC * (DOC + 1) // 2


@pytest.mark.parametrize("wrong", family.WRONG)
def test_a_wrong_equation_changes_the_logits(built, wrong):
    """Every equation of ``WRONG`` moves the reference's own logits far
    past float32's noise where the selection is live, so the float32
    tests above hold the program to the RIGHT one of each pair (a
    hundred times the tolerance of those tests: under the harness's
    draw of the attention's input norm the branch is a small part of a
    token's row, ``benchmark/families/keye_vl2.py``)."""
    model = built("share")
    off = family.logits(model["hf"], model["tensors"], model["docs"],
                        wrong=(wrong,))
    assert np.abs(off - model["want"]).max() > 100 * LOGIT_TOL


@pytest.mark.parametrize("dp,tp", [(1, 2), (2, 1)])
def test_sharded_forward_matches_one_device(model, dp, tp):
    """The heads under tensor parallelism (the indexer on every shard:
    the heads of a token share one selection), the rows and the held
    experts' ragged products under data parallelism: the same logits as
    on one device, and so the reference's."""
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
    """``engine/generation.py``'s two steps, teacher-forced, with the
    selection live in both: prefill fills the THIRD attention cache
    (the indexer's keys, one row of 8 a token a layer) beside K and V;
    a decode step writes the token's index key, scores the cache's
    rows, picks 6 and attends over them alone."""
    cfg, params, docs = model["cfg"], model["params"], model["docs"]
    ids = jnp.asarray(docs[:, :n_pre])
    hidden, cache = jax.jit(
        lambda p, i: T.prefill(cfg, p, i, jnp.ones_like(i),
                               total_len=DOC))(params, ids)
    assert cache["k"].shape == cache["v"].shape == (3, len(docs), 2, DOC, 16)
    assert cache["index_k"].shape == (3, len(docs), DOC, 8)
    empty = T.init_kv_cache(cfg, len(docs), DOC)
    assert {k: v.shape for k, v in empty.items()} \
        == {k: v.shape for k, v in cache.items()}
    grown = T.extend_kv_cache(empty, 4)
    assert grown["index_k"].shape == (3, len(docs), DOC + 4, 8)
    assert grown["k"].shape[3] == DOC + 4
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
    own generate (prefill, the decode loop over the three caches,
    sampling); ``engine:generate`` says what the third cache holds."""
    from realhf_tpu.obs import tracing
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
    tracing.start()
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
    [span] = capture.named("engine:generate")
    a = span["attributes"]
    assert (a["kv_layers"], a["sparse_layers"], a["index_topk"]) == (
        3, 3, TOPK)
    assert a["index_cache_bytes"] == 3 * 3 * 16 * 8 * 4
    assert a["decode_kernel"] == "xla"
    assert capture.counter("index_tokens_total", role=ROLE) == 3 * sum(lens)
    facts = engine.program_facts("generate")
    seen = {(op[3], op[0]) for op in facts.ops.values()}
    for phase in ("prefill", "decode"):
        assert {(phase, "index/project"), (phase, "index/scores"),
                (phase, "index/select")} <= seen


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
    """Loss and the gradient of every leaf against ``jax.grad`` of the
    reference, three documents and four pads a row, the selection live;
    rematerialised (the selection a kept residual) as the experiments
    run it, and not. NO gradient reaches the indexer's five tensors, in
    the program (which says so with ``stop_gradient``) as in the
    reference (which does not): the selection is discrete."""
    model = dict(model, cfg=dataclasses.replace(
        model["cfg"], gradient_checkpointing=remat))
    (loss, stats, grads), (ref_loss, parts, ref_grads) = _sft_case(
        model, n_docs=3, prompt_len=5)
    assert abs(loss - ref_loss) < 1e-5
    assert abs(stats["nll"] - parts["nll"]) < 1e-5
    assert "moe_aux_loss" not in stats and parts["aux"] == 0.0
    assert set(grads) == set(ref_grads)
    for name in sorted(grads):
        scale = np.abs(ref_grads[name]).max()
        gap = np.abs(grads[name] - ref_grads[name]).max()
        if ".indexer." in name:
            assert scale == 0 and gap == 0, name
            continue
        # (3 of 16 experts a token, 60 tokens: an expert may get none)
        assert scale > 0 or ".experts." in name, name
        assert gap <= 2e-5 * scale + 1e-12, (name, gap, scale)
    assert sum(".indexer." in name for name in grads) == 3 * 5


def test_the_indexer_is_bit_equal_after_three_optimizer_steps(built):
    """Three optimizer steps through ``Engine.train_batch`` in bf16 on
    float32 master weights with the default weight decay: every other
    leaf moves; the indexer's five, which no gradient reaches and no
    decay touches (``engine/optim.py``), are the bits that were
    loaded. The span says what ran; the counters what was selected."""
    from realhf_tpu.obs import tracing
    model = built("share")
    cfg = dataclasses.replace(model["cfg"], gradient_checkpointing=True,
                              param_dtype="bfloat16",
                              compute_dtype="bfloat16")
    engine = _engine(cfg, model["params"], optimizer=OptimizerConfig(
        lr=1e-2, weight_decay=0.05, warmup_steps_proportion=0.0,
        lr_scheduler_type="constant"), total_train_steps=10)
    ids, seg = _packed(model["docs"])
    mb = dict(input_ids=ids, seg_ids=seg,
              prompt_mask=np.zeros((1, 64), bool))
    before = jax.tree.map(np.asarray, engine.params)
    tracing.start()
    for _ in range(3):
        stats = engine.train_batch([mb, mb], sft._make_loss_fn(cfg),
                                   loss_fn_key="sft")
    capture = tracing.stop()
    assert np.isfinite(stats["loss"])
    after = jax.tree.map(np.asarray, engine.params)
    for i in range(3):
        l0, l1 = (p["layers"][str(i)] for p in (before, after))
        for leaf, was in l0["index"].items():
            assert np.array_equal(was.view(np.uint16),
                                  l1["index"][leaf].view(np.uint16)), leaf
            assert np.abs(was.astype(np.float32)).max() > 0
        for leaf in ("wq", "wk", "wv", "wo", "q_norm", "k_norm"):
            assert not np.array_equal(l0["attn"][leaf], l1["attn"][leaf])
        assert not np.array_equal(l0["mlp"]["router"], l1["mlp"]["router"])
    spans = capture.named("engine:train")
    assert len(spans) == 3
    a = spans[0]["attributes"]
    assert (a["layer_pattern"], a["sparse_layers"], a["index_heads"],
            a["index_dim"], a["index_topk"], a["experts_held"],
            a["experts"], a["top_k"], a["router"], a["moe_dispatch"],
            a["conv_layers"], a["dense_layers"]) == (
        "s s s", 3, 4, 8, TOPK, 4, 16, 3, "softmax", "ragged", 0, 0)
    # the XLA path here: no kernel takes the selection (on the chip
    # every ``engine:train`` span carries the count: three a layer)
    assert engine.program_facts("train").attributes[
        "flash_mask_calls"] == 0
    assert "window" not in a and "latent_layers" not in a
    selected, causal = sparse_index.pair_counts(seg, TOPK)
    assert capture.counter("sparse_pairs_total", role=ROLE,
                           kind="selected") == 3 * 2 * 3 * selected
    assert capture.counter("sparse_pairs_total", role=ROLE,
                           kind="causal") == 3 * 2 * 3 * causal
    assert capture.counter("index_tokens_total", role=ROLE) \
        == 3 * 2 * 3 * 3 * DOC
    facts = engine.program_facts("train")
    index = {(op[0], op[1]) for op in facts.ops.values()
             if (op[0] or "").startswith("index")}
    assert {part for part, _ in index} - {"index"} == {
        "index/project", "index/scores", "index/select"}
    # forward only: the kept selection is not made a second time
    assert {pass_ for _, pass_ in index} == {"fwd"}


def test_the_eight_shares_add_up_to_the_uncut_layer(built):
    """The guide's tie of the share to the model, on the PROGRAM's
    side: the residual stream after one layer under each of four shares
    of 4 experts, less what every share computes alike (attention over
    the selection), adds up to the layer with all 16 held."""
    model = built("whole")
    hf = dict(model["hf"], num_hidden_layers=1)
    ids, seg = _packed(model["docs"])

    def after(first, count):
        share = dict(hf, num_experts=count,
                     expert_share={"of": 16, "first": first})
        cfg = hf_models.config_from_hf(NAME, share)
        cfg.param_dtype = cfg.compute_dtype = "float32"
        lp = model["params"]["layers"]["0"]
        mlp = dict(lp["mlp"], **{w: lp["mlp"][w][first:first + count]
                                 for w in ("wg", "wu", "wd")})
        params = dict(model["params"], layers={"0": dict(lp, mlp=mlp)})
        hidden, _ = T.forward(cfg, params, jnp.asarray(ids),
                              jnp.asarray(seg))
        return np.asarray(hidden)

    whole = after(0, 16)
    parts = [after(f, 4) for f in range(0, 16, 4)]
    # the final norm is not linear: compare before it by adding the
    # routed parts in the reference, which returns the stream itself
    get = family._getter(model["tensors"], None)

    def stream(first, count):
        with jax.default_matmul_precision("highest"):
            return np.asarray(family._blocks(
                dict(hf, num_experts=count,
                     expert_share={"of": 16, "first": first}), get,
                jnp.asarray(ids), seg)[0])

    alike = stream(0, 0)
    routed = sum(stream(f, 4) - alike for f in range(0, 16, 4))
    assert np.abs(stream(0, 16) - alike).max() > 1e-4
    assert np.abs(alike + routed - stream(0, 16)).max() \
        < 2e-5 * np.abs(stream(0, 16)).max()
    # and the program under each share is the reference under it
    for f, got in zip(range(0, 16, 4), parts):
        want = family._rms(stream(f, 4), get("model.norm.weight"), 1e-6)
        assert np.abs(got - np.asarray(want))[seg != 0].max() < LOGIT_TOL
    assert np.abs(whole - np.asarray(family._rms(
        stream(0, 16), get("model.norm.weight"), 1e-6)))[seg != 0].max() \
        < LOGIT_TOL


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
    assert saved["model_type"] == "KeyeVL2"
    assert saved.get("expert_share") == model["hf"].get("expert_share")
    assert saved["sa_config"] == model["hf"]["sa_config"]
    assert registry.detect_family(path) == NAME
    ccfg, critic = registry.load_hf_checkpoint(path, NAME, is_critic=True)
    assert ccfg.is_critic and critic["head"]["w"].shape == (64, 1)
    np.testing.assert_array_equal(
        critic["layers"]["2"]["index"]["wq"],
        np.asarray(model["params"]["layers"]["2"]["index"]["wq"]))


@pytest.mark.parametrize("tp", [1, 2])
def test_streamed_load_and_save_round_trip(model, tmp_path, tp):
    """A layer at a time onto a mesh, and back into one file a layer,
    bit for bit what the generator wrote; the indexer's leaves on every
    shard."""
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
    layer = params["layers"]["1"]
    assert layer["attn"]["wq"].sharding.spec == spec(None, "model")
    assert layer["attn"]["wo"].sharding.spec == spec("model", None)
    assert layer["attn"]["q_norm"].sharding.spec == spec(None)
    for leaf, want in (("wq", spec(None, None)), ("wk", spec(None, None)),
                       ("k_norm", spec(None)), ("k_norm_bias", spec(None)),
                       ("w_weights", spec(None, None))):
        assert layer["index"][leaf].sharding.spec == want, leaf
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
    """The slot engine, the paged pool, pipeline stages and context
    parallelism know no selection of keys: under sparse layers they
    raise, naming them."""
    from realhf_tpu.engine import inflight, kv_pool
    from realhf_tpu.models import sharding
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    model = built("share")
    cfg, params = model["cfg"], model["params"]
    g = GenerationHyperparameters(max_new_tokens=2, greedy=True,
                                  force_no_logits_mask=True)
    named = (r"layer pattern \(layer_pattern 's s s': 3 sparse layers, "
             r"3 layers with experts")
    with pytest.raises(NotImplementedError, match="slot engine.*" + named):
        inflight.InflightBatchingGenerator(
            cfg, params, g, n_slots=2, max_prompt_len=8,
            eos_token_id=None, pad_token_id=0)
    with pytest.raises(NotImplementedError, match="KV pool.*" + named):
        kv_pool.KVPool(cfg, n_blocks=4, block_len=8)
    with pytest.raises(NotImplementedError, match="pipeline.*" + named):
        sharding.param_pspecs(cfg, pipeline_parallel=True)
    par = mesh_lib.ParallelismConfig(context_parallel_size=2)
    ctx = mesh_lib.MeshContext(
        ModelName("keye-ctx", 0),
        mesh_lib.make_mesh(par, jax.devices()[:2]), par)
    with pytest.raises(NotImplementedError,
                       match="context parallelism.*'s s s'.*selection"):
        Engine(cfg, ctx, jax.tree.map(np.asarray, params))


def test_the_config_says_what_a_sparse_layer_may_be():
    """``TransformerConfig``: sparse layers need their
    ``IndexerConfig`` and the model-wide rotary embedding; an indexer
    needs sparse layers and a layer pattern."""
    from realhf_tpu.models.config import (
        IndexerConfig,
        RotaryConfig,
        TransformerConfig,
    )
    base = dict(n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=64,
                head_dim=16, intermediate_dim=96, vocab_size=128,
                layer_norm_type="rms", mlp_type="llama", apply_rotary=True,
                use_attention_bias=False, use_attn_proj_bias=False)
    ix = IndexerConfig(heads=4, head_dim=8, topk=6)
    two = (("sparse", "dense"),) * 2
    cfg = TransformerConfig(**base, layer_pattern=two, indexer=ix)
    assert cfg.layers_of("sparse") == cfg.layers_of(*ATTENTION_OPERATORS) \
        == (0, 1)
    assert cfg.pattern_string == "s s" and cfg.layers_of("window") == ()
    with pytest.raises(ValueError, match="indexer is None"):
        TransformerConfig(**base, layer_pattern=two)
    with pytest.raises(ValueError, match="0 sparse layers"):
        TransformerConfig(**base, indexer=ix,
                          layer_pattern=(("attention", "dense"),) * 2)
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        TransformerConfig(**base, indexer=ix)
    with pytest.raises(NotImplementedError, match="model-wide rotary"):
        TransformerConfig(
            **base, layer_pattern=two, indexer=ix,
            rotary_by_operator={"sparse": RotaryConfig()})
    with pytest.raises(NotImplementedError, match="even head_dim"):
        TransformerConfig(**base, layer_pattern=two,
                          indexer=IndexerConfig(heads=4, head_dim=7, topk=6))
    # beside full attention in one stack: K and V keep one shape
    mixed = TransformerConfig(
        **base, indexer=ix,
        layer_pattern=(("attention", "dense"), ("sparse", "dense")))
    assert mixed.layers_of("sparse") == (1,) and mixed.pattern_string == "a s"


def test_select_topk_is_exact_and_breaks_ties_low():
    """The bisection over the scores' bits against a stable sort, on
    rows with ties (many equal scores, zeros of both signs), rows with
    fewer visible entries than ``topk`` and rows with none."""
    rng = np.random.default_rng(0)
    scores = rng.normal(size=(64, 96)).astype(np.float32)
    scores[:16] = np.round(scores[:16] * 2) / 2      # many ties
    scores[16:24, ::3] = 0.0
    scores[16:24, 1::3] = -0.0
    scores[24:32] *= 1e-30                           # tiny, both signs
    visible = rng.random((64, 96)) < 0.7
    visible[40:44] = False
    visible[44:48, 5:] = False                       # fewer than topk
    for topk in (1, 7, 96, 200):
        got = np.asarray(sparse_index.select_topk(
            jnp.asarray(scores), jnp.asarray(visible), topk))
        order = np.argsort(-np.where(visible, scores, -np.inf), axis=-1,
                           kind="stable")[:, :topk]
        want = np.zeros_like(visible)
        np.put_along_axis(want, order, True, axis=-1)
        want &= visible
        assert np.array_equal(got, want), topk
        assert (got.sum(-1) == np.minimum(visible.sum(-1), topk)).all()


def _rows(*rows, length=64):
    """Packed rows of ``length``: each a tuple of document lengths, a
    negative one a run of padding; what is left is padding."""
    seg = np.zeros((len(rows), length), np.int32)
    for r, docs in enumerate(rows):
        at = 0
        for j, n in enumerate(docs):
            seg[r, at:at + abs(n)] = (j + 1) * (n > 0)
            at += abs(n)
        assert at <= length
    return seg


#: name -> (packed rows, the blocks of 16 queries that have to score at
#: a ``topk`` of 24), each on an edge of :func:`scoring_blocks`' rule
_EDGE_ROWS = {
    "a_document_of_exactly_topk": (_rows((24, 24, 16)), []),
    "a_document_of_topk_plus_one": (_rows((25, 24, 15)), [1]),
    "a_blocks_last_row_alone_exceeds": (_rows((-7, 25, 24)), [1]),
    "three_documents_one_over_topk": (_rows((10, 30, 20)), [2]),
    "one_document_a_row": (_rows((64,)), [1, 2, 3]),
    "a_row_of_padding": (_rows(()), []),
    "padding_beside_a_long_row": (_rows((), (64,)), [1, 2, 3]),
    "two_rows_a_block_scores_for_one": (
        _rows((30, 34), (20, 20, 24)), [1, 3]),
    "two_rows_scoring_different_blocks": (
        _rows((26, 24), (24, 40)), [1, 3]),
    "length_not_a_multiple_of_the_block": (_rows((40,), length=40), [0]),
    "length_not_a_multiple_all_short": (_rows((20, 20), length=40), []),
    "a_row_no_longer_than_a_block": (_rows((-1, 15), length=16), []),
}


def _indexed(seg, scores):
    """An indexer's queries, keys and weights over rows ``seg``:
    ``random`` draws, or ``tied``: the keys one of THREE vectors and
    the weights constant, so that a query's scores take three values
    and the ``topk``-th falls among equals."""
    b, l = seg.shape
    rng = np.random.default_rng(l + b)
    q = rng.normal(size=(b, l, 2, 4)).astype(np.float32)
    k = rng.normal(size=(b, l, 4)).astype(np.float32)
    w = rng.normal(size=(b, l, 2)).astype(np.float32)
    if scores == "tied":
        k = k[:, :3][:, rng.integers(0, 3, size=l)]
        w = np.full_like(w, 0.25)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(w)


@pytest.mark.parametrize("scores", ["random", "tied"])
@pytest.mark.parametrize("case", sorted(_EDGE_ROWS))
def test_selection_mask_equals_scoring_every_block(case, scores):
    """``selection_mask`` scores only the blocks of queries in which
    some row sees more than ``topk`` keys and hands the others their
    visibility mask: EQUAL, entry for entry, to the unconditional rule
    (``select_topk`` of the whole row's scores under the whole
    visibility mask, written out here), on rows that sit on the edges
    of that predicate, with random scores and with tied ones."""
    seg, scoring = _EDGE_ROWS[case]
    topk, block = 24, 16
    q, k, w = _indexed(seg, scores)
    at = np.arange(seg.shape[1])
    visible = (seg[:, :, None] == seg[:, None, :]) \
        & (seg[:, :, None] != 0) & (at[None, :, None] >= at[None, None, :])
    want = np.asarray(sparse_index.select_topk(
        sparse_index.index_scores(q, k, w), jnp.asarray(visible), topk))
    got = np.asarray(jax.jit(
        lambda q, k, w, seg: sparse_index.selection_mask(
            q, k, w, seg, topk, block=block))(q, k, w, jnp.asarray(seg)))
    assert got.dtype == np.int8 and got.shape == visible.shape
    assert np.array_equal(got, want.astype(np.int8))
    assert got.sum() == sparse_index.pair_counts(seg, topk)[0]
    assert np.flatnonzero(sparse_index.scoring_blocks(
        seg, topk, block, xp=np)).tolist() == scoring
    # where no block scores the selection is the visibility mask
    # itself, and where one does a tie or a score decides some entry
    assert np.array_equal(got, visible) == (not scoring)


def test_blocks_that_score_by_host_and_program_and_the_counter(built):
    """One rule says which blocks of queries score:
    ``scoring_blocks`` gives the program (``xp=jnp``) and the host
    (``xp=np``) the same blocks on the same rows, a call of the program
    a leading axis, and the engine's ``index_blocks_total`` and its
    spans' ``index_scored_share`` grow by the host's count."""
    from realhf_tpu.obs import tracing
    stacked = np.stack([seg for seg, _ in _EDGE_ROWS.values()
                        if seg.shape == (1, 64)])
    for topk, block in ((24, 16), (6, 16), (40, 32), (24, 48), (63, 16)):
        host = sparse_index.scoring_blocks(stacked, topk, block, xp=np)
        assert host.shape == (len(stacked), 64 // block
                              if 64 % block == 0 else 1)
        program = jax.jit(lambda s: sparse_index.scoring_blocks(
            s, topk, block))(jnp.asarray(stacked))
        assert np.array_equal(host, np.asarray(program)), (topk, block)
        for seg, row in zip(stacked, host):  # a call at a time
            assert np.array_equal(row, sparse_index.scoring_blocks(
                seg, topk, block, xp=np))
    model = built("share")
    engine = _engine(model["cfg"], model["params"])
    ids, seg = _packed(model["docs"])
    # (rows of 64 are one block of queries here: 64 < QUERY_BLOCK)
    short = np.where(np.arange(64) % DOC < TOPK, seg, 0)
    assert sparse_index.scoring_blocks(seg, TOPK, xp=np).tolist() == [True]
    assert sparse_index.scoring_blocks(short, TOPK, xp=np).tolist() \
        == [False]
    tracing.start()
    engine.forward_hidden(ids, seg)
    engine.forward_hidden(ids, short)
    engine.forward_hidden(ids, short)
    capture = tracing.stop()
    assert capture.counter("index_blocks_total", role=ROLE,
                           kind="scored") == 3 * 1
    assert capture.counter("index_blocks_total", role=ROLE,
                           kind="all") == 3 * 3
    assert [s["attributes"]["index_scored_share"]
            for s in capture.named("engine:hidden")] == [1.0, 0.0, 0.0]


def test_sparse_stack_through_the_flash_kernels(interpreted_kernels):
    """Heads of 128 and rows of 1024, so that the packed rows meet the
    flash kernels' gate: with the kernels engaged (interpret mode) and
    the selection their one more operand, the stack gives the XLA
    path's hidden states and gradients; a block no selected pair falls
    in is counted on the host by the kernels' own rule."""
    # (a DENSE feed-forward, which the family's config reads from
    # ``mlp_only_layers`` as qwen3_moe does: the experts' kernels under
    # the interpreter are ``tests/ops/test_grouped_matmul.py``'s)
    hf = dict(CONFIGS["share"], hidden_size=128, num_attention_heads=2,
              num_key_value_heads=1, head_dim=128, num_hidden_layers=1,
              mlp_only_layers=[0],
              sa_config=dict(_BASE["sa_config"], topk=200))
    cfg = hf_models.config_from_hf(NAME, hf)
    cfg.param_dtype = cfg.compute_dtype = "float32"
    # (not rematerialised: the interpreter's callbacks are effects that
    # ``jax.checkpoint`` refuses; the compiled kernels under it are
    # ``tests/ops/test_chip_compile.py``'s)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(
        2, 128, size=(1, 1024)).astype(np.int32)
    seg = np.ones((1, 1024), np.int32)
    seg[0, 700:] = 2
    seg[0, 1000:] = 0

    def loss(p):
        # (a padding row's output is the paths' own: zeros from the
        # kernels, a mean over nothing from XLA)
        hidden, _ = T.forward(cfg, p, jnp.asarray(ids), jnp.asarray(seg))
        hidden = hidden * jnp.asarray(seg != 0)[..., None]
        return (hidden.astype(jnp.float32) ** 2).mean(), hidden

    run = jax.jit(jax.value_and_grad(loss, has_aux=True))
    (_, want), want_g = run(params)
    with interpreted_kernels():
        text = str(jax.make_jaxpr(
            jax.value_and_grad(loss, has_aux=True))(params))
        for name in ("flash_fwd_sel", "flash_bwd_dq_sel",
                     "flash_bwd_dkv_sel"):
            assert name in text, name
        (_, got), got_g = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    for name in ("wq", "wk", "wv", "wo"):
        a, b = (np.asarray(g["layers"]["0"]["attn"][name])
                for g in (got_g, want_g))
        assert np.abs(a - b).max() <= 2e-3 * np.abs(b).max() + 1e-9, name
    # what block skipping by the selection would save here
    _, states = T.forward(cfg, params, jnp.asarray(ids), jnp.asarray(seg),
                          return_kv=True)
    select = np.asarray(operators._index_select(
        cfg, params["layers"]["0"]["index"],
        operators._norm(cfg, params["embed"]["wte"][ids],
                params["layers"]["0"]["ln1"]["scale"], None),
        jnp.asarray(seg),
        *T._rotary_tables(cfg, T.positions_from_segments(
            jnp.asarray(seg)))["index"])[0])
    assert select.shape == (1, 1024, 1024) and select.dtype == np.int8
    assert select.sum() == sparse_index.pair_counts(seg, 200)[0]
    empty, visited = sparse_index.unselected_blocks(select, seg)
    assert 0 <= empty < visited
