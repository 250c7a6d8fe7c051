"""SmallThinker held to its plain reference
(``benchmark/families/smallthinker.py``) on the CPU: small widths that
keep every mechanism of the benchmark's cell (hidden 64, 4 query heads
over 2 key/value heads of 16; four layers, one period: a full layer
WITHOUT a rotary embedding, then three layers under a window of 8 with
one at base 1,500,000; a router that reads the layer's INPUT, 16
ReLU-gated experts of width 32, 3 a token, the gates a softmax over the
chosen logits), seeded random weights under Hugging Face's names
(``benchmark/generate.py`` makes them, the program's own loader reads
them), everything in float32. Two checkpoints: one that holds every
expert (the uncut model) and one expert-parallel rank's share (experts
4 to 5 of 16). Documents are 20 tokens, so the window (8) ends inside
a document: it BITES, and three are packed into a row of 64, so a
document's edge lies inside a window's reach.

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
from benchmark.families import smallthinker as family
from realhf_tpu.api.config import ModelName
from realhf_tpu.engine.engine import Engine
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.interfaces import sft
from realhf_tpu.models import hf as hf_models
from realhf_tpu.models import transformer as T
from realhf_tpu.models.hf import registry
from realhf_tpu.models.operators import n_params
from realhf_tpu.parallel import mesh as mesh_lib

#: max |delta logit| allowed between the program and the reference
LOGIT_TOL = 1e-5

_BASE = dict(
    model_type="smallthinker", model_name="toy", vocab_size=128,
    hidden_size=64, moe_ffn_hidden_size=32, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    max_position_embeddings=4096, rms_norm_eps=1e-6,
    moe_num_active_primary_experts=3,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    rope_layout=[0, 1, 1, 1], sliding_window_layout=[0, 1, 1, 1],
    sliding_window_size=8, rope_theta=1500000, rope_scaling=None,
    tie_word_embeddings=False, initializer_range=0.02, eos_token_id=1)
CONFIGS = {
    "whole": dict(_BASE, moe_num_primary_experts=16),
    "share": dict(_BASE, moe_num_primary_experts=2,
                  expert_share={"of": 16, "first": 4}),
}
DOC = 20  # tokens a document; three to a packed row of 64
NAME = "smallthinker"


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
        ModelName(f"smallthinker-d{dp}t{tp}", 0),
        mesh_lib.make_mesh(par, jax.devices()[:dp * tp]), par)
    return Engine(cfg, ctx, jax.tree.map(np.asarray, params), **kwargs)


def _engine_logits(engine, ids, seg):
    hidden = engine.forward_hidden(ids, seg)
    return np.asarray(T.lm_logits(engine.cfg, engine.params, hidden),
                      np.float32)


def test_config_is_read_from_the_published_keys(model):
    cfg, hf = model["cfg"], model["hf"]
    assert cfg.layer_pattern == (("attention", "moe"),) \
        + (("window", "moe"),) * 3
    assert cfg.pattern_string == "a w w w"
    assert (cfg.layers_of("attention"), cfg.layers_of("window"),
            cfg.kv_layers, cfg.n_moe_layers) == ((0,), (1, 2, 3), 4, 4)
    assert [cfg.layer_window(i) for i in range(4)] == [None, 8, 8, 8]
    assert cfg.rotary_by_operator["attention"] is None  # NoPE
    window = cfg.rotary_by_operator["window"]
    assert (window.scaling_type, window.base, window.partial_factor,
            window.interleaved) == (None, 1500000.0, 1.0, False)
    assert "attention" not in T._rotary_tables(
        cfg, jnp.zeros((1, 4), jnp.int32))
    assert (cfg.activation_function, cfg.gated_mlp, cfg.qk_norm,
            cfg.tied_embedding, cfg.layer_q_heads) == (
        "relu", True, None, False, None)
    moe = cfg.moe
    assert (moe.num_experts, moe.top_k, moe.score_fn, moe.router_input,
            moe.routing_type, moe.shared_intermediate_dim,
            moe.use_expert_bias) == (
        16, 3, "softmax", "layer_input", "none", None, False)
    assert moe.norm_topk_prob  # = a softmax over the chosen logits
    assert moe.experts_held == ((4, 2) if "expert_share" in hf else None)
    assert moe.n_held == hf["moe_num_primary_experts"]
    back = hf_models.config_to_hf(NAME, cfg)
    for key in sorted(set(hf) - {"initializer_range", "eos_token_id",
                                 "model_name"}):
        assert back[key] == hf[key], key
    assert ("expert_share" in back) == ("expert_share" in hf)
    n = sum(x.size for x in jax.tree.leaves(model["params"]))
    assert n == family.n_params(hf)
    # (the program's estimate leaves the layer norms' scales out)
    assert n_params(cfg) == n - (2 * cfg.n_layers + 1) * cfg.hidden_dim
    init = T.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(
        jnp.shape, model["params"])


@pytest.mark.parametrize("changed,match", [
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
    (dict(moe_primary_router_apply_softmax=False), "apply_softmax"),
    (dict(rope_layout=[0, 1, 0, 1]), "differ in rope_layout"),
    (dict(sliding_window_layout=[0, 1, 1]), "for 4 layers"),
])
def test_what_the_family_cannot_run_is_refused_not_ignored(changed, match):
    with pytest.raises(NotImplementedError, match=match):
        hf_models.config_from_hf(NAME, dict(CONFIGS["whole"], **changed))


def test_a_window_layer_without_a_rotary_is_refused_by_the_config():
    """``rotary_by_operator`` may say None for full layers alone."""
    with pytest.raises(ValueError, match="rotary_by_operator lacks"):
        hf_models.config_from_hf(NAME, dict(
            CONFIGS["whole"], rope_layout=[1, 0, 0, 0]))


def test_a_router_on_the_layers_input_belongs_to_a_pattern():
    from realhf_tpu.models.config import MoEConfig, TransformerConfig
    with pytest.raises(NotImplementedError, match="router_input"):
        TransformerConfig(
            n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=64,
            intermediate_dim=96, vocab_size=128, mlp_type="moe",
            moe=MoEConfig(router_input="layer_input"))
    with pytest.raises(NotImplementedError, match="router_input"):
        MoEConfig(router_input="attention_output")


def test_all_layouts_rotating_read_as_the_smaller_sibling_has_them():
    """``rope_layout`` all ones (the 4B sibling's): both kinds of layer
    get the table, and the program equals the reference there too."""
    hf = dict(CONFIGS["whole"], rope_layout=[1, 1, 1, 1],
              num_hidden_layers=4)
    cfg = hf_models.config_from_hf(NAME, hf)
    assert cfg.rotary_by_operator["attention"] \
        == cfg.rotary_by_operator["window"]
    assert hf_models.config_to_hf(NAME, cfg)["rope_layout"] == [1, 1, 1, 1]


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
    # and the reference's log-probabilities are those of its logits
    lp = family.logprobs(model["hf"], model["tensors"], model["docs"])
    want = np.take_along_axis(
        np.asarray(jax.nn.log_softmax(model["want"], axis=-1))[:, :-1],
        model["docs"][:, 1:, None], -1)[..., 0]
    assert np.abs(lp - want).max() < LOGIT_TOL


NOT_WRONG = "softmax_over_all_renormalised"


@pytest.mark.parametrize("wrong", tuple(w for w in family.WRONG
                                        if w != NOT_WRONG)
                         + ("positions_of_the_row",))
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


def test_softmax_over_all_renormalised_is_the_softmax_over_the_chosen(
        model):
    """The two readings of ``moe_primary_router_apply_softmax`` with
    ``norm_topk_prob`` are one function: the softmax over all 16
    logits, its 3 largest divided by their sum, is the softmax over
    those 3 logits."""
    got = family.logits(model["hf"], model["tensors"], model["docs"],
                        wrong=(NOT_WRONG,))
    assert np.abs(got - model["want"]).max() < LOGIT_TOL


@pytest.mark.parametrize("dp,tp", [(1, 2), (2, 1)])
def test_sharded_forward_matches_one_device(model, dp, tp):
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
    stack for all four layers (window layers keep every row, the decode
    attention masks what is past the window), the NoPE layer's keys
    written as they are, and the ROUTER of a decoded token reading the
    token before its layer's attention step. A prefill of 12 leaves the
    window's edge (8) inside the prompt; decoding to 20 moves it
    through the cache."""
    cfg, params, docs = model["cfg"], model["params"], model["docs"]
    ids = jnp.asarray(docs[:, :n_pre])
    hidden, cache = jax.jit(
        lambda p, i: T.prefill(cfg, p, i, jnp.ones_like(i),
                               total_len=DOC))(params, ids)
    assert cache["k"].shape[:3] == (4, len(docs), 2)
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
    """Loss and the gradient of every tensor (the router's, which its
    layer's INPUT reaches and the attention's output does not, among
    them) against ``jax.grad`` of the reference, three documents and
    four pads a row; to 1e-4 of a tensor's norm, rematerialised blocks
    or not."""
    model = dict(model, cfg=_with(model["cfg"],
                                  gradient_checkpointing=remat))
    (loss, stats, grads), (ref_loss, parts, ref_grads) = _sft_case(
        model, n_docs=3, prompt_len=5)
    assert abs(loss - ref_loss) < 1e-5
    assert abs(stats["nll"] - parts["nll"]) < 1e-5
    assert "moe_aux_loss" not in stats and parts["aux"] == 0.0
    assert stats["moe_load_max_over_mean"] >= 1.0
    assert set(grads) == set(ref_grads)
    moved = 0
    for name in sorted(grads):
        norm = np.linalg.norm(ref_grads[name])
        gap = np.linalg.norm(grads[name] - ref_grads[name])
        # (an expert that no answer token chose has no gradient, here
        # and there: 3 of 16 a token over 45 tokens leave a few)
        assert norm > 0 or ".experts." in name, name
        assert gap <= 1e-4 * norm, (name, gap, norm)
        moved += norm > 0
    assert moved > 0.8 * len(grads)


def _with(cfg, **changed):
    import dataclasses
    return dataclasses.replace(cfg, **changed)


def _one_layer(built):
    """The uncut checkpoint's layer 0 alone (the full layer), as a
    model: its config, the program's parameters and the reference's
    tensors."""
    model = built("whole")
    hf = dict(model["hf"], num_hidden_layers=1, rope_layout=[0],
              sliding_window_layout=[0])
    cfg = hf_models.config_from_hf(NAME, hf)
    cfg.param_dtype = cfg.compute_dtype = "float32"
    params = dict(model["params"],
                  layers={"0": model["params"]["layers"]["0"]})
    return model, hf, cfg, params


def test_the_router_reads_the_layers_input_not_what_attention_made(built):
    """A layer whose attention is zeroed (``wo = 0``) routes every token
    as the layer whose attention is not: the router's product reads the
    layer's INPUT. The program's load statistics are equal between the
    two to the last bit, and are the counts of the reference's routing;
    a router after attention (the usual place) routes the two
    differently, so the test could tell."""
    model, hf, cfg, params = _one_layer(built)
    ids = jnp.asarray(model["docs"])
    seg = jnp.ones_like(ids)

    def load(p):
        _, _, aux = jax.jit(lambda p: T.forward(
            cfg, p, ids, seg, return_aux=True))(p)
        return {k: float(v) for k, v in aux.items()}

    zeroed = jax.tree.map(lambda a: a, params)
    attn = zeroed["layers"]["0"]["attn"]
    zeroed["layers"]["0"]["attn"] = dict(attn, wo=jnp.zeros_like(attn["wo"]))
    assert load(params) == load(zeroed)
    routed = family.top_k_sets(hf, model["tensors"], model["docs"], 0)
    counts = routed.reshape(-1, 16).sum(0)
    assert counts.sum() == model["docs"].size * 3
    assert load(params)["moe_load_max_over_mean"] == pytest.approx(
        counts.max() / counts.mean())
    # the tensors with o_proj zeroed, through the reference
    name = "model.layers.0.self_attn.o_proj.weight"
    without = dict(model["tensors"], **{
        name: np.zeros_like(model["tensors"][name])})
    assert np.array_equal(
        family.top_k_sets(hf, without, model["docs"], 0), routed)
    after = [family.top_k_sets(hf, t, model["docs"], 0,
                               wrong=("router_after_attention",))
             for t in (model["tensors"], without)]
    assert not np.array_equal(after[0], after[1])


def test_eight_shares_of_a_layer_add_up_to_the_uncut_layer(built):
    """The guide's tie of the share to the model, on the PROGRAM's
    side: layer 0 of the uncut checkpoint run as eight shares of 2
    experts each (``expert_share {"of": 16, "first": 2 f}``, the
    stacks sliced, the router whole). Every share computes the
    attention and the residual alike, ``a``; what its experts add is
    its own. ``a`` once and the eight shares' parts are the uncut
    REFERENCE's layer."""
    model, hf, cfg, params = _one_layer(built)
    ids = jnp.asarray(model["docs"])
    seg = jnp.ones_like(ids)

    def layer_out(cfg_, p):
        """x after layer 0, before the final norm."""
        x = p["embed"]["wte"][ids]
        ctx = T.Ctx(T._rotary_tables(cfg_, jnp.zeros_like(ids)),
                    seg_ids=seg)
        y, _, _ = T._block(cfg_, p["layers"]["0"], x, ctx, lambda a: a,
                           kind=cfg_.layer_pattern[0])
        return np.asarray(y)

    mlp = params["layers"]["0"]["mlp"]

    def share(first):
        cfg_f = hf_models.config_from_hf(NAME, dict(
            hf, moe_num_primary_experts=2,
            expert_share={"of": 16, "first": first}))
        cfg_f.param_dtype = cfg_f.compute_dtype = "float32"
        held = {k: (v if k == "router" else v[first:first + 2])
                for k, v in mlp.items()}
        p = dict(params, layers={"0": dict(params["layers"]["0"],
                                           mlp=held)})
        return cfg_f, p

    # what every share computes alike: the layer with no expert's output
    none = dict(params, layers={"0": dict(params["layers"]["0"], mlp=dict(
        mlp, wd=jnp.zeros_like(mlp["wd"])))})
    alike = layer_out(cfg, none)
    parts = [layer_out(*share(f)) - alike for f in range(0, 16, 2)]
    with jax.default_matmul_precision("highest"):
        want, _ = family._blocks(hf, family._getter(model["tensors"], None),
                                 ids)
    want = np.asarray(want)
    assert all(np.abs(p).max() > 1e-5 for p in parts)  # each share adds
    assert np.abs(alike + sum(parts) - want).max() < LOGIT_TOL
    # the program's uncut layer too, and the shares are not the whole
    assert np.abs(layer_out(cfg, params) - want).max() < LOGIT_TOL
    assert np.abs(alike + parts[0] - want).max() > 50 * LOGIT_TOL


def test_a_share_asked_dense_equals_its_ragged_form(built):
    """A share goes through the ragged mode's sorted pairs, as the
    uncut model and every other sparse family's share do, unless its
    config says ``expert_dispatch: "dense"``: the dense mode over the
    HELD stacks (every held expert over every token: a cost that does
    not move with where the router sends the tokens). Both give the
    same hidden states, loss statistics and gradients; the key goes
    both ways through the converter and another value is refused."""
    import dataclasses
    from realhf_tpu.ops import moe as moe_ops
    model = built("share")
    ragged = model["cfg"]
    assert moe_ops.dispatch_mode(ragged) == "ragged"
    assert moe_ops.dispatch_mode(built("whole")["cfg"]) == "ragged"
    asked = dict(model["hf"], expert_dispatch="dense")
    cfg = hf_models.config_from_hf(NAME, asked)
    assert moe_ops.dispatch_mode(cfg) == "dense"
    assert hf_models.config_to_hf(NAME, cfg)["expert_dispatch"] == "dense"
    assert "expert_dispatch" not in hf_models.config_to_hf(NAME, ragged)
    with pytest.raises(NotImplementedError, match="expert_dispatch"):
        hf_models.config_from_hf(NAME, dict(asked, expert_dispatch="x"))
    cfg = dataclasses.replace(ragged, moe=dataclasses.replace(
        ragged.moe, use_grouped_gemm=False))
    ids, seg = (jnp.asarray(a) for a in _packed(model["docs"]))

    def run(c):
        def loss(p):
            h, _, aux = T.forward(c, p, ids, seg, return_aux=True)
            return jnp.square(h.astype(jnp.float32)).mean(), (h, aux)
        (_, (h, aux)), grads = jax.jit(jax.value_and_grad(
            loss, has_aux=True))(model["params"])
        return np.asarray(h), {k: float(v) for k, v in aux.items()}, grads

    h_d, aux_d, g_d = run(cfg)
    h_r, aux_r, g_r = run(ragged)
    assert np.abs(h_d - h_r).max() < LOGIT_TOL
    assert aux_d.pop("moe_share_overflows") == 0.0  # no rows to overflow
    aux_r.pop("moe_share_overflows")
    assert aux_d == aux_r
    for a, b in zip(jax.tree.leaves(g_d), jax.tree.leaves(g_r)):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() \
            <= 1e-5 * max(np.abs(np.asarray(b)).max(), 1e-6)


def test_held_statistics_are_the_reference_routings_counts(built):
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
    for layer in range(cfg.n_layers):
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
    attributes the family brings, the router's product under a part of
    its own in the compiled program, and every kind of leaf moved."""
    from realhf_tpu.obs import parts, tracing
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
    for i in range(4):
        for part, leaf in (("attn", "wq"), ("attn", "wo"), ("mlp", "router"),
                           ("mlp", "wg"), ("mlp", "wd")):
            assert not np.array_equal(
                before["layers"][str(i)][part][leaf],
                after["layers"][str(i)][part][leaf]), (i, part, leaf)
    [span] = capture.named("engine:train")
    a = span["attributes"]
    assert (a["layer_pattern"], a["window"], a["window_layers"],
            a["nope_layers"], a["router_input"], a["router"],
            a["experts_held"], a["experts"], a["top_k"], a["moe_dispatch"],
            a["conv_layers"], a["dense_layers"]) == (
        "a w w w", 8, 3, 1, "layer_input", "softmax", 2, 16, 3,
        "ragged", 0, 0)
    assert a["rotary"] == "a:none w:plain@1.5e+06/1"
    assert "flash_stream_rows" not in a  # the CPU's rows go to no kernel
    tokens = 2 * 3 * DOC
    role = "smallthinker-d1t1"
    assert capture.counter("moe_routed_pairs_total", role=role,
                           dispatch="ragged") == tokens * 3 * 4
    held = capture.counter("moe_held_pairs_total", role=role)
    assert held == stats["moe_held_pairs"] == a["moe_held_pairs"]
    assert 0 < held < 2 * 64 * 3 * 4
    table = parts.parse_program(engine.compiled_text("train"))
    by_part = {part for part, *_ in table.values()}
    assert {"experts/router", "experts/route", "experts/gather",
            "experts/products", "experts/combine", "attn",
            "attn_proj"} <= by_part
    products = [(part, pass_) for part, pass_, opcode, *_ in table.values()
                if part == "experts/router"
                and opcode in parts.PRODUCTS_OPCODES]
    assert products and {p for _, p in products} >= {"fwd", "bwd"}


def test_other_families_routers_stay_under_route():
    """``experts/router`` is the family's own: a model whose router
    reads the feed-forward's input has no such part, and its program
    is the one it was."""
    from realhf_tpu.models.config import MoEConfig, TransformerConfig
    from realhf_tpu.obs import parts
    cfg = TransformerConfig(
        n_layers=1, n_kv_heads=2, n_q_heads=4, hidden_dim=64,
        intermediate_dim=32, vocab_size=128, mlp_type="moe",
        layer_norm_type="rms", apply_rotary=True, use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu", compute_dtype="float32",
        moe=MoEConfig(num_experts=4, top_k=2, routing_type="none"))
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ids = jnp.ones((1, 16), jnp.int32)
    text = jax.jit(lambda p: T.forward(cfg, p, ids, ids)[0]).lower(
        params).compile().as_text()
    by_part = {part for part, *_ in parts.parse_program(text).values()}
    assert "experts/route" in by_part and "experts/router" not in by_part


def test_hf_round_trip_is_bit_equal(model, tmp_path):
    state, cfg = model["tensors"], model["cfg"]
    back = hf_models.params_to_hf(
        NAME, hf_models.params_from_hf(NAME, state, cfg), cfg)
    assert set(back) == set(state) == set(family.shapes(model["hf"]))
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
    assert saved["model_type"] == "smallthinker"
    assert saved.get("expert_share") == model["hf"].get("expert_share")
    assert (saved["rope_layout"], saved["sliding_window_layout"]) == (
        [0, 1, 1, 1], [0, 1, 1, 1])
    assert registry.detect_family(path) == NAME
    ccfg, critic = registry.load_hf_checkpoint(path, NAME, is_critic=True)
    assert ccfg.is_critic and critic["head"]["w"].shape == (64, 1)
    np.testing.assert_array_equal(
        critic["layers"]["2"]["mlp"]["router"],
        np.asarray(model["params"]["layers"]["2"]["mlp"]["router"]))


@pytest.mark.parametrize("tp", [1, 2])
def test_streamed_load_and_save_round_trip(model, tmp_path, tp):
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
    from realhf_tpu.engine import inflight, kv_pool
    from realhf_tpu.models import sharding
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    model = built("share")
    cfg, params = model["cfg"], model["params"]
    g = GenerationHyperparameters(max_new_tokens=2, greedy=True,
                                  force_no_logits_mask=True)
    named = (r"layer pattern \(layer_pattern 'a w w w': 1 attention "
             r"layers, 3 window layers, 4 layers with experts")
    with pytest.raises(NotImplementedError, match="slot engine.*" + named):
        inflight.InflightBatchingGenerator(
            cfg, params, g, n_slots=2, max_prompt_len=8,
            eos_token_id=None, pad_token_id=0)
    with pytest.raises(NotImplementedError, match="KV pool.*" + named):
        kv_pool.KVPool(cfg, n_blocks=4, block_len=8)
    with pytest.raises(NotImplementedError, match="pipeline.*" + named):
        sharding.param_pspecs(cfg, pipeline_parallel=True)


def test_the_stack_through_the_stream_kernels_counts_its_rows(
        interpreted_kernels, monkeypatch):
    """Heads of 64 and a row of 1024 with the whole-row kernels' limit
    lowered to 512, so that the row goes to the kernels that STREAM K
    and V (interpret mode): the stack of a NoPE full layer and three
    rotary layers under a window of 128 gives the XLA path's hidden
    states; ``flash_kv_blocks_total`` adds up each layer by its own
    rule, as for any row, and the span says how many rows streamed
    (``flash_stream_rows``, counter ``flash_stream_rows_total``): 0 for
    a row the whole-row kernels take."""
    from realhf_tpu.obs import tracing
    from realhf_tpu.ops import flash_attention as fa
    hf = dict(CONFIGS["share"], hidden_size=128, head_dim=64,
              num_key_value_heads=1, num_attention_heads=2,
              sliding_window_size=128)
    cfg = hf_models.config_from_hf(NAME, hf)
    cfg.param_dtype = cfg.compute_dtype = "float32"
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    ids = rng.integers(2, 128, size=(1, 1024)).astype(np.int32)
    seg = np.ones((1, 1024), np.int32)
    seg[0, 700:] = 2

    def run():
        engine = _engine(cfg, params)
        tracing.start()
        hidden = np.asarray(engine.forward_hidden(ids, seg))
        return hidden, tracing.stop(), engine

    want, xla, _ = run()
    assert not any(k.startswith("flash_") for k in xla.counters)
    role = "smallthinker-d1t1"
    streamed, stream = [], fa._flash_fwd_stream
    monkeypatch.setattr(fa, "_flash_fwd_stream", lambda *a: (
        streamed.append(a[-1]), stream(*a))[1])
    for limit, rows in ((512, 1), (fa.FLASH_MAX_LEN, 0)):
        monkeypatch.setattr(fa, "FLASH_MAX_LEN", limit)
        del streamed[:]
        with interpreted_kernels():
            got, capture, _ = run()
        # every layer's attention, each under its own window
        assert streamed == [None, 128, 128, 128][:4 * rows]
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
        [span] = capture.named("engine:hidden")
        assert span["attributes"]["flash_stream_rows"] == rows
        assert capture.counter("flash_stream_rows_total", role=role) == rows
        visited = sum(fa.block_counts(seg, sliding_window=w)[0]
                      for w in (None, 128, 128, 128))
        assert capture.counter("flash_kv_blocks_total", role=role,
                               kind="visited") == visited
