"""OLMoE held to its plain reference (``benchmark/families/olmoe.py``)
on the CPU: small widths that keep the shape of the problem (hidden 64,
4 heads of 16, experts of width 32, 2 layers; 8 experts top-2 and 16
experts top-8), seeded random weights under Hugging Face's names with
random norm scales (``benchmark/generate.py`` makes them, the program's
own loader reads them), everything in float32.

Every comparison is float32 against float32 on the same values, so the
tolerances are those of two orders of summation: the packed forward's
largest |delta logit| is 2.1e-7 and 2.4e-7 (top-2, top-8-of-16) on
logits of spread 0.16. ``LOGIT_TOL`` is 40 times that and 350 to 8,000
times under what each wrong equation gives (tried once, PR 26, largest
|delta logit| at top-2 / top-8-of-16): gates renormalised 0.028 /
0.0091, the query/key norm taken per head 0.073 / 0.088, the norm
after the rotary embedding 0.0088 / 0.0066 (a rotation keeps the
whole-width mean of squares, so only the scale's place differs), the
forward computed in bf16 0.0035 / 0.0037.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import generate, reference
from benchmark.families import olmoe as family
from realhf_tpu.api.config import ModelName
from realhf_tpu.engine import inflight
from realhf_tpu.engine.engine import Engine
from realhf_tpu.interfaces import sft
from realhf_tpu.models import hf as hf_models
from realhf_tpu.models import transformer as T
from realhf_tpu.models.hf import registry
from realhf_tpu.ops.sampling import GenerationHyperparameters
from realhf_tpu.parallel import mesh as mesh_lib
from realhf_tpu.models.operators import n_params

#: max |delta logit| allowed between the program and the reference
LOGIT_TOL = 1e-5
#: the same for log-probabilities of emitted tokens
LOGPROB_TOL = 1e-5

_BASE = dict(
    model_type="olmoe", architectures=["OlmoeForCausalLM"],
    hidden_size=64, intermediate_size=32, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=4, vocab_size=128,
    norm_topk_prob=False, rms_norm_eps=1e-5, rope_theta=10000,
    rope_scaling=None, tie_word_embeddings=False, attention_bias=False,
    max_position_embeddings=256, clip_qkv=None, hidden_act="silu",
    initializer_range=0.02, router_aux_loss_coef=0.01, eos_token_id=1)
CONFIGS = {
    "top2": dict(_BASE, num_experts=8, num_experts_per_tok=2),
    "top8of16": dict(_BASE, num_experts=16, num_experts_per_tok=8),
}
DOC = 24  # tokens a document; two to a packed row of 64


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
            cfg, params = registry.load_hf_checkpoint(ckpt, "olmoe")
            cfg.param_dtype = cfg.compute_dtype = "float32"
            params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                                  params)
            docs = np.random.default_rng(3).integers(
                2, hf["vocab_size"], size=(4, DOC)).astype(np.int32)
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
    """Two documents a row of 64: ids, segment ids (0 = pad)."""
    rows = len(docs) // 2
    ids = np.zeros((rows, 64), np.int32)
    seg = np.zeros((rows, 64), np.int32)
    for r in range(rows):
        for j in range(2):
            ids[r, j * DOC:(j + 1) * DOC] = docs[2 * r + j]
            seg[r, j * DOC:(j + 1) * DOC] = j + 1
    return ids, seg


def _unpacked(x):
    """[rows, 64, ...] -> [2 * rows, DOC, ...]: the documents back."""
    return np.concatenate(
        [x[r:r + 1, j * DOC:(j + 1) * DOC]
         for r in range(x.shape[0]) for j in range(2)])


def _engine(cfg, params, dp=1, tp=1):
    par = mesh_lib.ParallelismConfig(data_parallel_size=dp,
                                     tensor_parallel_size=tp)
    ctx = mesh_lib.MeshContext(
        ModelName(f"olmoe-d{dp}t{tp}", 0),
        mesh_lib.make_mesh(par, jax.devices()[:dp * tp]), par)
    return Engine(cfg, ctx, jax.tree.map(np.asarray, params))


def _engine_logits(engine, docs):
    ids, seg = _packed(docs)
    hidden = engine.forward_hidden(ids, seg)
    logits = T.lm_logits(engine.cfg, engine.params, hidden)
    return _unpacked(np.asarray(logits, np.float32))


def test_config_is_read_from_the_published_keys(model):
    cfg, hf = model["cfg"], model["hf"]
    assert cfg.qk_norm == "full" and cfg.mlp_type == "moe"
    assert cfg.moe.norm_topk_prob is False
    assert cfg.moe.routing_type == "aux_loss"
    assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.aux_loss_coeff) == (
        hf["num_experts"], hf["num_experts_per_tok"], 0.01)
    assert cfg.moe.capacity_factor is None and cfg.moe.use_grouped_gemm
    back = hf_models.config_to_hf("olmoe", cfg)
    for key in ("model_type", "hidden_size", "intermediate_size",
                "num_experts", "num_experts_per_tok", "norm_topk_prob",
                "num_attention_heads", "num_key_value_heads",
                "rms_norm_eps", "rope_theta", "vocab_size", "clip_qkv",
                "router_aux_loss_coef", "tie_word_embeddings"):
        assert back[key] == hf[key], key
    n = sum(x.size for x in jax.tree.leaves(model["params"]))
    assert n == family.n_params(hf)
    # the program's estimate leaves the layer norms' scales out
    assert n_params(cfg) == n - (2 * cfg.n_layers + 1) * cfg.hidden_dim


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_matches_transformers(name, tmp_path):
    """The reference was written from memory of ``modeling_olmoe.py``;
    the ``transformers`` installed here carries that file, so the
    memory is checked: the same logits, to float32 rounding, from
    ``OlmoeForCausalLM`` on weights it saved under its own names
    (norm scales moved off 1). The program is held to ``transformers``
    in ``test_hf_parity.py``."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    hf = CONFIGS[name]
    torch.manual_seed(3)
    model = transformers.OlmoeForCausalLM(transformers.OlmoeConfig(**{
        k: v for k, v in hf.items()
        if k not in ("model_type", "architectures")})).eval()
    with torch.no_grad():
        for pname, p in model.named_parameters():
            if "norm" in pname:
                p.add_(0.2 * torch.randn_like(p))
    model.save_pretrained(tmp_path, safe_serialization=True)
    with open(tmp_path / "config.json") as f:
        saved = json.load(f)
    tensors = reference.load_tensors(str(tmp_path))
    assert {k: v.shape for k, v in tensors.items()} == {
        n.format(i): shape[1:] if "{}" in n else shape
        for n, (shape, _) in family.shapes(saved).items()
        for i in (range(shape[0]) if "{}" in n else [0])}
    docs = np.random.default_rng(4).integers(
        0, hf["vocab_size"], size=(2, DOC))
    with torch.no_grad():
        want = model(input_ids=torch.from_numpy(docs)).logits.numpy()
    got = family.logits(saved, tensors, docs.astype(np.int32))
    assert np.abs(got - want).max() < LOGIT_TOL
    # and what the configuration file lists as assumed is what
    # transformers' OlmoeConfig defaults to
    default = transformers.OlmoeConfig()
    assert (default.initializer_range, default.eos_token_id,
            default.pad_token_id, default.router_aux_loss_coef,
            default.norm_topk_prob, default.clip_qkv) == (
        0.02, 50279, 1, 0.01, False, None)


def test_clip_qkv_is_refused_not_ignored():
    with pytest.raises(NotImplementedError, match="clip_qkv"):
        hf_models.config_from_hf("olmoe",
                                 dict(CONFIGS["top2"], clip_qkv=8.0))


def test_packed_forward_matches_reference(model):
    """Logits of the engine's packed forward, two documents a row."""
    got = _engine_logits(_engine(model["cfg"], model["params"]),
                         model["docs"])
    assert got.shape == model["want"].shape
    assert np.abs(got - model["want"]).max() < LOGIT_TOL
    assert model["want"].std() > 0.1  # the logits are not all alike


@pytest.mark.parametrize("dp,tp", [(1, 2), (2, 1)])
def test_sharded_forward_matches_one_device(model, dp, tp):
    """The whole-width norm under tensor parallelism (the mean of
    squares spans the shards), the ragged mode under data
    parallelism: the same logits as on one device, and so the
    reference's."""
    got = _engine_logits(_engine(model["cfg"], model["params"], dp, tp),
                         model["docs"])
    assert np.abs(got - model["want"]).max() < LOGIT_TOL


def test_prefill_then_decode_matches_full_forward(model):
    """``engine/generation.py``'s two steps, teacher-forced: a prefill
    of 16 tokens, then one ``decode_step`` a token through the cache;
    the logits of every position against the reference's forward over
    the whole document."""
    cfg, params, docs = model["cfg"], model["params"], model["docs"]
    n_pre = 16
    ids = jnp.asarray(docs[:, :n_pre])
    hidden, cache = jax.jit(
        lambda p, i: T.prefill(cfg, p, i, jnp.ones_like(i),
                               total_len=DOC))(params, ids)
    got = [np.asarray(T.lm_logits(cfg, params, hidden))]
    step = jax.jit(lambda p, c, t, pos: T.decode_step(
        cfg, p, c, t, pos, uniform_slot=True))
    for t in range(n_pre, DOC):
        h, cache = step(params, cache, jnp.asarray(docs[:, t]),
                        jnp.full((len(docs),), t, jnp.int32))
        got.append(np.asarray(T.lm_logits(cfg, params, h))[:, None])
    got = np.concatenate(got, axis=1)
    assert np.abs(got - model["want"]).max() < LOGIT_TOL


def test_slot_engine_extend_rows_matches_full_forward(model):
    """The slot engine's own call of the projections
    (``inflight._extend_rows``: suffix prefill and speculative verify):
    8 tokens at once against the rows a prefill of 16 left."""
    cfg, params, docs = model["cfg"], model["params"], model["docs"]
    n_pre, m = 16, 8
    ids = jnp.asarray(docs[:, :n_pre])
    _, cache = T.prefill(cfg, params, ids, jnp.ones_like(ids),
                         total_len=DOC)
    pos = jnp.broadcast_to(jnp.arange(n_pre, n_pre + m), (len(docs), m))
    hidden, _, _ = jax.jit(
        lambda p, k, v, valid, tok, pos: inflight._extend_rows(
            cfg, None, p, k, v, valid, tok, pos, pos,
            jnp.ones_like(tok, bool)))(
        params, cache["k"], cache["v"], cache["valid"],
        jnp.asarray(docs[:, n_pre:]), pos)
    got = np.asarray(T.lm_logits(cfg, params, hidden))
    assert np.abs(got - model["want"][:, n_pre:]).max() < LOGIT_TOL


def test_slot_engine_generation_matches_reference(model):
    """``InflightBatchingGenerator`` whole: three prompts of unequal
    length through two slots (prefill into a slot, decode chunks,
    refill), greedy; the log-probability it reports for every token it
    emitted against the reference's full forward over prompt plus
    emitted tokens."""
    cfg, params, hf = model["cfg"], model["params"], model["hf"]
    gen = inflight.InflightBatchingGenerator(
        cfg, params,
        GenerationHyperparameters(max_new_tokens=5, greedy=True,
                                  force_no_logits_mask=True),
        n_slots=2, max_prompt_len=16, eos_token_id=None, pad_token_id=0,
        chunk_size=2)
    prompts = [model["docs"][0][:16], model["docs"][1][:9],
               model["docs"][2][:12]]
    for prompt, out in zip(prompts, gen.generate_all(
            prompts, jax.random.PRNGKey(0))):
        assert len(out.tokens) == 5
        seq = np.concatenate([prompt, out.tokens])[None]
        want = family.logprobs(hf, model["tensors"], seq)[0, -5:]
        assert np.abs(out.logprobs - want).max() < LOGPROB_TOL


def _sft_objective(cfg, params):
    """What an SFT train step differentiates: the engine's forward and
    auxiliary terms around the interface's head and loss."""
    return _engine(cfg, params)._objective(sft._make_loss_fn(cfg))


def _sft_case(model, n_docs, doc_len, prompt_len):
    """One SFT microbatch: (program's loss, stats, gradient under HF's
    names), (reference's loss, parts, gradient)."""
    cfg, params = model["cfg"], model["params"]
    docs = model["docs"][:n_docs, :doc_len]
    row = 64
    ids = np.zeros((1, row), np.int32)
    seg = np.zeros((1, row), np.int32)
    prompt = np.zeros((1, row), bool)
    for j, doc in enumerate(docs):
        ids[0, j * doc_len:(j + 1) * doc_len] = doc
        seg[0, j * doc_len:(j + 1) * doc_len] = j + 1
        prompt[0, j * doc_len:j * doc_len + prompt_len] = True
    mb = dict(input_ids=jnp.asarray(ids), seg_ids=jnp.asarray(seg),
              prompt_mask=jnp.asarray(prompt))
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        _sft_objective(cfg, params), has_aux=True))(params, mb)
    got = hf_models.params_to_hf(
        "olmoe", jax.tree.map(np.asarray, grads), cfg)
    want = family.sft_loss_and_grad(model["hf"], model["tensors"], docs,
                                    prompt_len)
    return (float(loss), {k: float(v) for k, v in stats.items()}, got), \
        want, docs


def _check_sft(got, want):
    (loss, stats, grads), (ref_loss, parts, ref_grads) = got, want
    # float32 sums in two orders: the loss of 4.9 agrees to 5e-7, the
    # auxiliary term of 0.02 to 2e-9; every gradient leaf to 2e-5 of
    # the largest entry of the reference's leaf (measured: 1.1e-6)
    assert abs(loss - ref_loss) < 1e-5
    assert abs(stats["nll"] - parts["nll"]) < 1e-5
    assert abs(stats["moe_aux_loss"] - parts["aux"]) < 1e-7
    assert parts["aux"] > 0.005  # one layer's is about coef * 1
    assert set(grads) == set(ref_grads)
    for name in sorted(grads):
        scale = np.abs(ref_grads[name]).max()
        gap = np.abs(grads[name] - ref_grads[name]).max()
        assert gap <= 2e-5 * scale + 1e-12, (name, gap, scale)


def test_sft_loss_and_gradient_match_reference(model):
    """Loss (cross-entropy over answer tokens plus the program's
    auxiliary term) and the gradient of every leaf against
    ``jax.grad`` of the reference, two documents and 16 pads a row."""
    got, want, _ = _sft_case(model, n_docs=2, doc_len=DOC, prompt_len=6)
    _check_sft(got, want)
    assert got[1]["moe_load_max_over_mean"] >= 1.0


def test_sft_gradient_of_experts_without_tokens_is_zero(built):
    """A microbatch of one document of 4 tokens routes 4 x top_k pairs
    a layer: with 8 experts top-2 some expert receives none, and its
    gradient is zero in the program and in the reference; the other
    leaves still agree."""
    model = built("top2")
    got, want, docs = _sft_case(model, n_docs=1, doc_len=4, prompt_len=1)
    _check_sft(got, want)
    routed, _ = family.top_k_sets(model["hf"], model["tensors"], docs)
    idle = np.flatnonzero(~routed.any(axis=(0, 1)))
    assert len(idle) > 0
    for e in idle:
        name = f"model.layers.0.mlp.experts.{e}.down_proj.weight"
        assert not got[2][name].any() and not want[2][name].any()


def test_load_statistic_is_the_reference_routings_worst_expert(model):
    """What the train step returns beside the loss: the busiest
    expert's (token, k) pairs over the mean, worst layer, on a row
    with no pads (the program routes pads too) against the counts of
    the reference's own routing."""
    cfg, hf = model["cfg"], model["hf"]
    docs = np.random.default_rng(9).integers(
        2, hf["vocab_size"], size=(2, 32)).astype(np.int32)
    mb = dict(input_ids=jnp.asarray(docs.reshape(1, 64)),
              seg_ids=jnp.asarray(np.repeat([[1, 2]], 32, axis=1)),
              prompt_mask=jnp.zeros((1, 64), bool))
    _, stats = jax.jit(_sft_objective(cfg, model["params"]))(
        model["params"], mb)
    worst = 0.0
    for layer in range(cfg.n_layers):
        routed, _ = family.top_k_sets(hf, model["tensors"], docs, layer)
        counts = routed.reshape(-1, hf["num_experts"]).sum(0)
        assert counts.sum() == 64 * hf["num_experts_per_tok"]
        worst = max(worst, counts.max() / counts.mean())
    assert float(stats["moe_load_max_over_mean"]) == pytest.approx(worst)
    assert worst > 1.0


def test_hf_round_trip_is_bit_equal(model, tmp_path):
    state = model["tensors"]
    cfg = model["cfg"]
    back = hf_models.params_to_hf(
        "olmoe", hf_models.params_from_hf("olmoe", state, cfg), cfg)
    assert set(back) == set(state)
    for name in state:
        assert back[name].dtype == state[name].dtype
        assert np.array_equal(back[name].view(np.uint16),
                              state[name].view(np.uint16)), name
    # and through the files: the critic variant keeps the body
    path = str(tmp_path / "saved")
    registry.save_hf_checkpoint(
        path, "olmoe", cfg, jax.tree.map(np.asarray, model["params"]))
    with open(os.path.join(path, "config.json")) as f:
        assert json.load(f)["model_type"] == "olmoe"
    assert registry.detect_family(path) == "olmoe"
    ccfg, critic = registry.load_hf_checkpoint(path, "olmoe",
                                               is_critic=True)
    assert ccfg.is_critic and critic["head"]["w"].shape == (64, 1)
    np.testing.assert_array_equal(
        critic["blocks"]["attn"]["q_norm"],
        np.asarray(model["params"]["blocks"]["attn"]["q_norm"]))
