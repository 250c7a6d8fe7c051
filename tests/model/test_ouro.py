"""Ouro (ByteDance's LOOPED language model) held to its plain reference
(``benchmark/families/ouro.py``: four written-out passes) on the CPU:
small widths that keep every mechanism of the benchmark's cell (hidden
64, 2 layers of 4 heads of 16 and a SwiGLU of 160 walked T = 4 times
over ONE set of weights, and T = 1 and 3; a norm before and after each
operator; the final norm after every pass feeding the next pass, the
head and the exit gate), seeded random weights under Hugging Face's
names (``benchmark/generate.py`` makes them, the program's own loader
reads them), everything in float32 but where a test says bf16.

Every float32 comparison is float32 against float32 on the same
values, so the tolerances are those of two orders of summation: 2e-5
on hidden states, gate logits, logits and log-probabilities (their
scale is 1: a norm's output, a logit of standard deviation under 3),
1e-5 on the objective (a mean of losses near log 128 = 4.85) and
5e-5 of a gradient's largest entry. The bf16 bound is stated where it
is used.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import generate, reference
from benchmark.families import ouro as family
from realhf_tpu.api.config import ModelName
from realhf_tpu.engine import generation as gen_mod
from realhf_tpu.engine import packing
from realhf_tpu.engine.engine import Engine
from realhf_tpu.engine.inflight import InflightBatchingGenerator
from realhf_tpu.engine.kv_pool import KVPool
from realhf_tpu.interfaces import sft
from realhf_tpu.models import hf as hf_models
from realhf_tpu.models import sharding as shard_rules
from realhf_tpu.models import transformer as T
from realhf_tpu.models.hf import ouro as hf_ouro
from realhf_tpu.models.hf import registry
from realhf_tpu.ops import functional as F
from realhf_tpu.ops.sampling import GenerationHyperparameters
from realhf_tpu.parallel import mesh as mesh_lib

#: max |delta| allowed between the program and the reference on hidden
#: states, gate logits and logits (float32 against float32)
TOL = 2e-5
NAME = "ouro"
PASSES = (4, 1, 3)
_BASE = dict(
    model_type="ouro", vocab_size=128, hidden_size=64,
    intermediate_size=160, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, hidden_act="silu",
    rms_norm_eps=1e-6, rope_theta=1000000, rope_scaling=None,
    sliding_window=None, use_sliding_window=False,
    tie_word_embeddings=False, early_exit_threshold=1.0,
    max_position_embeddings=4096, layer_types=["full_attention"] * 2,
    max_window_layers=2,
    # (0.02 at a hidden size of 64 leaves the gate's logits within
    # 0.2 of 0: every lambda would read 0.5 whatever the gate computes)
    initializer_range=0.1, eos_token_id=1)
#: documents of a packed row of 48: ends at 20, 33, 42, six pads
DOCS_IN_ROW = (20, 13, 9)
ROW = 48


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """T -> a checkpoint the benchmark's generator wrote, read by the
    program's loader (float32 parameters and compute) and, file by
    file, by the reference; each made once a module."""
    made = {}

    def get(passes=4):
        if passes not in made:
            hf = dict(_BASE, total_ut_steps=passes)
            ckpt = str(tmp_path_factory.mktemp(f"ouro-t{passes}"))
            generate.write_checkpoint(ckpt, family, hf, seed=11)
            cfg, params = registry.load_hf_checkpoint(ckpt, NAME)
            cfg.param_dtype = cfg.compute_dtype = "float32"
            params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                                  params)
            made[passes] = dict(
                hf=hf, ckpt=ckpt, cfg=cfg, params=params,
                tensors=reference.load_tensors(ckpt),
                engine=_engine(cfg, params))
        return made[passes]
    return get


def _engine(cfg, params, dp=1, tp=1, **kwargs):
    par = mesh_lib.ParallelismConfig(data_parallel_size=dp,
                                     tensor_parallel_size=tp)
    ctx = mesh_lib.MeshContext(
        ModelName(f"ouro-d{dp}t{tp}", 0),
        mesh_lib.make_mesh(par, jax.devices()[:dp * tp]), par)
    return Engine(cfg, ctx, jax.tree.map(np.asarray, params), **kwargs)


def _packed(seed=4):
    """Documents of 20, 13 and 9 tokens and six pads a row of 48, two
    rows: (ids, seg, [the documents of row 0, of row 1])."""
    rng = np.random.default_rng(seed)
    ids = np.zeros((2, ROW), np.int32)
    seg = np.zeros((2, ROW), np.int32)
    docs = []
    for row in range(2):
        at, mine = 0, []
        for j, n in enumerate(DOCS_IN_ROW):
            mine.append(rng.integers(2, 128, size=(1, n)).astype(np.int32))
            ids[row, at:at + n], seg[row, at:at + n] = mine[-1][0], j + 1
            at += n
        docs.append(mine)
    return ids, seg, docs


def test_the_loader_reads_the_loop_from_the_published_keys(built):
    cfg = built(4)["cfg"]
    assert (cfg.n_passes, cfg.post_norm, cfg.exit_gate, cfg.n_layers,
            cfg.kv_layers, cfg.layer_pattern) == (4, True, True, 2, 8, None)
    assert cfg.exit_entropy_coeff == hf_ouro.ENTROPY_COEFF == family.BETA
    assert (cfg.rotary_base, cfg.layer_norm_epsilon, cfg.tied_embedding,
            cfg.use_attention_bias, cfg.sliding_window) == (
        1e6, 1e-6, False, False, None)
    blocks = built(4)["params"]["blocks"]
    assert set(blocks) == {"ln1", "ln1_post", "attn", "ln2", "ln2_post",
                           "mlp"}
    gate = built(4)["params"]["exit_gate"]
    assert gate["w"].shape == (64, 1) and gate["b"].shape == (1,)
    # a plain dense model has none of it, and says so
    plain = hf_models.config_from_hf("mistral", dict(
        _BASE, model_type="mistral"))
    assert (plain.n_passes, plain.post_norm, plain.exit_gate,
            plain.kv_layers) == (1, False, False, 2)
    assert set(T.init_params(plain, jax.random.PRNGKey(0))["blocks"]) == {
        "ln1", "attn", "ln2", "mlp"}


@pytest.mark.parametrize("passes", PASSES)
def test_every_pass_equals_the_reference_on_packed_rows(built, passes):
    """Two packed rows of three documents and padding: every pass's
    final hidden state and gate logit, and pass T's log-probabilities
    through the engine's own ``logprobs`` program, are what the
    reference gives each document ALONE."""
    model = built(passes)
    ids, seg, docs = _packed()
    states, _ = jax.jit(lambda p: T.forward(
        model["cfg"], p, ids, seg, return_passes=True))(model["params"])
    last, _ = jax.jit(lambda p: T.forward(
        model["cfg"], p, ids, seg))(model["params"])
    assert states.hidden.shape == (passes, 2, ROW, 64)
    assert states.gate.shape == (passes, 2, ROW)
    assert states.gate.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(last),
                                  np.asarray(states.hidden[-1]))
    lp = np.asarray(model["engine"].forward_logprobs(ids, seg))
    for row in range(2):
        at = 0
        for doc in docs[row]:
            n = doc.shape[1]
            hidden, gate = family.passes(model["hf"], model["tensors"], doc)
            assert np.abs(np.asarray(states.hidden[:, row, at:at + n])
                          - hidden[:, 0]).max() < TOL
            assert np.abs(np.asarray(states.gate[:, row, at:at + n])
                          - gate[:, 0]).max() < TOL
            want = family.logprobs(model["hf"], model["tensors"], doc)
            assert np.abs(lp[row, at:at + n - 1] - want[0]).max() < TOL
            assert lp[row, at + n - 1] == 0  # a document's last token
            at += n
    assert np.abs(np.asarray(states.gate)).max() > 0.5  # lambdas differ


def test_the_passes_differ_and_all_carry_weight(built):
    """What the comparison above would not see if the loop were one
    pass run four times over: the passes' states differ from each
    other, and the exit distribution puts mass on every pass."""
    model = built(4)
    ids, seg, _ = _packed()
    states, _ = T.forward(model["cfg"], model["params"], ids, seg,
                          return_passes=True)
    hidden = np.asarray(states.hidden)
    for t in range(3):
        assert np.abs(hidden[t + 1] - hidden[t])[seg != 0].max() > 0.1
    p = np.exp(np.asarray(F.exit_log_distribution(states.gate)))
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    assert (p[:, seg != 0].mean(1) > 0.05).all()
    want = np.asarray(family.exit_distribution(states.gate))
    assert np.abs(p - want).max() < 1e-6
    # no overflow at either end of the gate
    far = jnp.asarray([[-200.0, 200.0]] * 4)
    log_p = np.asarray(F.exit_log_distribution(far))
    assert np.isfinite(log_p[:, 1]).all() and np.isfinite(log_p[-1]).all()
    assert np.exp(log_p[:, 1]).tolist() == [1.0, 0.0, 0.0, 0.0]
    assert np.exp(log_p[-1, 0]) == 1.0


@pytest.mark.parametrize("wrong", family.WRONG)
def test_a_wrong_equation_is_outside_the_tolerance(built, wrong):
    """Each near-miss of the reference's list is told from the right
    model: the three that change the forward by every pass's hidden
    state and pass T's logits (at least 50 tolerances away; the first
    pass alone agrees where the entry is about what a LATER pass starts
    from or attends to), the one that changes the objective alone by
    the loss and by the gate's gradient, which it leaves at zero."""
    model = built(4)
    hf, tensors = model["hf"], model["tensors"]
    docs = _packed()[2][0][0]
    if wrong == "last_pass_loss_alone":
        right = family.objective_and_grad(hf, tensors, docs, 5)
        off = family.objective_and_grad(hf, tensors, docs, 5,
                                        wrong=(wrong,))
        assert abs(right[0] - off[0]) > 1e-3
        gate = "model.early_exit_gate.weight"
        assert np.abs(off[2][gate]).max() == 0
        assert np.abs(right[2][gate]).max() > 1e-4
        return
    hidden, _ = family.passes(hf, tensors, docs)
    off, _ = family.passes(hf, tensors, docs, wrong=(wrong,))
    gaps = np.abs(off - hidden).max((1, 2, 3))
    assert gaps[-1] > 50 * TOL and gaps[1] > 50 * TOL, gaps
    if wrong != "no_post_norms":
        assert gaps[0] == 0, gaps
    logits = family.logits(hf, tensors, docs)
    assert np.abs(family.logits(hf, tensors, docs, wrong=(wrong,))
                  - logits).max() > 50 * TOL


def _objective_case(model, remat, prompt_len=5, dtype=None):
    """The engine's objective and its gradients on ONE row that packs
    two documents of 20 tokens and 8 pads, against ``jax.grad`` of the
    reference on the two documents: ((loss, stats, gradients by HF
    name), the reference's)."""
    docs = np.random.default_rng(9).integers(
        2, 128, size=(2, 20)).astype(np.int32)
    cfg = dataclasses.replace(model["cfg"], gradient_checkpointing=remat)
    params = model["params"]
    if dtype is not None:
        cfg = dataclasses.replace(cfg, param_dtype=dtype,
                                  compute_dtype=dtype)
        params = jax.tree.map(lambda a: a.astype(dtype), params)
    ids = np.zeros((1, ROW), np.int32)
    seg = np.zeros((1, ROW), np.int32)
    prompt = np.zeros((1, ROW), bool)
    for j in range(2):
        ids[0, j * 20:(j + 1) * 20], seg[0, j * 20:(j + 1) * 20] = \
            docs[j], j + 1
        prompt[0, j * 20:j * 20 + prompt_len] = True
    mb = dict(input_ids=jnp.asarray(ids), seg_ids=jnp.asarray(seg),
              prompt_mask=jnp.asarray(prompt))
    loss_fn = sft._make_loss_fn(cfg)
    assert loss_fn.every_pass
    objective = _engine(cfg, params)._objective(loss_fn)
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        objective, has_aux=True))(params, mb)
    got = hf_models.params_to_hf(
        NAME, jax.tree.map(lambda g: np.asarray(g, np.float32), grads), cfg)
    key = ("objective", prompt_len)
    if key not in model:
        model[key] = family.objective_and_grad(
            model["hf"], model["tensors"], docs, prompt_len)
    return (float(loss), {k: float(v) for k, v in stats.items()},
            got), model[key]


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("passes", PASSES)
def test_objective_and_gradients_match_reference(built, remat, passes):
    """sum_t p_t nll_t - beta H(p) and the gradient of EVERY leaf (a
    shared matrix's is the sum of the passes' gradients; the four
    norms, the final norm, the gate's row and bias among them) against
    ``jax.grad`` of the reference's written-out passes, two documents
    and padding a row; rematerialised, as the experiments run it, and
    not; and every statistic the step reports. At T = 1 the gate gets
    no gradient: the one pass takes all the mass."""
    (loss, stats, grads), (ref_loss, parts, ref_grads) = _objective_case(
        built(passes), remat)
    assert abs(loss - ref_loss) < 1e-5
    for t in range(passes):
        assert abs(stats[f"nll_pass{t + 1}"] - parts["nll"][t]) < 1e-5
        assert abs(stats[f"exit_p{t + 1}"] - parts["p"][t]) < 1e-6
    assert abs(stats["nll"] - parts["nll"][-1]) < 1e-5
    assert abs(stats["exit_entropy"] - parts["entropy"]) < 1e-6
    assert abs(stats["expected_exit_pass"]
               - parts["expected_exit_pass"]) < 1e-5
    assert stats["n_tokens"] == 2 * 15
    assert set(grads) == set(ref_grads)
    for name in sorted(grads):
        scale = np.abs(ref_grads[name]).max()
        gap = np.abs(grads[name].reshape(ref_grads[name].shape)
                     - ref_grads[name]).max()
        if passes == 1 and "early_exit_gate" in name:
            assert scale == 0 and gap == 0, name
            continue
        assert scale > 0, name
        assert gap <= 5e-5 * scale + 1e-12, (name, gap, scale)
    if passes == 4:
        assert 1.0 < stats["expected_exit_pass"] < 4.0
        assert stats["exit_entropy"] > 0.5


def _looped_loss_over_every_logprob(cfg):
    """``interfaces/sft.py:_make_looped_loss_fn`` as it stood until PR
    61, kept here as the reference: every pass's log-probabilities by
    ``shifted_logprobs_from_hidden`` (a pass at a time), and the
    objective written over them, so that ``jax.grad`` reaches the head
    through the rematerialised chunks and the gate through ``p``."""
    from realhf_tpu.obs import parts
    beta = cfg.exit_entropy_coeff

    def loss_fn(params, states, mb):
        nll = -jax.lax.map(
            lambda h: F.shifted_logprobs_from_hidden(
                cfg, params, h, mb["input_ids"], mb["seg_ids"]),
            states.hidden)
        log_p = F.exit_log_distribution(states.gate)  # [T, S, L]
        with jax.named_scope(parts.EXIT):
            mask = sft._answer_mask(mb)
            denom = jnp.maximum(mask.sum(), 1)

            def mean(x):  # [..., S, L] -> [...] over the answer tokens
                return (x * mask).sum((-2, -1)) / denom

            p = jnp.exp(log_p)
            entropy = mean(-(p * log_p).sum(0))
            p_mean, nll_mean = mean(p), mean(nll)
            loss = mean((p * nll).sum(0)) - beta * entropy
            passes = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)
            stats = {"nll": nll_mean[-1],
                     "n_tokens": denom.astype(jnp.float32),
                     "expected_exit_pass": (passes * p_mean).sum(),
                     "exit_entropy": entropy}
            for t in range(p.shape[0]):
                stats[f"exit_p{t + 1}"] = p_mean[t]
                stats[f"nll_pass{t + 1}"] = nll_mean[t]
        return loss, stats

    loss_fn.every_pass = True
    return loss_fn


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_heads_own_gradient_leaves_the_objective_as_it_was(
        built, monkeypatch, dtype):
    """The looped objective over ``weighted_logprob_sum`` (every pass's
    head computes its gradient where it has the logits, the gate is
    reached through the weights ``p mask / denom``) against the
    objective over every pass's log-probabilities, at T = 4,
    rematerialised: the loss, every statistic and the gradient of every
    leaf, the head's, the layers' and the exit gate's among them. In
    float32 to 2e-6 of a tensor's largest entry (read: 6e-7); in bf16
    both are one program's roundings in two orders, a norm apart of
    under 1% a tensor (read: 0.36%; the reference's float32 gradient is
    1 to 2% from either: ``BF16_GRADIENT_BOUND``)."""
    model = built(4)
    got, _ = _objective_case(model, True, dtype=dtype)
    monkeypatch.setattr(sft, "_make_loss_fn",
                        _looped_loss_over_every_logprob)
    want, _ = _objective_case(model, True, dtype=dtype)
    (loss, stats, grads), (loss0, stats0, grads0) = got, want
    exact = dtype == "float32"
    # (the head's sums run in float32 in either dtype)
    assert abs(loss - loss0) < 2e-6 * abs(loss0)
    assert set(stats) == set(stats0) and len(stats) == 4 + 2 * 4
    for name in stats0:
        assert abs(stats[name] - stats0[name]) \
            <= 2e-6 * abs(stats0[name]), name
    assert set(grads) == set(grads0)
    assert {"lm_head.weight", "model.early_exit_gate.weight",
            "model.early_exit_gate.bias",
            "model.layers.0.mlp.down_proj.weight"} <= set(grads)
    for name in sorted(grads0):
        assert np.abs(grads0[name]).max() > 0, name
        if exact:
            assert np.abs(grads[name] - grads0[name]).max() \
                <= 2e-6 * np.abs(grads0[name]).max(), name
        else:
            assert np.linalg.norm(grads[name] - grads0[name]) \
                <= 0.01 * np.linalg.norm(grads0[name]), name


#: How far a bf16 engine's gradient may be from the float32 reference's,
#: as ``|g - ref|_2 / |ref|_2`` over a tensor. A shared weight's
#: gradient is the SUM of four passes' gradients, which the carried
#: accumulator takes a pass at a time in the parameters' dtype
#: (``models/transformer.py:_passes``, ``_layer_of``): three more
#: roundings of 2^-9 of the running sum, on top of what a bf16 forward
#: and backward lose anyway. Read here (seed 11, hidden 64): 0.0109 to
#: 0.0203 on the layers' matrices at T = 4 beside 0.0107 to 0.0185 at
#: T = 1, 0.0067 to 0.0162 on their norms (0.0066 to 0.0152), 0.0116 on
#: the gate's row, 0.0087 on the head, 0.0122 on the embedding: the sum
#: of four costs a tenth more than one pass's own bf16 error. 0.05 is
#: two and a half times the worst reading, and the three-pass
#: objective's gradient is 0.098 away (the test after this one).
BF16_GRADIENT_BOUND = 0.05


@pytest.mark.parametrize("passes", [4, 1])
def test_bf16_gradients_stay_within_a_bound_of_float32(built, passes):
    (_, stats, grads), (_, _, ref_grads) = _objective_case(
        built(passes), True, dtype="bfloat16")
    worst = {}
    for name, ref in ref_grads.items():
        if not np.abs(ref).max():
            continue
        got = grads[name].reshape(ref.shape)
        worst[name] = float(np.linalg.norm(got - ref)
                            / np.linalg.norm(ref))
    shared = [n for n in worst if ".layers." in n]
    assert len(shared) == 2 * 11
    assert max(worst.values()) < BF16_GRADIENT_BOUND, sorted(
        worst.items(), key=lambda x: -x[1])[:3]
    assert np.isfinite(stats["expected_exit_pass"])


def test_a_sum_that_lost_a_pass_is_outside_the_bound(built):
    """The bound above means something: the same weights' gradient
    under the THREE-pass objective on the same tensors (what the sum
    comes to when the fourth pass's term is lost and the third takes
    what is left of the exit mass) is two bounds away from the
    four-pass gradient, in float32."""
    model = built(4)
    (_, _, _), (_, _, ref_grads) = _objective_case(model, False)
    docs = np.random.default_rng(9).integers(
        2, 128, size=(2, 20)).astype(np.int32)
    three = family.objective_and_grad(
        dict(model["hf"], total_ut_steps=3), model["tensors"], docs, 5)[2]
    name = "model.layers.0.self_attn.q_proj.weight"
    gap = np.linalg.norm(three[name] - ref_grads[name]) \
        / np.linalg.norm(ref_grads[name])
    assert gap > 1.9 * BF16_GRADIENT_BOUND, gap


@pytest.mark.parametrize("n_pre", [20, 1])
@pytest.mark.parametrize("passes", [4, 3])
def test_prefill_then_decode_matches_full_forward(built, passes, n_pre):
    """``engine/generation.py``'s two steps, teacher-forced, through a
    cache T x N layers deep: pass t layer l reads and writes row t N +
    l, the final norm runs after every pass, and the logits are those
    of the reference's full forward."""
    model = built(passes)
    cfg, params = model["cfg"], model["params"]
    docs = np.random.default_rng(3).integers(
        2, 128, size=(2, 40)).astype(np.int32)
    want = family.logits(model["hf"], model["tensors"], docs)
    total = 40
    ids = jnp.asarray(docs[:, :n_pre])
    hidden, cache = jax.jit(lambda p, i: T.prefill(
        cfg, p, i, jnp.ones_like(i), total_len=total))(params, ids)
    step = jax.jit(lambda p, c, t, pos: T.decode_step(
        cfg, p, c, t, pos, uniform_slot=True))
    assert cache["k"].shape == cache["v"].shape \
        == (passes * 2, 2, 4, total, 16)
    empty = T.init_kv_cache(cfg, 2, total)
    assert {k: (v.shape, v.dtype) for k, v in empty.items()} == \
        {k: (v.shape, v.dtype) for k, v in cache.items()}
    # every pass wrote keys of its own
    k = np.asarray(cache["k"])[:, :, :, :n_pre]
    for t in range(1, passes):
        assert np.abs(k[2 * t:2 * t + 2] - k[:2]).max() > 1e-3
    got = [np.asarray(T.lm_logits(cfg, params, hidden))]
    for t in range(n_pre, total):
        h, cache = step(params, cache, jnp.asarray(docs[:, t]),
                        jnp.full((2,), t, jnp.int32))
        got.append(np.asarray(T.lm_logits(cfg, params, h))[:, None])
    got = np.concatenate(got, axis=1)
    assert np.abs(got - want).max() < TOL


def _greedy():
    return GenerationHyperparameters(max_new_tokens=8, min_new_tokens=1,
                                     greedy=True, force_no_logits_mask=True)


def _continuations_by_the_reference(model, prompts, outs):
    """Each finished request's tokens are the reference's greedy
    continuation and its log-probabilities the reference's, from the
    full forward over prompt and continuation."""
    for prompt, (tokens, logprobs) in zip(prompts, outs):
        whole = np.concatenate([prompt, tokens])[None]
        logits = family.logits(model["hf"], model["tensors"], whole)[0]
        lp = np.asarray(jax.nn.log_softmax(logits, -1))
        at = len(prompt) - 1 + np.arange(len(tokens))
        np.testing.assert_array_equal(logits[at].argmax(-1), tokens)
        np.testing.assert_allclose(lp[at, tokens], logprobs, atol=5e-5)


def test_generate_continues_as_the_reference_does(built):
    """The program's own ``generate`` (left-padded prompts of unequal
    lengths, the decode loop through the T x N-deep cache): greedy
    tokens and their log-probabilities are the reference's."""
    model = built(4)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, 128, size=n).astype(np.int32)
               for n in (11, 4, 7)]
    ids, seg, pos = packing.left_padded_prompts(prompts, pad_id=0)
    out = gen_mod.generate(
        model["cfg"], model["params"], jnp.asarray(ids), jnp.asarray(seg),
        jnp.asarray(pos), jax.random.PRNGKey(0), _greedy(),
        eos_token_id=None, pad_token_id=0)
    _continuations_by_the_reference(model, prompts, [
        (np.asarray(out.tokens[i]), np.asarray(out.logprobs[i]))
        for i in range(3)])


@pytest.mark.parametrize("pool", [False, True], ids=["dense", "paged"])
def test_the_slot_engine_continues_as_the_reference_does(built, pool):
    """``engine/inflight.py`` (five requests through two slots, so
    slots refill) with its dense windows and with ``engine/kv_pool.py``'s
    paged pool, both T x N layers deep."""
    model = built(4)
    cfg = model["cfg"]
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2, 128, size=n).astype(np.int32)
               for n in (9, 16, 5, 12, 3)]
    kv_pool = KVPool(cfg, n_blocks=16, block_len=8, dtype="fp32") \
        if pool else None
    if pool:
        assert kv_pool.arrays()["k"].shape[0] == cfg.kv_layers == 8
    g = InflightBatchingGenerator(
        cfg, model["params"], _greedy(), n_slots=2, max_prompt_len=24,
        eos_token_id=None, pad_token_id=0, chunk_size=4, kv_pool=kv_pool)
    if not pool:
        assert g.state["cache"]["k"].shape[0] == 8
    got = g.generate_all(prompts, jax.random.PRNGKey(7))
    _continuations_by_the_reference(
        model, prompts, [(fs.tokens, fs.logprobs) for fs in got])


def test_a_cached_prefix_extends_through_every_pass(built):
    """The slot engine's partial prefill (``_extend_rows``: a donor's
    rows of all T x N cache layers, then the suffix through every pass
    and the final norm between passes) decodes as a fill without a
    cached prefix does."""
    model = built(4)
    cfg = model["cfg"]
    rng = np.random.default_rng(5)
    prompt = rng.integers(2, 128, size=20).astype(np.int32)

    def gen():
        return InflightBatchingGenerator(
            cfg, model["params"], _greedy(), n_slots=1, max_prompt_len=40,
            eos_token_id=None, pad_token_id=0, chunk_size=4)

    plain = gen()
    want = plain.generate_all([prompt], jax.random.PRNGKey(0))[0]
    # the donor: the same prompt's own prefill, its first 16 rows
    donor = gen()
    donor.fill_slot(0, 0, prompt)
    cache = donor.state["cache"]
    lp = int(cache["length"][0])
    rows = slice(lp - 20, lp - 4)  # left padding: tokens 0..15
    prefix = (np.asarray(cache["k"][:, 0, :, rows]),
              np.asarray(cache["v"][:, 0, :, rows]))
    assert prefix[0].shape == (8, 4, 16, 16)
    g = gen()
    g.fill_slot(0, 0, prompt, cached_len=16, prefix_kv=prefix)
    assert g.last_fill["cached_len"] == 16
    for _ in range(2):
        g.decode_chunk(jax.random.PRNGKey(0))
    got = g.harvest()[0]
    np.testing.assert_array_equal(want.tokens, got.tokens)
    np.testing.assert_allclose(want.logprobs, got.logprobs, atol=5e-5)


def test_hf_round_trip(built, tmp_path):
    """The program's tree -> HF's names -> the program's tree, and the
    config both ways, bit for bit; a saved checkpoint loads, eagerly
    and streamed a layer at a time."""
    model = built(4)
    cfg, params = model["cfg"], jax.tree.map(np.asarray, model["params"])
    state = hf_models.params_to_hf(NAME, params, cfg)
    assert set(state) == set(model["tensors"]) == set(
        n.format(i) for n in family.shapes(model["hf"]) for i in range(2))
    assert state["model.early_exit_gate.weight"].shape == (1, 64)
    back = hf_models.params_from_hf(NAME, state, cfg)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(a, b)
    again = hf_models.config_from_hf(
        NAME, hf_models.config_to_hf(NAME, cfg))
    assert dataclasses.replace(
        again, param_dtype="float32", compute_dtype="float32") == cfg
    out = str(tmp_path / "saved")
    registry.save_hf_checkpoint(out, NAME, cfg, params)
    cfg2, params2 = registry.load_hf_checkpoint(out, NAME)
    assert (cfg2.n_passes, cfg2.post_norm, cfg2.exit_gate) == (4, True,
                                                               True)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
        np.testing.assert_array_equal(a, np.asarray(b, a.dtype))
    streamed = str(tmp_path / "streamed")
    registry.save_hf_checkpoint_streamed(streamed, NAME, cfg,
                                         model["params"])
    mesh = _engine(cfg, params).mesh
    cfg3, params3 = registry.load_hf_checkpoint_streamed(
        streamed, mesh, NAME, param_dtype="float32")
    assert jax.tree.structure(params3) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params3)):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_partition_specs_on_a_d2t2_mesh(built):
    """The four norms a layer and the gate on every shard, the
    projections by head and the feed-forward by column and row as any
    dense model's; on a mesh of 2 x 2 CPU devices the forward and the
    looped objective's gradient are one device's."""
    from jax.sharding import PartitionSpec as P
    model = built(4)
    cfg = model["cfg"]
    specs = shard_rules.param_pspecs(cfg)
    assert jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, P)) == jax.tree.structure(
        model["params"])
    blocks = specs["blocks"]
    for name in ("ln1", "ln1_post", "ln2", "ln2_post"):
        assert blocks[name]["scale"] == P(None, None)
    assert specs["exit_gate"] == {"w": P(None, None), "b": P(None)}
    assert blocks["attn"]["wq"] == P(None, None, "model")
    assert blocks["mlp"]["wd"] == P(None, "model", None)
    ids, seg, _ = _packed()
    sharded = _engine(cfg, model["params"], dp=2, tp=2)
    got = np.asarray(sharded.forward_logprobs(ids, seg))
    want = np.asarray(model["engine"].forward_logprobs(ids, seg))
    assert np.abs(got - want).max() < TOL
    mb = dict(input_ids=jnp.asarray(ids), seg_ids=jnp.asarray(seg),
              prompt_mask=jnp.asarray(seg == 0))

    def grad_of(engine):
        objective = engine._objective(sft._make_loss_fn(cfg))
        (loss, _), grads = jax.jit(jax.value_and_grad(
            objective, has_aux=True))(engine.params, mb)
        return float(loss), jax.tree.map(np.asarray, grads)

    (loss, grads), (loss1, grads1) = grad_of(sharded), grad_of(
        model["engine"])
    assert abs(loss - loss1) < 1e-5
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads1)):
        assert np.abs(a - b).max() <= 5e-5 * np.abs(b).max() + 1e-9


def test_a_critic_reads_the_last_pass(built):
    """A reward or value model of the family: the scalar head on pass
    T's final hidden state, through the engine's ``values`` program."""
    model = built(4)
    cfg = hf_models.config_from_hf(NAME, model["hf"], is_critic=True)
    cfg.param_dtype = cfg.compute_dtype = "float32"
    assert (cfg.is_critic, cfg.n_passes, cfg.kv_layers) == (True, 4, 8)
    head = np.random.default_rng(0).normal(size=(64, 1)).astype(np.float32)
    params = {**jax.tree.map(np.asarray, model["params"]),
              "head": {"w": head}}
    ids, seg, docs = _packed()
    got = np.asarray(_engine(cfg, params).forward_values(ids, seg))
    doc = docs[0][0]
    hidden, _ = family.passes(model["hf"], model["tensors"], doc)
    assert np.abs(got[0, :doc.shape[1]] - (hidden[-1, 0] @ head)[:, 0]
                  ).max() < 1e-4


@pytest.mark.parametrize("key,value", [("early_exit_threshold", 0.9),
                                       ("early_exit_threshold", 0.5)])
def test_leaving_the_loop_early_is_refused_by_name(key, value):
    with pytest.raises(NotImplementedError, match="early_exit_threshold"):
        hf_models.config_from_hf(NAME, dict(_BASE, total_ut_steps=4,
                                            **{key: value}))
    with pytest.raises(NotImplementedError, match="early_exit_threshold"):
        family.dims(dict(_BASE, total_ut_steps=4, **{key: value}))


@pytest.mark.parametrize("what", ["pipeline", "pattern",
                                  "passes_without_a_gate"])
def test_what_does_not_run_a_loop_is_refused_by_name(built, what):
    model = built(4)
    cfg = model["cfg"]
    if what == "pipeline":
        from realhf_tpu.parallel.pipeline import PipelineContext
        ctx = PipelineContext(mesh=None, n_stages=2, n_microbatches=2)
        ids, seg, _ = _packed()
        with pytest.raises(NotImplementedError, match="looped"):
            T.forward(cfg, model["params"], ids, seg, pipeline=ctx)
    elif what == "pattern":
        with pytest.raises(NotImplementedError, match="one dense block"):
            dataclasses.replace(
                cfg, layer_pattern=(("attention", "dense"),) * 2)
    else:
        plain = dataclasses.replace(cfg, exit_gate=False)
        ids, seg, _ = _packed()
        with pytest.raises(ValueError, match="no exit gate"):
            T.forward(plain, model["params"], ids, seg, return_passes=True)
