"""Kernel-level tests mirroring reference ``tests/cpp_extensions/
test_cugae.py`` (GAE vs naive python) plus sampling warpers, masked
normalization, and fused shifted-logprob checks."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.ops import functional as F
from realhf_tpu.ops.gae import gae_packed_numpy, gae_padded
from realhf_tpu.ops.sampling import top_k_top_p_logits


def naive_gae_1d(rewards, values, cu_seqlens, bootstrap, gamma, lam):
    """Direct port of the reference python fallback semantics
    (ppo_functional.pygae1d_nolp_misalign:337) as the test oracle."""
    bs = len(cu_seqlens) - 1
    adv_all, ret_all = [], []
    v_off = 0
    for i in range(bs):
        r = rewards[cu_seqlens[i]:cu_seqlens[i + 1]]
        l = len(r)
        v = values[v_off:v_off + l + 1]
        v_off += l + 1
        adv = np.zeros(l)
        lastgaelam = 0.0
        for t in reversed(range(l)):
            nextv = v[t + 1]
            if t == l - 1:
                nextv *= bootstrap[i]
            delta = r[t] + gamma * nextv - v[t]
            lastgaelam = delta + gamma * lam * lastgaelam
            adv[t] = lastgaelam
        adv_all.append(adv)
        ret_all.append(adv + v[:l])
    return np.concatenate(adv_all), np.concatenate(ret_all)


class TestGAE:

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_matches_naive(self, seed):
        rng = np.random.default_rng(seed)
        lens = rng.integers(1, 30, size=(9,))
        cu = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
        rewards = rng.standard_normal(cu[-1]).astype(np.float32)
        values = rng.standard_normal(cu[-1] + len(lens)).astype(np.float32)
        bootstrap = rng.integers(0, 2, size=(len(lens),)).astype(np.float32)
        adv, ret = gae_packed_numpy(rewards, values, cu, bootstrap,
                                    gamma=0.99, lam=0.95)
        adv_ref, ret_ref = naive_gae_1d(rewards, values, cu, bootstrap,
                                        0.99, 0.95)
        np.testing.assert_allclose(adv, adv_ref, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(ret, ret_ref, rtol=1e-4, atol=1e-5)

    def test_padded_masks_tail(self):
        rewards = jnp.ones((2, 8))
        values = jnp.ones((2, 9))
        lengths = jnp.array([3, 8], jnp.int32)
        adv, ret = gae_padded(rewards, values, lengths,
                              jnp.array([0.0, 1.0]), 1.0, 1.0)
        assert (np.asarray(adv)[0, 3:] == 0).all()
        assert (np.asarray(ret)[0, 3:] == 0).all()


class TestSampling:

    def test_top_k(self):
        logits = jnp.asarray(np.random.default_rng(0).standard_normal((4, 50)))
        out = np.asarray(top_k_top_p_logits(logits, top_k=5))
        assert ((out > -1e29).sum(-1) == 5).all()
        # surviving entries are the top-5
        ref = np.asarray(logits)
        for b in range(4):
            top5 = set(np.argsort(ref[b])[-5:])
            assert set(np.where(out[b] > -1e29)[0]) == top5

    def test_top_p(self):
        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.standard_normal((8, 100)) * 3)
        out = np.asarray(top_k_top_p_logits(logits, top_p=0.9))
        probs = np.asarray(jax.nn.softmax(logits, -1))
        for b in range(8):
            kept = out[b] > -1e29
            assert kept.sum() >= 1
            # kept mass >= 0.9; dropping the smallest kept token goes below
            assert probs[b][kept].sum() >= 0.9 - 1e-5
            if kept.sum() > 1:
                smallest = probs[b][kept].min()
                assert probs[b][kept].sum() - smallest < 0.9 + 1e-5

    def test_noop(self):
        logits = jnp.asarray(np.random.default_rng(2).standard_normal((2, 10)))
        np.testing.assert_array_equal(
            np.asarray(top_k_top_p_logits(logits, top_k=0, top_p=1.0)),
            np.asarray(logits))

    def test_top_k_top_p_unioned(self):
        # Combined top-k+top-p must use UNIONED semantics (reference
        # real_llm_generate.py:82-87, ordered=False): the nucleus is
        # computed over the FULL distribution, then intersected with
        # the top-k set -- NOT renormalized within the k survivors.
        rng = np.random.default_rng(3)
        logits = jnp.asarray(rng.standard_normal((16, 100)) * 3)
        for k, p in [(5, 0.9), (50, 0.5), (20, 0.99), (3, 0.2)]:
            both = np.asarray(top_k_top_p_logits(logits, top_k=k,
                                                 top_p=p)) > -1e29
            only_k = np.asarray(top_k_top_p_logits(logits,
                                                   top_k=k)) > -1e29
            only_p = np.asarray(top_k_top_p_logits(logits,
                                                   top_p=p)) > -1e29
            expect = only_k & only_p
            # at least one token always survives
            expect |= ~expect.any(-1, keepdims=True) & only_k \
                & (np.asarray(logits) == np.asarray(logits).max(
                    -1, keepdims=True))
            np.testing.assert_array_equal(both, expect,
                                          err_msg=f"k={k} p={p}")


class TestFunctional:

    def test_masked_normalization(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 16)).astype(np.float32) * 5 + 2)
        mask = jnp.asarray(rng.integers(0, 2, size=(4, 16)).astype(np.float32))
        out = np.asarray(F.masked_normalization(x, mask))
        sel = out[np.asarray(mask) > 0]
        assert abs(sel.mean()) < 1e-4
        assert abs(sel.std() - 1) < 1e-2
        assert (out[np.asarray(mask) == 0] == 0).all()

    def test_shifted_logprobs_match_naive(self):
        cfg = TransformerConfig(
            n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
            intermediate_dim=64, vocab_size=50, apply_rotary=True,
            layer_norm_type="rms", mlp_type="llama",
            use_attention_bias=False, use_attn_proj_bias=False,
            use_mlp_bias=False, activation_function="silu",
            compute_dtype="float32")
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(0, 50, size=(2, 24)), jnp.int32)
        seg = jnp.asarray(np.concatenate(
            [np.full((2, 10), 1), np.full((2, 10), 2), np.zeros((2, 4))],
            axis=1), jnp.int32)
        h, _ = T.forward(cfg, params, ids, seg)
        lp = np.asarray(F.shifted_logprobs_from_hidden(
            cfg, params, h, ids, seg, chunk=8))
        logits = np.asarray(T.lm_logits(cfg, params, h))
        naive = jax.nn.log_softmax(jnp.asarray(logits), -1)
        naive = np.asarray(naive)
        for b in range(2):
            for t in range(23):
                same_seg = (np.asarray(seg)[b, t + 1] == np.asarray(seg)[b, t]
                            and np.asarray(seg)[b, t + 1] != 0)
                if same_seg:
                    expect = naive[b, t, np.asarray(ids)[b, t + 1]]
                    np.testing.assert_allclose(lp[b, t], expect, rtol=1e-4,
                                               atol=1e-5)
                else:
                    assert lp[b, t] == 0.0
        # boundary between segment 1 and 2 and at padding must be zero
        assert lp[0, 9] == 0.0 and lp[0, 19] == 0.0

    def test_entropy(self):
        cfg = TransformerConfig(
            n_layers=1, n_kv_heads=2, n_q_heads=2, hidden_dim=16,
            intermediate_dim=32, vocab_size=30, apply_rotary=True,
            layer_norm_type="rms", mlp_type="llama",
            use_attention_bias=False, use_attn_proj_bias=False,
            use_mlp_bias=False, activation_function="silu",
            compute_dtype="float32")
        params = T.init_params(cfg, jax.random.PRNGKey(1))
        ids = jnp.ones((1, 8), jnp.int32)
        h, _ = T.forward(cfg, params, ids, jnp.ones_like(ids))
        ent = np.asarray(F.entropy_from_hidden(cfg, params, h, chunk=4))
        assert ent.shape == (1, 8)
        assert (ent > 0).all() and (ent <= np.log(30) + 1e-5).all()


# ----------------------------------------------------------------------
# The vocabulary head picks the label by a select (PR 32): held to the
# formulation it replaced, written out here on whole logits
# ----------------------------------------------------------------------
HEAD_V, HEAD_H, HEAD_L = 50, 32, 21   # 21: no chunk of 8 divides the row


def _gather_logprobs(cfg, params, hidden, ids, seg, temperature, logits_mask):
    """``log_softmax`` + ``take_along_axis`` on the row's whole logits:
    what ``shifted_logprobs_from_hidden`` computed before PR 32."""
    w = T.head_weight(cfg, params).astype(hidden.dtype)
    logits = jnp.einsum("slh,hv->slv", hidden, w,
                        preferred_element_type=jnp.float32)
    logits = logits[..., :cfg.vocab_size]
    if temperature != 1.0:
        logits = logits / temperature
    if logits_mask is not None:
        logits = jnp.where(logits_mask, logits, -1e30)
    logp = jax.nn.log_softmax(logits, axis=-1)
    labels = jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], 1)
    lp = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    valid = jnp.concatenate(
        [(seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] != 0),
         jnp.zeros_like(seg[:, :1], bool)], axis=1)
    return jnp.where(valid, lp, 0.0)


def _head_case(tied, padded, dtype, masked):
    cfg = TransformerConfig(
        n_layers=1, n_kv_heads=2, n_q_heads=4, hidden_dim=HEAD_H,
        intermediate_dim=64, vocab_size=HEAD_V, apply_rotary=True,
        layer_norm_type="rms", mlp_type="llama", use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu", tied_embedding=tied,
        compute_dtype=dtype, param_dtype=dtype)
    rng = np.random.default_rng(11)
    vp = HEAD_V + 6 if padded else HEAD_V   # a tp-padded vocabulary
    mat = jnp.asarray(rng.standard_normal((vp, HEAD_H)) * 0.3, dtype)
    params = ({"embed": {"wte": mat}} if tied
              else {"embed": {"wte": mat}, "head": {"w": mat.T}})
    hidden = jnp.asarray(rng.standard_normal((2, HEAD_L, HEAD_H)), dtype)
    ids = jnp.asarray(rng.integers(0, HEAD_V, (2, HEAD_L)), jnp.int32)
    seg = jnp.asarray(np.concatenate(
        [np.full((2, 9), 1), np.full((2, 10), 2), np.zeros((2, 2))], 1),
        jnp.int32)
    mask = None
    if masked:
        allowed = rng.random((2, HEAD_L, HEAD_V)) > 0.3
        allowed[0, 3, int(ids[0, 4])] = False   # a masked LABEL
        allowed[1, 12, int(ids[1, 13])] = False
        mask = jnp.asarray(allowed)
    return cfg, params, hidden, ids, seg, mask


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("padded", [False, True], ids=["exact", "tp_padded"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("temperature", [1.0, 0.7])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_head_select_matches_gather(tied, temperature, masked, padded, dtype):
    """Values, and the gradients with respect to hidden states and head
    weight, of the chunked select against the gather on whole logits:
    the same operations in the same order, so equal in float32 up to
    the products' own rounding; in bf16 the chunks round the weight's
    gradient once a chunk where the whole row rounds it once."""
    cfg, params, hidden, ids, seg, mask = _head_case(
        tied, padded, dtype, masked)
    weight = np.asarray(np.random.default_rng(5).standard_normal(
        (2, HEAD_L)), np.float32)

    def run(fn, **kw):
        def loss(params, hidden):
            lp = fn(cfg, params, hidden, ids, seg, **kw)
            return (lp * weight).sum(), lp
        (_, lp), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, hidden)
        return lp, grads

    lp, (gp, gh) = run(F.shifted_logprobs_from_hidden, chunk=8,
                       temperature=temperature, logits_mask=mask)
    lp0, (gp0, gh0) = run(_gather_logprobs, temperature=temperature,
                          logits_mask=mask)
    assert lp.dtype == jnp.float32 and lp.shape == (2, HEAD_L)
    np.testing.assert_allclose(lp, lp0, rtol=1e-6, atol=1e-6)
    if masked:  # a masked label reads the mask's -1e30, as before
        assert lp[0, 3] < -1e29 and lp[1, 12] < -1e29
    tol = dict(rtol=1e-5, atol=1e-6) if dtype == "float32" \
        else dict(rtol=2e-2, atol=2e-2)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    np.testing.assert_allclose(f32(gh), f32(gh0), **tol)
    head = (lambda p: p["embed"]["wte"]) if tied else (lambda p: p["head"]["w"])
    gw = head(gp)
    assert gw.shape == head(params).shape
    np.testing.assert_allclose(f32(gw), f32(head(gp0)), **tol)
    if padded:  # the padded columns take no gradient
        pad = f32(gw)[HEAD_V:] if tied else f32(gw)[:, HEAD_V:]
        assert (pad == 0).all()


def test_head_select_traces_no_gather_or_scatter():
    """The transposed program holds no gather and no scatter: the
    label's cotangent is an elementwise select."""
    cfg, params, hidden, ids, seg, _ = _head_case(True, False, "float32",
                                                  False)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, h: F.shifted_logprobs_from_hidden(
            cfg, p, h, ids, seg, chunk=8).sum(), argnums=(0, 1)))(
                params, hidden)
    text = str(jaxpr)
    assert "gather" not in text and "scatter" not in text
    assert "select_n" in text


# ----------------------------------------------------------------------
# The head of a loss that IS a weighted sum of log-probabilities
# computes its gradient where it has the logits: held to jax.grad of
# sum(c * shifted_logprobs_from_hidden(...))
# ----------------------------------------------------------------------
def _weighted_case(tied, padded, dtype, passes):
    """``_head_case`` on a packed row with three segment boundaries and
    a padded tail (no chunk of 8 divides its 21 tokens), weights of
    both signs, and ``passes`` states a token where a number is
    given."""
    cfg, params, hidden, ids, _, _ = _head_case(tied, padded, dtype, False)
    seg = jnp.asarray(np.concatenate(
        [np.full((2, n), d) for d, n in ((1, 5), (2, 5), (3, 4), (4, 4),
                                         (0, 3))], 1), jnp.int32)
    rng = np.random.default_rng(7)
    lead = () if passes is None else (passes,)
    hidden = jnp.asarray(rng.standard_normal(lead + hidden.shape), dtype)
    c = jnp.asarray(rng.standard_normal(lead + ids.shape), jnp.float32)
    return cfg, params, hidden, ids, seg, c


@pytest.mark.parametrize("cotangent", [1.0, -0.37])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("padded", [False, True], ids=["exact", "tp_padded"])
@pytest.mark.parametrize("passes", [None, 3], ids=["one_pass", "passes"])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_weighted_logprob_sum_matches_grad_of_the_logprobs(
        tied, passes, padded, dtype, cotangent):
    """Value, ``lp`` and the gradients with respect to the head's
    weight, the states and the weights ``c`` against ``jax.grad`` of
    ``sum(c * shifted_logprobs_from_hidden(...))`` (a pass at a time
    where there are several), under an incoming cotangent of 1 and of
    another number; and the primal, called without a gradient. In
    float32 to 1e-6 of a tensor's largest entry; in bf16 the tolerance
    of ``test_head_select_matches_gather``."""
    cfg, params, hidden, ids, seg, c = _weighted_case(
        tied, padded, dtype, passes)

    def reference(params, hidden, c):
        one = lambda h: F.shifted_logprobs_from_hidden(
            cfg, params, h, ids, seg, chunk=8)
        lp = one(hidden) if passes is None else jax.lax.map(one, hidden)
        return cotangent * (c * lp).sum(), lp

    def weighted(params, hidden, c):
        total, lp = F.weighted_logprob_sum(cfg, params, hidden, ids, seg,
                                           c, chunk=8)
        return cotangent * total, lp

    def run(fn):
        return jax.jit(jax.value_and_grad(
            fn, argnums=(0, 1, 2), has_aux=True))(params, hidden, c)

    (value, lp), (gp, gh, gc) = run(weighted)
    (value0, lp0), (gp0, gh0, gc0) = run(reference)
    assert lp.dtype == jnp.float32 and lp.shape == c.shape
    assert gh.dtype == hidden.dtype and gc.dtype == jnp.float32
    f32 = lambda a: np.asarray(a.astype(jnp.float32))

    def close(got, want, exact=False):
        got, want = f32(got), f32(want)
        assert got.shape == want.shape
        if dtype == "float32" or exact:
            np.testing.assert_allclose(
                got, want, rtol=0, atol=1e-6 * np.abs(want).max())
        else:
            np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)

    close(lp, lp0, exact=True)
    assert (f32(lp)[..., np.asarray(seg) == 0] == 0).all()
    assert (f32(lp)[..., [4, 9, 13, 17]] == 0).all()  # a document's last
    close(value, value0, exact=True)
    close(gc, gc0, exact=True)   # lp itself, times the cotangent
    close(gh, gh0)
    head = (lambda p: p["embed"]["wte"]) if tied else (lambda p: p["head"]["w"])
    gw = head(gp)
    assert gw.dtype == head(params).dtype
    close(gw, head(gp0))
    if padded:  # the padded columns take no gradient
        pad = f32(gw)[HEAD_V:] if tied else f32(gw)[:, HEAD_V:]
        assert (pad == 0).all()
    # nobody differentiates this call: today's forward
    total, lp1 = jax.jit(lambda: F.weighted_logprob_sum(
        cfg, params, hidden, ids, seg, c, chunk=8))()
    close(cotangent * total, value0, exact=True)
    close(lp1, lp0, exact=True)


def test_weighted_logprob_sum_runs_three_products_a_chunk():
    """The differentiated program holds three matrix products, all in
    the ONE scan over the chunks, and neither a gather nor a scatter
    (the label's entry and its cotangent are selects); ``jax.grad`` of
    the log-probabilities holds four (forward, the rematerialised
    forward, two transposed); the call nobody differentiates holds the
    forward's alone; and ``lp`` carries no gradient."""
    cfg, params, hidden, ids, seg, c = _weighted_case(
        True, False, "float32", None)

    def weighted(p, h):
        return F.weighted_logprob_sum(cfg, p, h, ids, seg, c, chunk=8)

    grad = str(jax.make_jaxpr(jax.grad(
        lambda p, h: weighted(p, h)[0], argnums=(0, 1)))(params, hidden))
    assert "gather" not in grad and "scatter" not in grad
    assert "select_n" in grad
    assert grad.count("dot_general") == 3 and grad.count("scan[") == 1
    old = str(jax.make_jaxpr(jax.grad(
        lambda p, h: (c * F.shifted_logprobs_from_hidden(
            cfg, p, h, ids, seg, chunk=8)).sum(), argnums=(0, 1)))(
                params, hidden))
    assert old.count("dot_general") == 4
    primal = str(jax.make_jaxpr(weighted)(params, hidden))
    assert primal.count("dot_general") == 1
    through_lp = jax.grad(lambda h: weighted(params, h)[1].sum())(hidden)
    assert not np.asarray(through_lp).any()


def test_weighted_logprob_sum_over_a_sharded_vocabulary():
    """On a mesh of two devices with the head's vocabulary axis sharded
    (and tp-padded): value, ``lp`` and gradients as on one device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg, params, hidden, ids, seg, c = _weighted_case(
        False, True, "float32", None)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    sharded = dict(params, head={"w": jax.device_put(
        params["head"]["w"], NamedSharding(mesh, P(None, "model")))})

    def run(params):
        def loss(p, h, c):
            return F.weighted_logprob_sum(cfg, p, h, ids, seg, c, chunk=8)
        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(params, hidden, c)

    (value, lp), grads = run(sharded)
    (value0, lp0), grads0 = run(params)
    assert len(grads[0]["head"]["w"].sharding.device_set) == 2
    np.testing.assert_allclose(value, value0, rtol=1e-6)
    np.testing.assert_allclose(lp, lp0, rtol=1e-6, atol=1e-6)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(grads0)):
        np.testing.assert_allclose(
            got, want, rtol=0, atol=1e-6 * np.abs(want).max() + 1e-12)


def test_entropy_matches_log_softmax_form():
    cfg, params, hidden, *_ = _head_case(True, True, "float32", False)
    ent = F.entropy_from_hidden(cfg, params, hidden, chunk=8,
                                temperature=0.7)
    logits = T.lm_logits(cfg, params, hidden) / 0.7
    logp = jax.nn.log_softmax(logits, -1)
    np.testing.assert_allclose(ent, -(jnp.exp(logp) * logp).sum(-1),
                               rtol=1e-5, atol=1e-6)
