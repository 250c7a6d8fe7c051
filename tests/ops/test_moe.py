"""MoE layer tests: routing/dispatch correctness (capacity vs dense),
aux losses, gemma/mixtral HF parity. Mirrors reference
``tests/cpp_extensions/test_grouped_gemm.py`` (grouped GEMM vs
sequential experts)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import MoEConfig, TransformerConfig
from realhf_tpu.ops import moe as moe_ops

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


def moe_cfg(capacity=None, top_k=2, n_experts=4):
    return TransformerConfig(
        n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
        intermediate_dim=64, vocab_size=64, apply_rotary=True,
        layer_norm_type="rms", mlp_type="moe", use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu", compute_dtype="float32",
        moe=MoEConfig(num_experts=n_experts, top_k=top_k,
                      capacity_factor=capacity, aux_loss_coeff=0.01,
                      z_loss_coeff=0.001))


class TestMoEOps:

    def test_dense_matches_manual(self):
        """Dense dispatch must equal a per-token loop over selected
        experts (the sequential-experts oracle)."""
        cfg = moe_cfg()
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        m = jax.tree.map(lambda a: a[0], params["blocks"])["mlp"]
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((1, 8, 32)), jnp.float32)
        out, aux = moe_ops.moe_mlp_with_losses(cfg, m, x)

        xt = np.asarray(x)[0]
        logits = xt @ np.asarray(m["router"])
        probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
        expect = np.zeros_like(xt)
        for t in range(8):
            idx = np.argsort(probs[t])[::-1][:2]
            p = probs[t][idx] / probs[t][idx].sum()
            for i, e in enumerate(idx):
                g = xt[t] @ np.asarray(m["wg"])[e]
                u = xt[t] @ np.asarray(m["wu"])[e]
                act = g / (1 + np.exp(-g))  # silu
                expect[t] += p[i] * ((act * u) @ np.asarray(m["wd"])[e])
        np.testing.assert_allclose(np.asarray(out)[0], expect, rtol=1e-4,
                                   atol=1e-5)
        assert "moe_aux_loss" in aux and "moe_z_loss" in aux
        assert float(aux["moe_aux_loss"]) > 0

    def test_capacity_matches_dense_when_uncapped(self):
        """With capacity >= T*k/E per expert nothing is dropped, so the
        capacity dispatch equals the dense path."""
        cfg_d = moe_cfg(capacity=None)
        cfg_c = moe_cfg(capacity=8.0)  # ample capacity
        params = T.init_params(cfg_d, jax.random.PRNGKey(1))
        m = jax.tree.map(lambda a: a[0], params["blocks"])["mlp"]
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((2, 8, 32)), jnp.float32)
        out_d, _ = moe_ops.moe_mlp_with_losses(cfg_d, m, x)
        out_c, _ = moe_ops.moe_mlp_with_losses(cfg_c, m, x)
        np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_d),
                                   rtol=1e-4, atol=1e-5)

    def test_capacity_drops_overflow(self):
        cfg = moe_cfg(capacity=0.25, top_k=1, n_experts=2)
        params = T.init_params(cfg, jax.random.PRNGKey(2))
        m = jax.tree.map(lambda a: a[0], params["blocks"])["mlp"]
        x = jnp.ones((1, 16, 32), jnp.float32)  # identical tokens ->
        # all route to one expert; capacity 0.25*16*1/2 = 2 -> most drop
        out, _ = moe_ops.moe_mlp_with_losses(cfg, m, x)
        # dropped tokens produce zero output
        norms = np.linalg.norm(np.asarray(out)[0], axis=-1)
        assert (norms > 1e-6).sum() <= 2

    def test_forward_with_aux_and_grads(self):
        cfg = moe_cfg()
        params = T.init_params(cfg, jax.random.PRNGKey(3))
        ids = jnp.ones((1, 8), jnp.int32)
        seg = jnp.ones_like(ids)
        h, _, aux = T.forward(cfg, params, ids, seg, return_aux=True)
        assert h.shape == (1, 8, 32)
        assert float(aux["moe_aux_loss"]) > 0

        def loss(p):
            h, _, aux = T.forward(cfg, p, ids, seg, return_aux=True)
            return h.sum() + sum(aux.values())

        g = jax.grad(loss)(params)
        gn = sum(float(jnp.abs(x).sum()) for x in jax.tree.leaves(g))
        assert np.isfinite(gn) and gn > 0
        # router must receive gradient through the aux loss
        assert float(jnp.abs(g["blocks"]["mlp"]["router"]).sum()) > 0

    def test_sinkhorn_doubly_stochasticish(self):
        rng = np.random.default_rng(4)
        logits = jnp.asarray(rng.standard_normal((16, 4)), jnp.float32)
        out = moe_ops.sinkhorn(logits)
        p = np.asarray(jnp.exp(out))
        np.testing.assert_allclose(p.sum(0), p.sum(0).mean(), rtol=0.2)


class TestMixtralParity:

    @pytest.fixture(scope="class")
    def mixtral(self, tmp_path_factory):
        torch.manual_seed(0)
        hf_cfg = transformers.MixtralConfig(
            hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=200,
            num_local_experts=4, num_experts_per_tok=2,
            max_position_embeddings=128)
        model = transformers.MixtralForCausalLM(hf_cfg).eval()
        path = tmp_path_factory.mktemp("mixtral")
        model.save_pretrained(path, safe_serialization=True)
        return model, str(path)

    def test_logits_match_hf(self, mixtral):
        from realhf_tpu.models import hf as hfreg
        model, path = mixtral
        cfg, params = hfreg.load_hf_checkpoint(path)
        assert cfg.mlp_type == "moe" and cfg.moe.num_experts == 4
        cfg.compute_dtype = "float32"
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 200, size=(2, 16)).astype(np.int32)
        with torch.no_grad():
            theirs = model(
                input_ids=torch.from_numpy(ids).long()).logits.numpy()
        h, _ = T.forward(cfg, params, jnp.asarray(ids),
                         jnp.ones((2, 16), jnp.int32))
        ours = np.asarray(T.lm_logits(cfg, params, h))
        np.testing.assert_allclose(ours, theirs, rtol=5e-2, atol=5e-3)

    def test_save_roundtrip(self, mixtral, tmp_path):
        from realhf_tpu.models import hf as hfreg
        model, path = mixtral
        cfg, params = hfreg.load_hf_checkpoint(path)
        out = tmp_path / "resaved"
        hfreg.save_hf_checkpoint(str(out), "mixtral", cfg, params)
        reloaded = transformers.AutoModelForCausalLM.from_pretrained(
            str(out)).eval()
        rng = np.random.default_rng(1)
        ids = torch.from_numpy(
            rng.integers(0, 200, size=(1, 12)).astype(np.int64))
        with torch.no_grad():
            a = model(input_ids=ids).logits.numpy()
            b = reloaded(input_ids=ids).logits.numpy()
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


class TestGemmaParity:

    def test_logits_match_hf(self, tmp_path):
        from realhf_tpu.models import hf as hfreg
        torch.manual_seed(1)
        hf_cfg = transformers.GemmaConfig(
            hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=200, max_position_embeddings=128)
        model = transformers.GemmaForCausalLM(hf_cfg).eval()
        model.save_pretrained(tmp_path / "g", safe_serialization=True)
        cfg, params = hfreg.load_hf_checkpoint(str(tmp_path / "g"))
        cfg.compute_dtype = "float32"
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 200, size=(2, 12)).astype(np.int32)
        with torch.no_grad():
            theirs = model(
                input_ids=torch.from_numpy(ids).long()).logits.numpy()
        h, _ = T.forward(cfg, params, jnp.asarray(ids),
                         jnp.ones((2, 12), jnp.int32))
        ours = np.asarray(T.lm_logits(cfg, params, h))
        np.testing.assert_allclose(ours, theirs, rtol=5e-2, atol=5e-3)


class TestRaggedGroupedGEMM:
    """jax.lax.ragged_dot grouped-GEMM dispatch (reference GroupedMLP,
    experts.py:98): exact top-k MoE, parity with the dense path in
    forward and gradients."""

    def _cfgs(self):
        import dataclasses as dc
        cfg_r = moe_cfg(capacity=None)
        cfg_d = moe_cfg(capacity=None)
        cfg_r.moe = dc.replace(cfg_r.moe, use_grouped_gemm=True)
        cfg_d.moe = dc.replace(cfg_d.moe, use_grouped_gemm=False)
        return cfg_r, cfg_d

    def test_forward_matches_dense(self):
        from realhf_tpu.models import transformer as T
        from realhf_tpu.ops.moe import moe_mlp_with_losses

        cfg_r, cfg_d = self._cfgs()
        params = T.init_params(cfg_r, jax.random.PRNGKey(0))
        lp = jax.tree.map(lambda p: p[0], params["blocks"]["mlp"])
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((2, 16, 32)), jnp.float32)
        valid = jnp.asarray(rng.random((2, 16)) > 0.2)
        out_r, aux_r = moe_mlp_with_losses(cfg_r, lp, x,
                                           valid_mask=valid)
        out_d, aux_d = moe_mlp_with_losses(cfg_d, lp, x,
                                           valid_mask=valid)
        np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_d),
                                   atol=1e-5, rtol=1e-5)
        for k in aux_d:
            np.testing.assert_allclose(float(aux_r[k]), float(aux_d[k]),
                                       rtol=1e-6)

    def test_gradients_match_dense(self):
        from realhf_tpu.models import transformer as T
        from realhf_tpu.ops.moe import moe_mlp_with_losses

        cfg_r, cfg_d = self._cfgs()
        params = T.init_params(cfg_r, jax.random.PRNGKey(1))
        lp = jax.tree.map(lambda p: p[0], params["blocks"]["mlp"])
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((1, 12, 32)), jnp.float32)

        def loss(cfg):
            def f(lp_, x_):
                o, aux = moe_mlp_with_losses(cfg, lp_, x_)
                return (o.astype(jnp.float32) ** 2).sum() \
                    + sum(aux.values())
            return jax.grad(f, argnums=(0, 1))(lp, x)

        gr = loss(cfg_r)
        gd = loss(cfg_d)
        for a, b in zip(jax.tree.leaves(gr), jax.tree.leaves(gd)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=2e-5)


class TestGateNormalisation:
    """``MoEConfig.norm_topk_prob``: Mixtral divides the k gates by
    their sum, OLMoE takes them as the softmax over all experts gave
    them."""

    LOGITS = np.random.default_rng(5).normal(size=(12, 16)).astype(
        np.float32)

    @pytest.mark.parametrize("top_k,n_experts", [(2, 8), (8, 16), (1, 4)])
    def test_off_returns_the_softmaxs_own_values(self, top_k, n_experts):
        cfg = MoEConfig(num_experts=n_experts, top_k=top_k,
                        norm_topk_prob=False)
        logits = self.LOGITS[:, :n_experts]
        probs, idx = moe_ops.router_probs(cfg, jnp.asarray(logits))
        full = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
        want_idx = np.argsort(-full, axis=-1)[:, :top_k]
        np.testing.assert_array_equal(np.asarray(idx), want_idx)
        np.testing.assert_array_equal(
            np.asarray(probs), np.take_along_axis(full, want_idx, -1))
        assert (np.asarray(probs).sum(-1) < 1.0 - 1e-3).all()

    @pytest.mark.parametrize("top_k,n_experts", [(2, 8), (8, 16), (1, 4)])
    def test_on_divides_by_the_sum_as_before(self, top_k, n_experts):
        logits = jnp.asarray(self.LOGITS[:, :n_experts])
        on, idx_on = moe_ops.router_probs(
            MoEConfig(num_experts=n_experts, top_k=top_k), logits)
        off, idx_off = moe_ops.router_probs(
            MoEConfig(num_experts=n_experts, top_k=top_k,
                      norm_topk_prob=False), logits)
        np.testing.assert_array_equal(np.asarray(idx_on),
                                      np.asarray(idx_off))
        np.testing.assert_allclose(np.asarray(on).sum(-1), 1.0, rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(on), np.asarray(off / off.sum(-1, keepdims=True)),
            rtol=1e-6)

    @pytest.mark.parametrize("mode", ["ragged", "dense", "capacity"])
    def test_layer_output_scales_with_the_gate_mass(self, mode):
        """Every dispatch mode honours the flag: the un-renormalised
        layer's output is the renormalised one's times each token's
        gate mass."""
        def cfg(norm):
            c = moe_cfg(capacity=4.0 if mode == "capacity" else None,
                        top_k=2, n_experts=4)
            c.moe.use_grouped_gemm = mode == "ragged"
            c.moe.norm_topk_prob = norm
            return c
        assert moe_ops.dispatch_mode(cfg(True)) == mode
        params = T.init_params(cfg(True), jax.random.PRNGKey(2))
        m = jax.tree.map(lambda a: a[0], params["blocks"])["mlp"]
        x = jnp.asarray(np.random.default_rng(1).normal(
            size=(2, 6, 32)).astype(np.float32))
        on, _ = moe_ops.moe_mlp_with_losses(cfg(True), m, x)
        off, aux = moe_ops.moe_mlp_with_losses(cfg(False), m, x)
        probs = jax.nn.softmax(
            x.reshape(12, 32) @ m["router"], axis=-1)
        mass = jax.lax.top_k(probs, 2)[0].sum(-1).reshape(2, 6, 1)
        np.testing.assert_allclose(np.asarray(off), np.asarray(on * mass),
                                   rtol=2e-5, atol=1e-7)
        # the statistic beside the losses: 12 x 2 pairs over 4 experts
        load = float(aux[moe_ops.LOAD_STAT])
        assert 1.0 <= load <= 4.0 and abs(load * 6 - round(load * 6)) < 1e-5
        assert moe_ops.aux_loss(aux) == sum(
            v for k, v in aux.items() if k != moe_ops.LOAD_STAT)


def test_load_statistic_is_the_worst_layers_not_a_sum():
    cfg = moe_cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(3))
    ids = jnp.asarray(np.random.default_rng(2).integers(
        0, 64, size=(2, 16)), jnp.int32)
    _, _, aux = T.forward(cfg, params, ids, jnp.ones_like(ids),
                          return_aux=True)
    per_layer = []
    for i in range(cfg.n_layers):
        one = TransformerConfig(**{**cfg.__dict__, "n_layers": i + 1})
        sub = jax.tree.map(lambda a: a[:i + 1], params["blocks"])
        _, _, a = T.forward(one, {**params, "blocks": sub}, ids,
                            jnp.ones_like(ids), return_aux=True)
        per_layer.append(float(a[moe_ops.LOAD_STAT]))
    # the stack's statistic is a running maximum over its layers
    assert float(aux[moe_ops.LOAD_STAT]) == per_layer[-1] \
        == max(per_layer)
    assert float(aux["moe_aux_loss"]) > 0


# ----------------------------------------------------------------------
# The sigmoid-and-bias router and an expert-parallel rank's share
# ----------------------------------------------------------------------
def share_cfg(held=None, **moe):
    return TransformerConfig(
        n_layers=1, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
        intermediate_dim=64, vocab_size=64, apply_rotary=True,
        layer_norm_type="rms", mlp_type="moe", use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu", compute_dtype="float32",
        moe=MoEConfig(**{**dict(
            num_experts=16, top_k=4, routing_type="none",
            score_fn="sigmoid", use_expert_bias=True,
            intermediate_dim=24, experts_held=held), **moe}))


def share_layer(seed=0, tokens=40):
    """A 16-expert layer's leaves (every expert), a biased router and
    an input [1, tokens, 32]."""
    rng = np.random.default_rng(seed)
    m = dict(router=rng.standard_normal((32, 16)) * 0.5,
             expert_bias=rng.standard_normal((16,)) * 0.3,
             wg=rng.standard_normal((16, 32, 24)) * 0.2,
             wu=rng.standard_normal((16, 32, 24)) * 0.2,
             wd=rng.standard_normal((16, 24, 32)) * 0.2)
    m = {k: jnp.asarray(v, jnp.float32) for k, v in m.items()}
    x = jnp.asarray(rng.standard_normal((1, tokens, 32)), jnp.float32)
    return m, x


@pytest.fixture(params=["ragged_dot", "kernels"])
def products(request, monkeypatch, interpreted_kernels):
    """Both ways a share's grouped products run
    (``ops/moe.py:_grouped_products``): ``lax.ragged_dot`` with every
    row in a group, and ``ops/grouped_matmul.py``'s kernels, here in
    interpret mode, with the group sizes as they are."""
    if request.param == "ragged_dot":
        yield request.param
        return
    # (the interpreter's callbacks are effects that the fallback's
    # ``checkpoint`` cannot split under a gradient)
    monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: f)
    with interpreted_kernels():
        yield request.param


def held_leaves(m, first, count):
    return {k: (v[first:first + count] if k in ("wg", "wu", "wd") else v)
            for k, v in m.items()}


def sigmoid_oracle(m, x, norm=True, scaling=1.0, bias=True, eps=1e-6):
    """The layer by a loop over tokens and their chosen experts."""
    xt = np.asarray(x, np.float64)[0]
    s = 1 / (1 + np.exp(-(xt @ np.asarray(m["router"], np.float64))))
    choice = s + (np.asarray(m["expert_bias"], np.float64) if bias else 0)
    out = np.zeros_like(xt)
    for t in range(len(xt)):
        idx = np.argsort(choice[t])[::-1][:4]
        g = s[t][idx]
        if norm:
            g = g / (g.sum() + eps)
        for gate, e in zip(g * scaling, idx):
            a = xt[t] @ np.asarray(m["wg"], np.float64)[e]
            u = xt[t] @ np.asarray(m["wu"], np.float64)[e]
            out[t] += gate * ((a / (1 + np.exp(-a)) * u)
                              @ np.asarray(m["wd"], np.float64)[e])
    return out


@pytest.mark.parametrize("norm,scaling,bias", [
    (True, 1.0, True), (False, 1.0, True), (True, 2.5, True),
    (True, 1.0, False)], ids=["published", "not_renormalised",
                              "scaled", "no_bias"])
def test_sigmoid_router_matches_a_loop_over_tokens(norm, scaling, bias):
    """Sigmoid scores, the bias in the choice and never in the gate,
    the 1e-6 in the renormalisation, the scaling factor: ragged and
    dense dispatch against the oracle, every expert held."""
    m, x = share_layer()
    if not bias:
        m = {k: v for k, v in m.items() if k != "expert_bias"}
    want = sigmoid_oracle(m, x, norm, scaling, bias)
    for grouped in (True, False):
        cfg = share_cfg(norm_topk_prob=norm, routed_scaling_factor=scaling,
                        use_expert_bias=bias, use_grouped_gemm=grouped)
        out, aux = moe_ops.moe_mlp_with_losses(cfg, m, x)
        np.testing.assert_allclose(np.asarray(out)[0], want, rtol=2e-4,
                                   atol=2e-6)
        assert set(aux) == {moe_ops.LOAD_STAT}  # routing_type "none"
    # the bias moved some token's choice, or the test shows nothing
    if bias:
        assert np.abs(want - sigmoid_oracle(m, x, norm, scaling,
                                            False)).max() > 1e-3


def test_no_gradient_reaches_the_expert_bias():
    m, x = share_layer()
    cfg = share_cfg()
    grads = jax.grad(lambda m: moe_ops.moe_mlp_with_losses(
        cfg, m, x)[0].sum())(m)
    assert not np.asarray(grads["expert_bias"]).any()
    assert np.asarray(grads["router"]).any()


def test_eight_shares_add_up_to_the_whole_layer(products):
    """The tie of the share to the model: eight ranks of 2 experts,
    each routing over all 16, add up to what the layer gives with every
    expert held (the same code) and to the loop over tokens; the pairs
    they multiply add up to the pairs routed."""
    m, x = share_layer(seed=1)
    whole, aux = moe_ops.moe_mlp_with_losses(share_cfg(), m, x)
    assert moe_ops.HELD_PAIRS_STAT not in aux
    total, pairs, worst = 0.0, 0.0, 0.0
    for rank in range(8):
        cfg = share_cfg(held=(2 * rank, 2))
        part, aux = moe_ops.moe_mlp_with_losses(
            cfg, held_leaves(m, 2 * rank, 2), x)
        total = total + part
        pairs += float(aux[moe_ops.HELD_PAIRS_STAT])
        worst = max(worst, float(aux[moe_ops.HELD_LOAD_STAT]))
        assert float(aux[moe_ops.HELD_LOAD_STAT]) <= float(
            aux[moe_ops.LOAD_STAT])
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(whole)[0], sigmoid_oracle(m, x),
                               rtol=2e-4, atol=2e-6)
    assert pairs == 40 * 4
    assert worst == pytest.approx(float(aux[moe_ops.LOAD_STAT]))
    # every expert held as an explicit share is the uncut layer too
    same, aux = moe_ops.moe_mlp_with_losses(share_cfg(held=(0, 16)), m, x)
    np.testing.assert_allclose(np.asarray(same), np.asarray(whole),
                               rtol=1e-6, atol=1e-7)
    assert float(aux[moe_ops.HELD_PAIRS_STAT]) == 40 * 4
    # with every expert held the fast path IS the whole: none overflow
    assert moe_ops.share_rows(share_cfg(held=(0, 16)), 40) == 40 * 4
    assert float(aux[moe_ops.SHARE_OVERFLOW_STAT]) == 0


@pytest.mark.parametrize("pulled, slow", [(32, 0), (33, 1), (64, 1)])
def test_overflow_statistic_is_the_branch_taken(products, pulled, slow):
    """``SHARE_OVERFLOW_STAT`` reads 1 exactly where the held pairs
    pass ``share_rows`` (64 of 64 x 4 here). The router reads one
    input feature alone: it sends the first ``pulled`` tokens to the
    two held experts and two more, the others to four absent ones.
    Either branch equals the held experts' part of the whole layer."""
    m, x = share_layer(seed=3, tokens=64)
    cfg = share_cfg(held=(4, 2))
    assert moe_ops.share_rows(cfg, 64) == 64
    sign = np.zeros(16, np.float32)
    sign[[4, 5, 9, 13]], sign[[0, 1, 2, 3]] = 1.0, -1.0
    m = dict(m, expert_bias=jnp.zeros(16),
             router=jnp.zeros((32, 16)).at[-1].set(50.0 * sign))
    x = x.at[0, :, -1].set(jnp.where(jnp.arange(64) < pulled, 1.0, -1.0))
    part, aux = moe_ops.moe_mlp_with_losses(cfg, held_leaves(m, 4, 2), x)
    assert float(aux[moe_ops.HELD_PAIRS_STAT]) == 2 * pulled
    assert float(aux[moe_ops.SHARE_OVERFLOW_STAT]) == slow
    only = dict(m, wd=m["wd"].at[:4].set(0).at[6:].set(0))
    want, _ = moe_ops.moe_mlp_with_losses(share_cfg(), only, x)
    np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(part)).max() > 1e-3


@pytest.mark.parametrize("first", [0, 6, 14])
def test_no_pair_of_a_held_expert_is_dropped_under_imbalance(products,
                                                              first):
    """A bias that sends EVERY token to the held experts (and to two
    more): the share multiplies 2 x T pairs, far above T x k / E x 2,
    and still equals those experts' part of the whole layer; a bias
    that sends nothing to them gives exactly zero."""
    m, x = share_layer(seed=2, tokens=64)
    pull = np.zeros(16, np.float32)
    pull[[first, first + 1, (first + 5) % 16, (first + 9) % 16]] = 10.0
    m["expert_bias"] = jnp.asarray(pull)
    cfg = share_cfg(held=(first, 2))
    part, aux = moe_ops.moe_mlp_with_losses(cfg, held_leaves(m, first, 2), x)
    assert float(aux[moe_ops.HELD_PAIRS_STAT]) == 2 * 64
    assert float(aux[moe_ops.HELD_LOAD_STAT]) == 64 * 16 / (64 * 4)
    # twice the fast path's rows: the slow path ran, and says so
    assert moe_ops.share_rows(cfg, 64) == 64
    assert float(aux[moe_ops.SHARE_OVERFLOW_STAT]) == 1
    # the same two experts alone in the whole layer: the others zeroed
    only = dict(m, wd=m["wd"].at[:first].set(0).at[first + 2:].set(0))
    want, _ = moe_ops.moe_mlp_with_losses(share_cfg(), only, x)
    np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(part)).max() > 1e-3
    m["expert_bias"] = jnp.asarray(-pull)
    none, aux = moe_ops.moe_mlp_with_losses(cfg, held_leaves(m, first, 2), x)
    assert float(aux[moe_ops.HELD_PAIRS_STAT]) == 0
    assert float(aux[moe_ops.SHARE_OVERFLOW_STAT]) == 0
    assert not np.asarray(none).any()
    grads = jax.grad(lambda m: moe_ops.moe_mlp_with_losses(
        cfg, m, x)[0].sum())(held_leaves(m, first, 2))
    assert all(np.isfinite(np.asarray(g)).all() for g in grads.values())


def test_a_share_needs_the_ragged_mode_and_a_range_of_the_experts():
    m, x = share_layer()
    with pytest.raises(NotImplementedError, match="experts_held"):
        moe_ops.moe_mlp_with_losses(
            share_cfg(held=(0, 2), capacity_factor=2.0),
            held_leaves(m, 0, 2), x)
    with pytest.raises(ValueError, match="experts_held"):
        MoEConfig(num_experts=16, experts_held=(12, 8))
    with pytest.raises(NotImplementedError, match="score_fn"):
        MoEConfig(score_fn="tanh")


@pytest.mark.parametrize("pull", [0.0, 10.0], ids=["fast_path", "fallback"])
def test_every_row_of_a_shares_grouped_products_lies_in_a_group(
        monkeypatch, pull):
    """On the chip ``lax.ragged_dot`` leaves a row that no group covers
    unwritten, in the backward's products too (PERF.md, PR 31: the
    forward agreed with the reference and the gradient was garbage),
    and the CPU's zero there hides it. So the invariant itself is
    held: the group sizes of every grouped product of a share add up
    to its rows, on the fast path and on the fallback."""
    m, x = share_layer(seed=3, tokens=64)
    bias = np.asarray(m["expert_bias"]).copy()
    bias[[4, 5]] += pull  # every token to the held experts, or not
    m["expert_bias"] = jnp.asarray(bias)
    seen = []
    real = jax.lax.ragged_dot

    def checked(lhs, rhs, group_sizes, **kw):
        seen.append((lhs.shape[0], int(group_sizes.sum())))
        return real(lhs, rhs, group_sizes, **kw)

    monkeypatch.setattr(jax.lax, "ragged_dot", checked)
    # (the fallback's chunks are rematerialised: not here, where the
    # group sizes have to be numbers)
    monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: f)
    with jax.disable_jit():
        moe_ops.moe_mlp_with_losses(share_cfg(held=(4, 2)),
                                    held_leaves(m, 4, 2), x)
    # the fast path's rows once; the fallback all 64 x 4 sorted rows,
    # that many at a time (three products a chunk)
    rows = 2 * 64 * 4 * 2 // 16
    assert seen == [(rows, rows)] * 3 * (64 * 4 // rows if pull else 1)


@pytest.mark.parametrize("pull", [0.0, 10.0], ids=["fast_path", "fallback"])
def test_the_kernels_group_sizes_are_the_held_pairs_and_no_more(
        monkeypatch, pull):
    """The twin of the test above on the kernels' path
    (``ops/grouped_matmul.py`` returns a row no group covers as zero
    and does not multiply it): the group sizes of every grouped
    product of a share add up to the HELD pairs among its rows, never
    to more than the rows, and over the fallback's chunks to all of
    them; nothing is counted into the last group."""
    m, x = share_layer(seed=3, tokens=64)
    bias = np.asarray(m["expert_bias"]).copy()
    bias[[4, 5]] += pull
    m["expert_bias"] = jnp.asarray(bias)
    seen = []

    def checked(lhs, rhs, group_sizes):
        seen.append((lhs.shape[0], int(group_sizes.sum())))
        covered = jnp.arange(lhs.shape[0])[:, None] < group_sizes.sum()
        sizes = group_sizes.at[-1].add(lhs.shape[0] - group_sizes.sum())
        return jnp.where(covered, jax.lax.ragged_dot(lhs, rhs, sizes), 0)

    monkeypatch.setattr(moe_ops, "pallas_enabled", lambda: True)
    monkeypatch.setattr(moe_ops, "grouped_matmul", checked)
    monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: f)
    with jax.disable_jit():
        _, aux = moe_ops.moe_mlp_with_losses(share_cfg(held=(4, 2)),
                                             held_leaves(m, 4, 2), x)
    held = int(aux[moe_ops.HELD_PAIRS_STAT])
    rows = 2 * 64 * 4 * 2 // 16
    assert all(n == rows and covered <= rows for n, covered in seen)
    if pull:  # 128 held pairs over four chunks of 64 sorted rows
        assert held == 2 * 64 > rows
        assert seen == [(rows, rows)] * 6 + [(rows, 0)] * 6
    else:
        assert 0 < held < rows and seen == [(rows, held)] * 3


# ----------------------------------------------------------------------
# A shared expert beside the routed ones
# ----------------------------------------------------------------------
def shared_leaves(seed=5, width=20):
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(rng.standard_normal(shape) * 0.2, jnp.float32)
            for k, shape in (("wg", (32, width)), ("wu", (32, width)),
                             ("wd", (width, 32)))}


def swiglu_oracle(s, x):
    xt = np.asarray(x, np.float64)[0]
    a = xt @ np.asarray(s["wg"], np.float64)
    return (a / (1 + np.exp(-a)) * (xt @ np.asarray(s["wu"], np.float64))) \
        @ np.asarray(s["wd"], np.float64)


@pytest.mark.parametrize("grouped", [True, False], ids=["ragged", "dense"])
def test_shared_expert_is_added_to_every_token_with_weight_one(grouped):
    """The routed part (no bias, the 1e-20 of the family that has a
    shared expert, a scaling factor) plus a dense SwiGLU over all
    tokens, in both exact dispatch modes; no statistic changes."""
    m, x = share_layer(seed=2)
    m = {k: v for k, v in m.items() if k != "expert_bias"}
    shared = shared_leaves()
    cfg = share_cfg(use_expert_bias=False, routed_scaling_factor=2.5,
                    norm_topk_eps=1e-20, shared_intermediate_dim=20,
                    use_grouped_gemm=grouped)
    routed, aux0 = moe_ops.moe_mlp_with_losses(cfg, m, x)
    out, aux = moe_ops.moe_mlp_with_losses(cfg, {**m, "shared": shared}, x)
    want = sigmoid_oracle(m, x, True, 2.5, False) + swiglu_oracle(shared, x)
    np.testing.assert_allclose(np.asarray(out)[0], want, rtol=2e-4,
                               atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(out - routed)[0], swiglu_oracle(shared, x), rtol=2e-4,
        atol=2e-6)
    assert set(aux) == set(aux0) == {moe_ops.LOAD_STAT}
    assert float(aux[moe_ops.LOAD_STAT]) == float(aux0[moe_ops.LOAD_STAT])
    grads = jax.grad(lambda s: moe_ops.moe_mlp_with_losses(
        cfg, {**m, "shared": s}, x)[0].sum())(shared)
    assert all(np.asarray(g).any() for g in grads.values())


def test_sixteen_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """The tie of the share to the model where every rank also holds
    the shared expert: sixteen ranks of ONE expert each, their ROUTED
    parts (each rank's result less what the shared expert gives it)
    added up, plus the shared expert counted once, are what the layer
    gives with every expert held; adding the ranks' results as they
    are would count the shared expert sixteen times."""
    m, x = share_layer(seed=3)
    shared = shared_leaves()
    kw = dict(shared_intermediate_dim=20)
    whole, _ = moe_ops.moe_mlp_with_losses(
        share_cfg(**kw), {**m, "shared": shared}, x)
    once = swiglu_oracle(shared, x)
    routed, naive, pairs = 0.0, 0.0, 0.0
    for rank in range(16):
        part, aux = moe_ops.moe_mlp_with_losses(
            share_cfg(held=(rank, 1), **kw),
            {**held_leaves(m, rank, 1), "shared": shared}, x)
        routed = routed + (np.asarray(part, np.float64)[0] - once)
        naive = naive + np.asarray(part, np.float64)[0]
        pairs += float(aux[moe_ops.HELD_PAIRS_STAT])
    np.testing.assert_allclose(routed + once, np.asarray(whole)[0],
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(whole)[0],
                               sigmoid_oracle(m, x) + once, rtol=2e-4,
                               atol=2e-6)
    assert np.abs(naive - np.asarray(whole)[0]).max() > 1.0
    assert pairs == 40 * 4


def test_eight_shares_and_the_shared_experts_once_add_up_to_the_layer():
    """Moonlight's router and its deployment at a small size: sigmoid
    scores, the k chosen by score + selection bias, gates over (their
    sum + 1e-20) times 2.446, the two shared experts as ONE SwiGLU
    every rank holds. Eight ranks of two experts each: their ROUTED
    parts added up, plus the shared experts counted once, are what the
    layer gives with all sixteen held, and what a loop over tokens and
    their chosen experts gives."""
    m, x = share_layer(seed=7)
    shared = shared_leaves(width=2 * 24)  # n_shared_experts x the width
    kw = dict(shared_intermediate_dim=48, routed_scaling_factor=2.446,
              norm_topk_eps=1e-20)
    whole, _ = moe_ops.moe_mlp_with_losses(
        share_cfg(**kw), {**m, "shared": shared}, x)
    once = swiglu_oracle(shared, x)
    routed, naive, pairs = 0.0, 0.0, 0.0
    for rank in range(8):
        part, aux = moe_ops.moe_mlp_with_losses(
            share_cfg(held=(2 * rank, 2), **kw),
            {**held_leaves(m, 2 * rank, 2), "shared": shared}, x)
        routed = routed + (np.asarray(part, np.float64)[0] - once)
        naive = naive + np.asarray(part, np.float64)[0]
        pairs += float(aux[moe_ops.HELD_PAIRS_STAT])
    np.testing.assert_allclose(routed + once, np.asarray(whole)[0],
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(whole)[0],
        sigmoid_oracle(m, x, scaling=2.446, eps=1e-20) + once,
        rtol=2e-4, atol=2e-6)
    # the shares as they are count the shared experts eight times
    np.testing.assert_allclose(naive - np.asarray(whole)[0], 7 * once,
                               rtol=2e-4, atol=2e-5)
    assert pairs == 40 * 4
    # the bias moves the choice and nothing else: without it other
    # experts are chosen for some token
    unbiased, _ = moe_ops.moe_mlp_with_losses(
        share_cfg(**kw), {**m, "shared": shared,
                          "expert_bias": jnp.zeros((16,))}, x)
    assert np.abs(np.asarray(unbiased - whole)).max() > 1e-2


@pytest.mark.parametrize("tokens,pulled,count", [
    (64, 64, 2), (64, 40, 2), (50, 50, 3)],
    ids=["every_chunk_full", "pairs_end_inside_a_chunk",
         "rows_do_not_divide"])
def test_the_slow_path_in_chunks_is_the_held_experts_part(tokens, pulled,
                                                          count):
    """The fallback takes all ``T x k`` sorted rows ``share_rows`` at a
    time in a rematerialised scan: result AND gradients (of the input,
    the router and each held expert's matrices) are those of the same
    experts alone in the uncut layer, where an expert's pairs straddle
    two chunks, where the held pairs end inside a chunk, and where the
    last chunk runs past ``T x k`` (50 x 4 = 200 rows in three chunks
    of 75)."""
    m, x = share_layer(seed=4, tokens=tokens)
    cfg = share_cfg(held=(4, count))
    rows = moe_ops.share_rows(cfg, tokens)
    assert rows < tokens * 4 and (tokens * 4 % rows == 0) == (count == 2)
    sign = np.zeros(16, np.float32)
    sign[[4, 5, 9, 13]], sign[[0, 1, 2, 3]] = 1.0, -1.0
    m = dict(m, expert_bias=jnp.zeros(16),
             router=m["router"] * 0.05 + jnp.zeros((32, 16)).at[-1].set(
                 6.0 * sign))  # (no saturated sigmoid: the router learns)
    x = x.at[0, :, -1].set(jnp.where(jnp.arange(tokens) < pulled, 1.0,
                                     -1.0))
    held = slice(4, 4 + count)

    def share(m_, x_):
        out, aux = moe_ops.moe_mlp_with_losses(
            cfg, held_leaves(m_, 4, count), x_)
        return (out * jnp.cos(jnp.arange(32.0))).sum(), aux

    def alone(m_, x_):
        only = dict(m_, wd=m_["wd"].at[:4].set(0).at[4 + count:].set(0))
        return (moe_ops.moe_mlp_with_losses(share_cfg(), only, x_)[0]
                * jnp.cos(jnp.arange(32.0))).sum()

    (got, aux), grads = jax.value_and_grad(share, argnums=(0, 1),
                                           has_aux=True)(m, x)
    assert float(aux[moe_ops.SHARE_OVERFLOW_STAT]) == 1
    assert float(aux[moe_ops.HELD_PAIRS_STAT]) == 2 * pulled > rows
    want, want_grads = jax.value_and_grad(alone, argnums=(0, 1))(m, x)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grads[1]),
                               np.asarray(want_grads[1]), rtol=1e-4,
                               atol=1e-6)
    for leaf in ("router", "wg", "wu", "wd"):
        g, w = np.asarray(grads[0][leaf]), np.asarray(want_grads[0][leaf])
        if leaf != "router":  # the held experts' own matrices
            g, w = g[held], w[held]
        assert np.abs(w).max() > 0, leaf
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=1e-6,
                                   err_msg=leaf)
