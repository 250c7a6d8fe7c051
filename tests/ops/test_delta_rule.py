"""``ops/delta_rule.py``: the chunked gated delta rule (one decay a key
channel) against the recurrence taken token by token, forward and
gradient, under the harness's weights' regime (the state halves every
token) and the published initialisation's (a state lives hundreds of
tokens), at lengths that are and are not multiples of the chunk, on
packed rows whose documents end inside a chunk and inside a 16-token
sub-block; and, with the decay made equal over a head's channels,
against ``transformers``' own recurrent gated delta rule."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realhf_tpu.ops import delta_rule as D

H, DK, DV = 2, 16, 8


def inputs(seed, b, l, regime, dk=DK, dv=DV, h=H):
    """q, k l2-normed (q scaled), v, g <= 0, beta in (0, 1)."""
    rng = np.random.default_rng(seed)
    q, k = (rng.normal(size=(b, l, h, dk)) for _ in range(2))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(b, l, h, dv))
    if regime == "harness":  # A_log, dt_bias near 0: g about -0.69
        g = -np.exp(0.02 * rng.normal(size=(1, 1, h, 1))) * np.log1p(
            np.exp(0.3 * rng.normal(size=(b, l, h, dk))))
    else:  # published: A in [1, 16], dt log-uniform in [1e-3, 1e-1]
        a = rng.uniform(1, 16, size=(1, 1, h, 1))
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                size=(1, 1, h, dk)))
        bias = dt + np.log(-np.expm1(-dt))  # softplus^-1(dt)
        g = -a * np.log1p(np.exp(
            0.3 * rng.normal(size=(b, l, h, dk)) + bias))
    beta = 1 / (1 + np.exp(-rng.normal(size=(b, l, h))))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def token_by_token(q, k, v, g, beta, seg):
    """The recurrence as written, a token at a time: the state is 0
    before a document's first token, a padding token leaves it."""
    seg = np.asarray(seg)
    b, l, h, dk = k.shape

    def row(qs, ks, vs, gs, bs, first, valid):
        def step(s, x):
            qt, kt, vt, gt, bt, new, ok = x
            s0 = jnp.where(new, 0.0, s)
            s1 = s0 * jnp.exp(gt)[..., None]
            u = bt[..., None] * (vt - jnp.einsum("hkv,hk->hv", s1, kt))
            s1 = s1 + kt[..., None] * u[..., None, :]
            s1 = jnp.where(ok, s1, s)
            return s1, jnp.einsum("hkv,hk->hv", s1, qt)
        return jax.lax.scan(step, jnp.zeros((h, dk, v.shape[-1])),
                            (qs, ks, vs, gs, bs, first, valid))

    before = np.pad(seg, ((0, 0), (1, 0)))[:, :-1]
    first = jnp.asarray((seg != 0) & (seg != before))
    with jax.default_matmul_precision("highest"):
        last, o = jax.vmap(row)(q, k, v, g, beta, first,
                                jnp.asarray(seg != 0))
    return o, last


def segments(l, *ends):
    """One row: documents 1, 2, .. ending before ``ends``, padding
    (0) after the last."""
    seg = np.zeros((1, l), np.int32)
    at = 0
    for i, e in enumerate(ends):
        seg[0, at:e] = i + 1
        at = e
    return seg


@pytest.mark.parametrize("regime", ["harness", "published"])
@pytest.mark.parametrize("l,ends", [
    (128, (128,)),            # two whole chunks, one document
    (100, (100,)),            # not a multiple of 64
    (40, (40,)),              # shorter than a chunk
    (192, (70, 137, 192)),    # boundaries inside chunks 2 and 3
    (160, (5, 21, 90, 150)),  # inside a sub-block, and padding after
    (130, (64, 128, 130)),    # boundaries ON the chunk grid
    (700, (300, 520, 690)),   # two SEGMENTS of six chunks, a document
                              # over the segments' boundary at 384
])
def test_chunked_equals_token_by_token(regime, l, ends):
    seg = segments(l, *ends)
    x = inputs(7, 1, l, regime)
    with jax.default_matmul_precision("highest"):
        o, last = D.chunked_delta_rule(*x, jnp.asarray(seg))
    want_o, want_last = token_by_token(*x, seg)
    valid = (seg != 0)[..., None, None]
    scale = float(jnp.abs(want_o).max())
    assert float(jnp.abs(jnp.where(valid, o - want_o, 0)).max()) \
        < 2e-5 * scale
    assert float(jnp.abs(last - want_last).max()) \
        < 2e-5 * max(1.0, float(jnp.abs(want_last).max()))


def test_left_padding_leaves_the_state_at_zero_until_the_document():
    seg = np.zeros((1, 96), np.int32)
    seg[0, 37:] = 1
    x = inputs(3, 1, 96, "published")
    with jax.default_matmul_precision("highest"):
        o, last = D.chunked_delta_rule(*x, jnp.asarray(seg))
        o1, last1 = D.chunked_delta_rule(
            *(a[:, 37:] for a in x), jnp.asarray(seg[:, 37:]))
    np.testing.assert_allclose(o[:, 37:], o1, atol=2e-6)
    np.testing.assert_allclose(last, last1, atol=2e-6)


@pytest.mark.parametrize("regime", ["harness", "published"])
def test_gradients_equal_the_recurrences(regime):
    l = 600  # two rematerialised segments of five chunks
    seg = segments(l, 23, 301, 560)
    x = inputs(11, 2, l, regime)
    seg = np.concatenate([seg, segments(l, l)])
    w = jnp.asarray(np.random.default_rng(5).normal(size=(2, l, H, DV)),
                    jnp.float32) * jnp.asarray(seg != 0)[..., None, None]

    def loss(fn, *a):
        return (fn(*a, jnp.asarray(seg) if fn is D.chunked_delta_rule
                   else seg)[0] * w).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: loss(D.chunked_delta_rule, *a),
                       argnums=(0, 1, 2, 3, 4))(*x)
    want = jax.grad(lambda *a: loss(token_by_token, *a),
                    argnums=(0, 1, 2, 3, 4))(*x)
    for name, a, b_ in zip("q k v g beta".split(), got, want):
        ok = jnp.asarray(seg != 0).reshape(2, l, *([1] * (a.ndim - 2)))
        err = float(jnp.abs(jnp.where(ok, a - b_, 0)).max())
        assert err < 5e-5 * float(jnp.abs(b_).max()), (name, err)
        assert bool(jnp.isfinite(a).all()), name


def test_no_exponent_overflows_where_the_factored_form_would():
    """1.6 a token over a chunk is exp(102) in the factored form:
    float32 ends at exp(88.7). Here every exponent is <= 0."""
    l = 128
    q, k, v, g, beta = inputs(2, 1, l, "published")
    g = jnp.full_like(g, -1.7)
    seg = jnp.ones((1, l), jnp.int32)
    o, last = D.chunked_delta_rule(q, k, v, g, beta, seg)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(last).all())
    want, _ = token_by_token(q, k, v, g, beta, np.ones((1, l), np.int32))
    np.testing.assert_allclose(o, want, atol=1e-5)
    grads = jax.grad(lambda g_: D.chunked_delta_rule(
        q, k, v, g_, beta, seg)[0].sum())(g)
    assert bool(jnp.isfinite(grads).all())


def test_a_step_at_a_time_continues_the_chunked_state():
    l = 90
    x = inputs(13, 2, l + 5, "published")
    seg = jnp.ones((2, l), jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, state = D.chunked_delta_rule(*(a[:, :l] for a in x), seg)
        whole, _ = D.chunked_delta_rule(*x, jnp.ones((2, l + 5), jnp.int32))
        for t in range(l, l + 5):
            o, state = D.delta_rule_step(*(a[:, t] for a in x), state)
            np.testing.assert_allclose(o, whole[:, t], atol=2e-6)


def test_equal_decay_is_transformers_recurrent_gated_delta_rule():
    """With one decay for all of a head's channels the recurrence is
    the gated delta rule of Qwen3-Next, whose token-by-token form
    ``transformers`` carries: code nobody here wrote."""
    torch = pytest.importorskip("torch")
    from transformers.models.qwen3_next.modeling_qwen3_next import (
        torch_recurrent_gated_delta_rule,
    )
    l = 150
    q, k, v, g, beta = inputs(17, 2, l, "published")
    g = jnp.broadcast_to(g[..., :1], g.shape)
    with jax.default_matmul_precision("highest"):
        o, last = D.chunked_delta_rule(q, k, v, g, beta,
                                       jnp.ones((2, l), jnp.int32))
    t = lambda a: torch.tensor(np.asarray(a))
    # theirs scales the query by dk^-0.5 itself: hand it the unit one
    want, want_last = torch_recurrent_gated_delta_rule(
        t(q) * DK ** 0.5, t(k), t(v), t(g[..., 0]), t(beta), None, True)
    np.testing.assert_allclose(o, want.numpy(), atol=3e-6)
    np.testing.assert_allclose(last, want_last.numpy(), atol=3e-6)


def test_prepare_runs_where_the_segment_is_computed():
    """``prepare`` (a layer's l2 norm and decay) applied inside equals
    applying it before; the output takes the values' dtype, the state
    stays float32."""
    l = 600
    q, k, v, g, beta = inputs(19, 1, l, "published")
    seg = jnp.asarray(segments(l, 250, 590))
    raw = (q * 3.0, k * 0.5, g * 2.0)
    prepare = lambda q_, k_, g_: (q_ / 3.0, k_ / 0.5, g_ / 2.0)
    with jax.default_matmul_precision("highest"):
        want, want_last = D.chunked_delta_rule(q, k, v, g, beta, seg)
        got, last = D.chunked_delta_rule(*raw[:2], v, raw[2], beta, seg,
                                         prepare=prepare)
        half, last16 = D.chunked_delta_rule(
            *raw[:2], v.astype(jnp.bfloat16), raw[2], beta, seg,
            prepare=prepare)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(last, want_last, atol=2e-6)
    assert half.dtype == jnp.bfloat16 and last16.dtype == jnp.float32
    np.testing.assert_allclose(half.astype(jnp.float32), want, atol=2e-2)


def test_a_segment_is_four_chunks_of_64():
    """What the eighth cell's size hangs on: the compiler counts its
    whole train step at 13.19 GB with a segment of 4 chunks and 13.56
    with 8, of the 13.9 a program is held to (``-m slow``:
    ``tests/ops/test_chip_compile.py::
    test_kimis_whole_train_step_compiles``); and the lengths above
    cross the boundaries they say only while these hold."""
    assert (D.CHUNK, D.SUB, D.SEGMENT_CHUNKS) == (64, 16, 4)


def test_doc_index_counts_padding_with_the_document_before():
    seg = jnp.asarray([[0, 0, 3, 3, 0, 7, 7, 0], [1, 2, 2, 4, 0, 0, 0, 0]])
    assert D.doc_index(seg).tolist() == [[0, 0, 1, 1, 1, 2, 2, 2],
                                         [1, 2, 2, 3, 3, 3, 3, 3]]
