"""``ops/delta_rule.py``: the chunked gated delta rule (one decay a key
channel) against the recurrence taken token by token, forward and
gradient, under the harness's weights' regime (the state halves every
token) and the published initialisation's (a state lives hundreds of
tokens), at lengths that are and are not multiples of the chunk, on
packed rows whose documents end inside a chunk and inside a 16-token
sub-block; and, with the decay made equal over a head's channels,
against ``transformers``' own recurrent gated delta rule. Every test of
the chunked form runs by both of its paths (``path``): the XLA
products at tiny heads, and the two Pallas kernels (heads of 128, a
whole lane, under the TPU interpreter: ``interpreted_kernels``)."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realhf_tpu.ops import delta_rule as D

H, DK, DV = 2, 16, 8
PATHS = ["xla", "kernel"]


@pytest.fixture(params=PATHS)
def path(request, interpreted_kernels):
    """``(chunked, widths)``: ``chunked_delta_rule`` as this path runs
    it and the heads' ``dict(dk=, dv=, h=)`` it is run at. The kernels
    take heads of whole lanes only (a narrower head goes down the XLA
    path by ``kernel_takes``), so their cases run at 128."""
    if request.param == "xla":
        assert not D.pallas_enabled()
        yield D.chunked_delta_rule, dict(dk=DK, dv=DV, h=H)
        return
    # (a gradient's backward kernel is traced after the forward call
    # has returned: the whole test runs under the interpreter)
    with interpreted_kernels():
        yield D.chunked_delta_rule, dict(dk=128, dv=128, h=1)


def two_heads(widths):
    """The gradients' cases run at two heads on both paths, each with
    a decay of its own: a head's columns of ``[L, H x d]``, its rows of
    beta and of the decay's two tensors, and the sum of d of those
    over a row's chunks are addressed by the kernels' block specs."""
    return dict(widths, h=H)


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
def test_chunked_equals_token_by_token(path, regime, l, ends):
    chunked, widths = path
    seg = segments(l, *ends)
    x = inputs(7, 1, l, regime, **widths)
    with jax.default_matmul_precision("highest"):
        o, last = chunked(*x, jnp.asarray(seg))
    want_o, want_last = token_by_token(*x, seg)
    valid = (seg != 0)[..., None, None]
    scale = float(jnp.abs(want_o).max())
    assert float(jnp.abs(jnp.where(valid, o - want_o, 0)).max()) \
        < 2e-5 * scale
    assert float(jnp.abs(last - want_last).max()) \
        < 2e-5 * max(1.0, float(jnp.abs(want_last).max()))


def test_left_padding_leaves_the_state_at_zero_until_the_document(path):
    chunked, widths = path
    seg = np.zeros((1, 96), np.int32)
    seg[0, 37:] = 1
    x = inputs(3, 1, 96, "published", **widths)
    with jax.default_matmul_precision("highest"):
        o, last = chunked(*x, jnp.asarray(seg))
        o1, last1 = chunked(
            *(a[:, 37:] for a in x), jnp.asarray(seg[:, 37:]))
    np.testing.assert_allclose(o[:, 37:], o1, atol=2e-6)
    np.testing.assert_allclose(last, last1, atol=2e-6)


@pytest.mark.parametrize("regime", ["harness", "published"])
def test_gradients_equal_the_recurrences(path, regime):
    """d of q, k, v, g and beta, of the outputs AND of the last state
    (the kernels' backward starts from its cotangent)."""
    chunked, widths = path
    widths = two_heads(widths)
    l = 600  # two rematerialised segments of five chunks
    seg = segments(l, 23, 301, 560)
    x = inputs(11, 2, l, regime, **widths)
    seg = np.concatenate([seg, segments(l, l)])
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.normal(size=x[2].shape),
                    jnp.float32) * jnp.asarray(seg != 0)[..., None, None]
    w_last = jnp.asarray(rng.normal(size=(
        2, widths["h"], widths["dk"], widths["dv"])), jnp.float32)

    def loss(fn, *a):
        o, last = fn(*a, jnp.asarray(seg) if fn is chunked else seg)
        return (o * w).sum() + (last * w_last).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: loss(chunked, *a),
                       argnums=(0, 1, 2, 3, 4))(*x)
    want = jax.grad(lambda *a: loss(token_by_token, *a),
                    argnums=(0, 1, 2, 3, 4))(*x)
    for name, a, b_ in zip("q k v g beta".split(), got, want):
        ok = jnp.asarray(seg != 0).reshape(2, l, *([1] * (a.ndim - 2)))
        err = float(jnp.abs(jnp.where(ok, a - b_, 0)).max())
        assert err < 5e-5 * float(jnp.abs(b_).max()), (name, err)
        assert bool(jnp.isfinite(a).all()), name


def test_gradients_reach_what_prepare_reads(path):
    """Through a layer's ``Prepare`` (its l2 norm and its decay from
    a pre-activation) to the two tensors the decay is made of, on a
    row with padding, against the same ``Prepare`` before the
    recurrence token by token."""
    chunked, widths = path
    widths = two_heads(widths)
    l = 150
    seg = segments(l, 70, 140)
    q, k, v, f, beta = inputs(23, 1, l, "published", **widths)
    h, dk = widths["h"], widths["dk"]
    rng = np.random.default_rng(29)
    a_log = jnp.asarray(rng.uniform(0, 2, size=(h,)), jnp.float32)
    dt_bias = jnp.asarray(rng.normal(size=(h, dk)) - 3, jnp.float32)
    w = jnp.asarray(rng.normal(size=v.shape), jnp.float32) \
        * jnp.asarray(seg != 0)[..., None, None]

    def prepare_of(a_log, dt_bias):
        # (the kernels apply THIS one themselves, a chunk at a time)
        return D.Prepare(rate=-jnp.exp(a_log), dt_bias=dt_bias,
                         scale=dk ** -0.5, eps=1e-6)

    def inside(a_log, dt_bias, q, k, f):
        return (chunked(q, k, v, f, beta, jnp.asarray(seg),
                        prepare=prepare_of(a_log, dt_bias))[0] * w).sum()

    def before(a_log, dt_bias, q, k, f):
        return (token_by_token(*prepare_of(a_log, dt_bias)(q, k, f)[:2], v,
                               prepare_of(a_log, dt_bias)(q, k, f)[2], beta,
                               seg)[0] * w).sum()

    args = (a_log, dt_bias, q * 3.0, k * 0.5, f)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(inside, argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(before, argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b_ in zip("a_log dt_bias q k f".split(), got, want):
        err = float(jnp.abs(a - b_).max())
        assert err < 1e-4 * float(jnp.abs(b_).max()), (name, err)


def _pairs_and_inverse_by_xla(q, k, g, beta, seg):
    """The XLA form's ``(a, bm, x)`` of every chunk, [B, H, N, C, C]
    each: ``_pair_products`` with a document's masks applied and
    ``_unit_lower_inverse`` of ``beta a``, as ``_segment`` makes them
    (a padding token: no decay, no step, the document before it)."""
    b, l, h, _ = k.shape
    n, c = l // D.CHUNK, D.CHUNK
    valid = jnp.asarray(seg != 0)
    g = jnp.where(valid[..., None, None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)

    def chunks(x):  # [B, L, H, d] -> [B, H, N, C, d]
        return x.reshape(b, n, c, h, -1).transpose(0, 3, 1, 2, 4)

    doc = D.doc_index(jnp.asarray(seg)).reshape(b, 1, n, c)
    same = doc[..., :, None] == doc[..., None, :]
    kk, qk = D._pair_products(chunks(q), chunks(k),
                              jnp.cumsum(chunks(g), axis=-2))
    a = jnp.where(same & jnp.tril(jnp.ones((c, c), bool), -1), kk, 0.0)
    bm = jnp.where(same & jnp.tril(jnp.ones((c, c), bool)), qk, 0.0)
    beta = chunks(beta[..., None])[..., 0]
    return a, bm, D._unit_lower_inverse(beta[..., :, None] * a)


@pytest.mark.parametrize("regime", ["harness", "published"])
@pytest.mark.parametrize("ends,pad_left", [
    ((23, 301, 560), 0),   # documents end inside chunks, padding after
    ((640,), 37),          # padding before the one document
], ids=["packed", "left_padding"])
def test_the_backward_is_handed_what_the_forward_made(
        interpreted_kernels, monkeypatch, regime, ends, pad_left):
    """Under a gradient the forward kernel keeps every chunk's masked
    pairs and triangular inverse (``RESIDUAL_NAMES[1]``, laid out by
    ``_hand_over``) and the backward kernel makes neither again: what
    it trusts is what the XLA form makes of the same row."""
    l, h = 640, 2  # two blocks of five chunks
    seg = segments(l, *ends)
    seg[0, :pad_left] = 0
    q, k, v, g, beta = inputs(37, 1, l, regime, dk=128, dv=128, h=h)
    made = []
    forward_call = D._forward_call

    def recorded(static, keep_starts, *operands):
        made.append(forward_call(static, keep_starts, *operands))
        return made[-1]

    monkeypatch.setattr(D, "_forward_call", recorded)
    with interpreted_kernels(), jax.default_matmul_precision("highest"):
        jax.vjp(lambda *a: D.chunked_delta_rule(*a, jnp.asarray(seg)),
                q, k, v, g, beta)
    (_, _, starts, handed), = made
    assert handed.shape == (1, h, l // D.CHUNK, 96, 128)
    assert starts.shape == (1, h, l // D.CHUNK, 128, 128)
    got = jax.vmap(jax.vmap(jax.vmap(
        lambda block: D._handed(block[None], 0))))(handed)
    with jax.default_matmul_precision("highest"):
        want = _pairs_and_inverse_by_xla(q, k, g, beta, seg)
    for name, a, b_ in zip(("a", "bm", "x"), got, want):
        assert a.shape == b_.shape == (1, h, l // D.CHUNK, 64, 64), name
        err = float(jnp.abs(a - b_).max())
        assert err < 1e-5 * float(jnp.abs(b_).max()), (name, err)
    # strictly lower, lower, unit lower: the masks are in what is kept
    a, bm, x = (np.asarray(m) for m in got)
    assert not np.triu(a).any() and not np.triu(bm, 1).any()
    assert not np.triu(x, 1).any()
    assert (np.diagonal(x, axis1=-2, axis2=-1) == 1).all()


def _kernel_outputs(jaxpr):
    """How many arrays each ``pallas_call`` of a jaxpr writes, in
    order, those of its sub-jaxprs among them."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(len(eqn.outvars))
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_kernel_outputs(sub))
    return found


def test_only_a_gradients_forward_writes_what_the_backward_reads(
        interpreted_kernels):
    """The primal scan (inference, prefill) writes the outputs and the
    last state and nothing else; under a gradient the forward kernel
    also writes ``RESIDUAL_NAMES``, which the backward kernel takes
    beside the operands and the two cotangents."""
    l = 128
    x = inputs(41, 1, l, "published", dk=128, dv=128, h=1)
    seg = jnp.ones((1, l), jnp.int32)

    def scan(*a):
        return D.chunked_delta_rule(*a, seg)

    with interpreted_kernels():
        primal = jax.make_jaxpr(scan)(*x)
        gradient = jax.make_jaxpr(jax.grad(
            lambda *a: scan(*a)[0].sum(), argnums=(0, 1, 2, 3, 4)))(*x)
    assert _kernel_outputs(primal.jaxpr) == [2]
    assert _kernel_outputs(gradient.jaxpr) == [
        2 + len(D.RESIDUAL_NAMES), 6]
    assert D.RESIDUAL_NAMES == ("delta_starts", "delta_pairs")


#: one backward kernel of a compiled program as ``scan_handed`` reads
#: it: the seven operands, what the forward kept, the two cotangents
_BWD_LINE = (
    "  %transpose_jvp_delta_bwd__.{i} = (bf16[1,2048,4096]{{2,1,0:T(8,128)"
    "(2,1)}}, f32[1,32,2,128]{{3,2,1,0:T(2,128)}}) custom-call({operands}),"
    " custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
    "{{s32[1,4,32,64]{{3,2,1,0}}, f32[1,32,32,64]{{3,2,1,0}}}}\n")


@pytest.mark.parametrize("kept,handed", [
    ((), 0), (("%pallas_call.15",), 1),
    (("%pallas_call.15", "f32[1,32,32,96,128]{4,3,2,1,0} %gte.16"), 2)],
    ids=["none", "starts_alone", "starts_and_pairs"])
def test_scan_handed_counts_what_a_backward_kernel_takes(kept, handed):
    """``scan_handed`` on a compiled program's text: a backward call's
    operands besides the scan's seven and the two cotangents, the
    least over the calls; 0 without a backward kernel."""
    operands = ["%copy-done.4", "%copy-done.3", "%reshape.18",
                "%reshape.19", "%reshape.20", "/*index=5*/%reshape.21",
                "%constant.13", *kept, "%broadcast.8", "%transpose.3"]
    text = "ENTRY %main (p: f32[2]) -> f32[2] {\n" + "".join(
        _BWD_LINE.format(i=i, operands=", ".join(operands))
        for i in range(2)) + "}\n"
    if not kept:  # a forward kernel alone
        text = text.replace("transpose_jvp_delta_bwd", "jvp_delta_fwd")
    assert D.scan_handed(text) == handed
    assert D.scan_kernel_calls(text) == 2
    more = text.replace(f"{operands[-3]}, %broadcast.8",
                        f"{operands[-3]}, %more.1, %broadcast.8", 1)
    assert D.scan_handed(more) == handed  # the LEAST over the calls


def test_no_exponent_overflows_where_the_factored_form_would(path):
    """1.6 a token over a chunk is exp(102) in the factored form:
    float32 ends at exp(88.7). Here every exponent is <= 0."""
    chunked, widths = path
    l = 128
    q, k, v, g, beta = inputs(2, 1, l, "published", **widths)
    g = jnp.full_like(g, -1.7)
    seg = jnp.ones((1, l), jnp.int32)
    with jax.default_matmul_precision("highest"):
        o, last = chunked(q, k, v, g, beta, seg)
        grads = jax.grad(lambda g_: chunked(
            q, k, v, g_, beta, seg)[0].sum())(g)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(last).all())
    want, _ = token_by_token(q, k, v, g, beta, np.ones((1, l), np.int32))
    np.testing.assert_allclose(o, want, atol=1e-5)
    assert bool(jnp.isfinite(grads).all())


def test_a_step_at_a_time_continues_the_chunked_state(path):
    chunked, widths = path
    l = 90
    x = inputs(13, 2, l + 5, "published", **widths)
    seg = jnp.ones((2, l), jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, state = chunked(*(a[:, :l] for a in x), seg)
        whole, _ = chunked(*x, jnp.ones((2, l + 5), jnp.int32))
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


def test_prepare_runs_where_the_path_computes(path):
    """``prepare`` (a layer's l2 norm and decay) applied inside equals
    applying it before; the output takes the values' dtype, the state
    stays float32; operands in bf16 (as the engine hands them over)
    are taken to float32 inside."""
    chunked, widths = path
    l = 600
    q, k, v, g, beta = inputs(19, 1, l, "published", **widths)
    seg = jnp.asarray(segments(l, 250, 590))
    raw = (q * 3.0, k * 0.5, g * 2.0)
    prepare = lambda q_, k_, g_: (q_ / 3.0, k_ / 0.5, g_ / 2.0)
    with jax.default_matmul_precision("highest"):
        want, want_last = chunked(q, k, v, g, beta, seg)
        got, last = chunked(*raw[:2], v, raw[2], beta, seg,
                            prepare=prepare)
        half, last16 = chunked(
            *raw[:2], v.astype(jnp.bfloat16), raw[2], beta, seg,
            prepare=prepare)
        bf16 = lambda x: x.astype(jnp.bfloat16)
        all_half, _ = chunked(bf16(q), bf16(k), bf16(v), bf16(g), beta, seg)
        as_rounded, _ = chunked(*(bf16(x).astype(jnp.float32)
                                  for x in (q, k, v, g)), beta, seg)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(last, want_last, atol=2e-6)
    assert half.dtype == jnp.bfloat16 and last16.dtype == jnp.float32
    np.testing.assert_allclose(half.astype(jnp.float32), want, atol=2e-2)
    assert all_half.dtype == jnp.bfloat16
    np.testing.assert_allclose(all_half.astype(jnp.float32), as_rounded,
                               atol=1e-2)


def test_a_segment_is_four_chunks_of_64():
    """What the eighth cell's size hangs on: the compiler counts its
    whole train step at 13.19 GB with a segment of 4 chunks and 13.56
    with 8, of the 13.9 a program is held to (``-m slow``:
    ``tests/ops/test_chip_compile.py::
    test_kimis_whole_train_step_compiles``); and the lengths above
    cross the boundaries they say only while these hold."""
    assert (D.CHUNK, D.SUB, D.SEGMENT_CHUNKS) == (64, 16, 4)


def test_the_kernels_take_whole_lanes_and_the_rest_goes_by_xla(
        interpreted_kernels, monkeypatch):
    """A head that is no multiple of 128 wide goes down the XLA path
    where the kernels are enabled: by what the call can see of its
    operands, not by a setting."""
    assert D.kernel_takes(128, 128) and D.kernel_takes(128, 256)
    assert not D.kernel_takes(64, 128) and not D.kernel_takes(128, 192)
    monkeypatch.setattr(D, "pallas_enabled", lambda: True)
    x = inputs(31, 1, 70, "published")
    seg = jnp.ones((1, 70), jnp.int32)
    text = jax.jit(D.chunked_delta_rule).lower(*x, seg).as_text()
    assert "delta_fwd" not in text and "tpu_custom_call" not in text
    with interpreted_kernels():
        wide = inputs(31, 1, 70, "published", dk=128, dv=128, h=1)
        lowered = jax.jit(D.chunked_delta_rule).lower(*wide, seg)
    assert D.DELTA_FWD in lowered.as_text(debug_info=True)


def test_doc_index_counts_padding_with_the_document_before():
    seg = jnp.asarray([[0, 0, 3, 3, 0, 7, 7, 0], [1, 2, 2, 4, 0, 0, 0, 0]])
    assert D.doc_index(seg).tolist() == [[0, 0, 1, 1, 1, 2, 2, 2],
                                         [1, 2, 2, 3, 3, 3, 3, 3]]
