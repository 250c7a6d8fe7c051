"""``ops/ssm_scan.py``: the chunked Mamba-2 scan against the recurrence
taken token by token, outputs, last states and every gradient, on packed
rows whose document boundaries fall INSIDE a chunk, with padding on
either side, and one token at a time through ``ssm_step``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realhf_tpu.ops.ssm_scan import CHUNK, SEGMENT_CHUNKS, chunked_ssm_scan, \
    ssm_step

H, G, P, N = 4, 2, 8, 16


def _operands(seed, b, l, decay=1.0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return dict(
        x=f(b, l, H, P), dt=f(b, l, H), b=f(b, l, G, N), c=f(b, l, G, N),
        rate=-jnp.exp(decay * f(H)), dt_bias=f(H), skip=1.0 + f(H))


def _recurrence(x, dt, b, c, seg, rate, dt_bias, skip):
    """Token by token, a ``lax.scan`` over positions that carries S:
    zero at a document's first token, left as it is by padding."""
    bsz, l = seg.shape
    before = jnp.pad(seg, ((0, 0), (1, 0)))[:, :-1]
    first = (seg != 0) & (seg != before)
    delta = jax.nn.softplus(dt + dt_bias)
    bh, ch = (jnp.repeat(t, H // G, axis=2) for t in (b, c))

    def token(s, t):
        xt, dl, bt, ct, new, live = t
        s0 = jnp.where(new[:, None, None, None], 0.0, s)
        nxt = s0 * jnp.exp(dl * rate)[..., None, None] \
            + (dl[..., None] * xt)[..., None] * bt[:, :, None, :]
        nxt = jnp.where(live[:, None, None, None], nxt, s)
        return nxt, (nxt * ct[:, :, None, :]).sum(-1) + xt * skip[:, None]

    by_token = lambda t: jnp.moveaxis(t, 1, 0)
    last, y = jax.lax.scan(
        token, jnp.zeros((bsz, H, P, N), jnp.float32),
        tuple(map(by_token, (x, delta, bh, ch, first, seg != 0))))
    return jnp.moveaxis(y, 0, 1), last


def _rows(l, kind):
    if kind == "one_document":
        return np.ones((2, l), np.int32)
    if kind == "boundaries_inside_chunks":
        # 3 documents a row, no boundary on a multiple of 128
        seg = np.ones((2, l), np.int32)
        seg[0, 70:200] = 2
        seg[0, 200:] = 3
        seg[1, 5:131] = 2
        seg[1, 131:] = 3
        return seg
    if kind == "padded_right":
        seg = np.ones((2, l), np.int32)
        seg[0, 100:190] = 2
        seg[0, 190:] = 0
        seg[1, l - 3:] = 0
        return seg
    assert kind == "padded_left"
    seg = np.ones((2, l), np.int32)
    seg[0, :37] = 0
    seg[0, 150:] = 2
    seg[1, :1] = 0
    return seg


KINDS = ("one_document", "boundaries_inside_chunks", "padded_right",
         "padded_left")


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("l", (300, CHUNK * (SEGMENT_CHUNKS + 1) + 17))
def test_chunked_scan_is_the_recurrence(kind, l):
    """Outputs at every valid token and the state after the row, over
    more than one chunk (and, at the longer row, more than one
    segment)."""
    ops = _operands(0, 2, l)
    seg = jnp.asarray(_rows(l, kind))
    with jax.default_matmul_precision("highest"):
        y, last = jax.jit(chunked_ssm_scan)(
            ops["x"], ops["dt"], ops["b"], ops["c"], seg,
            rate=ops["rate"], dt_bias=ops["dt_bias"], skip=ops["skip"])
        want, want_last = jax.jit(_recurrence)(
            ops["x"], ops["dt"], ops["b"], ops["c"], seg, ops["rate"],
            ops["dt_bias"], ops["skip"])
    live = np.asarray(seg != 0)[..., None, None]
    np.testing.assert_allclose(np.where(live, y, 0), np.where(live, want, 0),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(last, want_last, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("decay", (1.0, 4.0))
def test_gradients_are_the_recurrences(decay):
    """Every operand's and every leaf's gradient of a loss over the
    valid tokens' outputs and the last state, boundaries inside chunks
    and padding; ``decay`` 4: rates up to e^4 a token, where ``exp(G_t)
    exp(-G_s)`` would have left float32."""
    l = 300
    ops = _operands(1, 2, l, decay)
    seg = _rows(l, "boundaries_inside_chunks")
    seg[1, 280:] = 0
    seg = jnp.asarray(seg)
    rng = np.random.default_rng(2)
    wy = jnp.asarray(rng.standard_normal((2, l, H, P)), jnp.float32) \
        * (seg != 0)[..., None, None]
    ws = jnp.asarray(rng.standard_normal((2, H, P, N)), jnp.float32)
    names = ("x", "dt", "b", "c", "rate", "dt_bias", "skip")

    def loss(fn, *args):
        kw = dict(zip(names, args))
        y, last = fn(kw["x"], kw["dt"], kw["b"], kw["c"], seg,
                     rate=kw["rate"], dt_bias=kw["dt_bias"],
                     skip=kw["skip"])
        return (y * wy).sum() + (last * ws).sum()

    def by_recurrence(x, dt, b, c, seg, *, rate, dt_bias, skip):
        return _recurrence(x, dt, b, c, seg, rate, dt_bias, skip)

    args = tuple(ops[n] for n in names)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(
            lambda *a: loss(chunked_ssm_scan, *a),
            argnums=tuple(range(len(names)))))(*args)
        want = jax.jit(jax.grad(
            lambda *a: loss(by_recurrence, *a),
            argnums=tuple(range(len(names)))))(*args)
    for name, g, w in zip(names, got, want):
        assert np.isfinite(np.asarray(g)).all(), name
        scale = float(np.abs(w).max()) + 1e-6
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=3e-4,
                                   err_msg=name)


def test_a_document_does_not_see_the_one_before_it():
    """The second document of a packed row computes what it computes
    alone, and the row's last state is its own."""
    l = 260
    ops = _operands(3, 1, l)
    seg = np.ones((1, l), np.int32)
    seg[0, 77:] = 2
    kw = dict(rate=ops["rate"], dt_bias=ops["dt_bias"], skip=ops["skip"])
    with jax.default_matmul_precision("highest"):
        y, last = chunked_ssm_scan(ops["x"], ops["dt"], ops["b"], ops["c"],
                                   jnp.asarray(seg), **kw)
        cut = lambda t: t[:, 77:]
        alone, alone_last = chunked_ssm_scan(
            cut(ops["x"]), cut(ops["dt"]), cut(ops["b"]), cut(ops["c"]),
            jnp.ones((1, l - 77), jnp.int32), **kw)
    np.testing.assert_allclose(y[:, 77:], alone, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(last, alone_last, rtol=1e-4, atol=1e-4)


def test_steps_continue_the_scan():
    """The state after a prefix, moved on a token at a time by
    ``ssm_step``, gives the outputs the scan gives over the whole
    row."""
    l, cut = 200, 150
    ops = _operands(4, 2, l)
    seg = jnp.ones((2, l), jnp.int32)
    kw = dict(rate=ops["rate"], dt_bias=ops["dt_bias"], skip=ops["skip"])
    with jax.default_matmul_precision("highest"):
        want, want_last = chunked_ssm_scan(
            ops["x"], ops["dt"], ops["b"], ops["c"], seg, **kw)
        _, state = chunked_ssm_scan(
            ops["x"][:, :cut], ops["dt"][:, :cut], ops["b"][:, :cut],
            ops["c"][:, :cut], seg[:, :cut], **kw)
        for t in range(cut, l):
            y, state = ssm_step(ops["x"][:, t], ops["dt"][:, t],
                                ops["b"][:, t], ops["c"][:, t], state, **kw)
            np.testing.assert_allclose(y, want[:, t], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, want_last, rtol=2e-4, atol=2e-4)
