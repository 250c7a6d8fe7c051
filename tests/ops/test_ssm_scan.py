"""``ops/ssm_scan.py``: the chunked Mamba-2 scan against the recurrence
taken token by token, outputs, last states and every gradient, on packed
rows whose document boundaries fall INSIDE a chunk, with padding on
either side, and one token at a time through ``ssm_step``. Every test
of the chunked form runs by both of its paths (``path``): the XLA
products at tiny heads, and the two Pallas kernels (groups of two heads
of 64 and a state of 128, whole lanes, under the TPU interpreter:
``interpreted_kernels``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realhf_tpu.ops import ssm_scan as S
from realhf_tpu.ops.ssm_scan import CHUNK, SEGMENT_CHUNKS, chunked_ssm_scan, \
    ssm_step

H, G = 4, 2
PATHS = ["xla", "kernels"]


@pytest.fixture(params=PATHS)
def widths(request, interpreted_kernels):
    """``dict(p=, n=)``: the head's and the state's width this path is
    run at. The kernels take a group of whole lanes and a state of
    whole lanes only (anything narrower goes down the XLA path by
    ``kernel_takes``), so their cases run at 2 x 64 and 128."""
    if request.param == "xla":
        assert not S.pallas_enabled()
        yield dict(p=8, n=16)
        return
    # (a gradient's backward kernel is traced after the forward call
    # has returned: the whole test runs under the interpreter)
    with interpreted_kernels():
        assert S.pallas_enabled() and S.kernel_takes(H // G, 64, 128)
        yield dict(p=64, n=128)


def _operands(seed, b, l, decay=1.0, p=8, n=16):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return dict(
        x=f(b, l, H, p), dt=f(b, l, H), b=f(b, l, G, n), c=f(b, l, G, n),
        rate=-jnp.exp(decay * f(H)), dt_bias=f(H), skip=1.0 + f(H))


def _scan(ops, seg, fn=chunked_ssm_scan):
    return fn(ops["x"], ops["dt"], ops["b"], ops["c"], seg,
              rate=ops["rate"], dt_bias=ops["dt_bias"], skip=ops["skip"])


def _recurrence(x, dt, b, c, seg, *, rate, dt_bias, skip):
    """Token by token, a ``lax.scan`` over positions that carries S:
    zero at a document's first token, left as it is by padding."""
    bsz, l, _, p = x.shape
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
        token, jnp.zeros((bsz, H, p, b.shape[-1]), jnp.float32),
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
def test_chunked_scan_is_the_recurrence(widths, kind, l):
    """Outputs at every valid token and the state after the row, over
    more than one chunk (and, at the longer row, more than one segment
    of the XLA path and five blocks of the kernels' grid, the last one
    padded up)."""
    ops = _operands(0, 2, l, **widths)
    seg = jnp.asarray(_rows(l, kind))
    with jax.default_matmul_precision("highest"):
        y, last = jax.jit(_scan)(ops, seg)
        want, want_last = jax.jit(
            lambda ops, seg: _scan(ops, seg, _recurrence))(ops, seg)
    live = np.asarray(seg != 0)[..., None, None]
    # (float32 on both sides: 1e-5 of the largest output, which grows
    # with a head's width)
    atol = max(2e-4, 1e-5 * float(np.abs(want).max()))
    np.testing.assert_allclose(np.where(live, y, 0), np.where(live, want, 0),
                               rtol=2e-4, atol=atol)
    np.testing.assert_allclose(last, want_last, rtol=2e-4, atol=atol)


NAMES = ("x", "dt", "b", "c", "rate", "dt_bias", "skip")


def _gradients(ops, seg, wy, ws):
    """(d of every operand and leaf by the chunked scan, by the
    recurrence) of a loss over the outputs and the last state."""
    def loss(fn, *args):
        y, last = _scan(dict(zip(NAMES, args)), seg, fn)
        return (y * wy).sum() + (last * ws).sum()

    args = tuple(ops[n] for n in NAMES)
    with jax.default_matmul_precision("highest"):
        return tuple(jax.jit(jax.grad(
            lambda *a, fn=fn: loss(fn, *a),
            argnums=tuple(range(len(NAMES)))))(*args)
            for fn in (chunked_ssm_scan, _recurrence))


def _assert_close(got, want, atol):
    for name, g, w in zip(NAMES, got, want):
        assert np.isfinite(np.asarray(g)).all(), name
        scale = float(np.abs(w).max()) + 1e-6
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=atol,
                                   err_msg=name)


@pytest.mark.parametrize("decay", (1.0, 4.0))
def test_gradients_are_the_recurrences(widths, decay):
    """Every operand's and every leaf's gradient of a loss over the
    valid tokens' outputs and the last state, boundaries inside chunks
    and padding; ``decay`` 4: rates up to e^4 a token, where ``exp(G_t)
    exp(-G_s)`` would have left float32."""
    l = 300
    ops = _operands(1, 2, l, decay, **widths)
    seg = _rows(l, "boundaries_inside_chunks")
    seg[1, 280:] = 0
    seg = jnp.asarray(seg)
    rng = np.random.default_rng(2)
    wy = jnp.asarray(rng.standard_normal(ops["x"].shape), jnp.float32) \
        * (seg != 0)[..., None, None]
    ws = jnp.asarray(rng.standard_normal(
        (2, H, widths["p"], widths["n"])), jnp.float32)
    _assert_close(*_gradients(ops, seg, wy, ws), atol=3e-4)


def test_gradients_reach_the_three_leaves_a_head(widths):
    """d of ``rate``, ``dt_bias`` and ``skip`` head by head, summed
    over two rows that are packed differently (one padded on the left,
    one on the right) and over the chunks of each, from a loss on the
    LAST STATE alone and from one on the outputs alone: the first
    reaches the leaves only through the carry between chunks."""
    l = 2 * CHUNK + 40
    ops = _operands(5, 2, l, **widths)
    seg = np.ones((2, l), np.int32)
    seg[0, :9], seg[0, 140:] = 0, 2
    seg[1, 200:], seg[1, l - 30:] = 2, 0
    seg = jnp.asarray(seg)
    rng = np.random.default_rng(6)
    wy = jnp.asarray(rng.standard_normal(ops["x"].shape), jnp.float32) \
        * (seg != 0)[..., None, None]
    ws = jnp.asarray(rng.standard_normal(
        (2, H, widths["p"], widths["n"])), jnp.float32)
    for weights in ((jnp.zeros_like(wy), ws), (wy, jnp.zeros_like(ws))):
        got, want = _gradients(ops, seg, *weights)
        for name, g, w in list(zip(NAMES, got, want))[4:]:
            # (D reaches the outputs alone, the other two both)
            live = name != "skip" or weights[1] is not ws
            assert g.shape == (H,) and live == bool(jnp.abs(w).min() > 0)
            np.testing.assert_allclose(g, w, rtol=2e-3, err_msg=name)


def test_a_document_does_not_see_the_one_before_it(widths):
    """The second document of a packed row computes what it computes
    alone, and the row's last state is its own."""
    l = 260
    ops = _operands(3, 1, l, **widths)
    seg = np.ones((1, l), np.int32)
    seg[0, 77:] = 2
    cut = dict(ops, **{k: ops[k][:, 77:] for k in ("x", "dt", "b", "c")})
    with jax.default_matmul_precision("highest"):
        y, last = _scan(ops, jnp.asarray(seg))
        alone, alone_last = _scan(cut, jnp.ones((1, l - 77), jnp.int32))
    np.testing.assert_allclose(y[:, 77:], alone, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(last, alone_last, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("side", ["left", "right"])
def test_padding_leaves_the_state_as_it_is(widths, side):
    """A row padded on either side, by less and by more than a chunk,
    gives its document the outputs and the last state the document
    has alone: a padding token takes no step, before the document
    (the state stays 0) and after it (the state stays the document's
    last)."""
    l, pad = 150, CHUNK + 37
    ops = _operands(7, 1, l, **widths)
    zeros = lambda t: jnp.zeros((1, pad) + t.shape[2:], t.dtype)
    join = (lambda t: jnp.concatenate([zeros(t), t], axis=1)) \
        if side == "left" else (
            lambda t: jnp.concatenate([t, zeros(t)], axis=1))
    padded = dict(ops, **{k: join(ops[k]) for k in ("x", "dt", "b", "c")})
    seg = join(jnp.ones((1, l), jnp.int32))
    with jax.default_matmul_precision("highest"):
        want, want_last = _scan(ops, jnp.ones((1, l), jnp.int32))
        y, last = _scan(padded, seg)
    mine = slice(pad, None) if side == "left" else slice(0, l)
    np.testing.assert_allclose(y[:, mine], want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(last, want_last, rtol=1e-4, atol=1e-4)


def test_no_exponent_overflows_where_the_factored_form_would(widths):
    """A state that more than halves every token: 1.7 a token over a
    chunk of 128 is exp(218) in the factored form ``exp(G_t) exp(-G_s)``
    and float32 ends at exp(88.7). Here the difference is taken first:
    every exponent is <= 0, outputs and gradients are finite and the
    recurrence's."""
    l = 2 * CHUNK
    ops = _operands(8, 1, l, **widths)
    ops["dt"] = jnp.zeros_like(ops["dt"])
    ops["dt_bias"] = jnp.full((H,), float(np.log(np.expm1(1.0))))  # Delta 1
    ops["rate"] = jnp.full((H,), -1.7)
    seg = jnp.ones((1, l), jnp.int32)
    wy = jnp.ones_like(ops["x"])
    ws = jnp.ones((1, H, widths["p"], widths["n"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, last = _scan(ops, seg)
        want, _ = _scan(ops, seg, _recurrence)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(last).all())
    np.testing.assert_allclose(y, want, rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(want).max()))
    _assert_close(*_gradients(ops, seg, wy, ws), atol=3e-4)


def test_steps_continue_the_scan(widths):
    """The state after a prefix, moved on a token at a time by
    ``ssm_step``, gives the outputs the scan gives over the whole
    row."""
    l, cut = 200, 150
    ops = _operands(4, 2, l, **widths)
    seg = jnp.ones((2, l), jnp.int32)
    kw = dict(rate=ops["rate"], dt_bias=ops["dt_bias"], skip=ops["skip"])
    head = dict(ops, **{k: ops[k][:, :cut] for k in ("x", "dt", "b", "c")})
    with jax.default_matmul_precision("highest"):
        want, want_last = _scan(ops, seg)
        _, state = _scan(head, seg[:, :cut])
        for t in range(cut, l):
            y, state = ssm_step(ops["x"][:, t], ops["dt"][:, t],
                                ops["b"][:, t], ops["c"][:, t], state, **kw)
            np.testing.assert_allclose(y, want[:, t], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, want_last, rtol=2e-4, atol=2e-4)


def test_operands_in_bf16_are_taken_to_float32_inside(widths):
    """The output takes x's dtype, the state stays float32, and bf16
    operands (as the engine hands them over) give what their rounded
    values give in float32."""
    l = 200
    ops = _operands(9, 1, l, **widths)
    seg = jnp.ones((1, l), jnp.int32)
    bf16 = lambda t: t.astype(jnp.bfloat16)
    half = dict(ops, **{k: bf16(ops[k]) for k in ("x", "dt", "b", "c")})
    rounded = {k: v.astype(jnp.float32) for k, v in half.items()}
    with jax.default_matmul_precision("highest"):
        y, last = _scan(half, seg)
        want, want_last = _scan(rounded, seg)
    assert y.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    np.testing.assert_allclose(y.astype(jnp.float32), want, rtol=1e-2,
                               atol=1e-2)
    np.testing.assert_allclose(last, want_last, rtol=1e-4, atol=1e-4)


def test_the_kernels_take_whole_lanes_and_the_rest_goes_by_xla(
        interpreted_kernels, monkeypatch):
    """A group of heads or a state that is no multiple of 128 wide goes
    down the XLA path where the kernels are enabled: by what the call
    can see of its operands, not by a setting."""
    assert S.kernel_takes(8, 64, 128) and S.kernel_takes(2, 64, 256)
    assert S.kernel_takes(1, 128, 128)
    assert not S.kernel_takes(1, 64, 128)   # a group of 64 columns
    assert not S.kernel_takes(8, 64, 64)    # a state of half a lane tile
    assert not S.kernel_takes(32, 4, 128)   # a head of half a sublane tile
    monkeypatch.setattr(S, "pallas_enabled", lambda: True)
    seg = jnp.ones((1, 70), jnp.int32)
    text = jax.jit(_scan).lower(_operands(31, 1, 70), seg).as_text()
    assert S.SSM_FWD not in text and "tpu_custom_call" not in text
    with interpreted_kernels():
        lowered = jax.jit(_scan).lower(_operands(31, 1, 70, p=64, n=128),
                                       seg)
    assert S.SSM_FWD in lowered.as_text(debug_info=True)


def test_a_chunk_is_128_and_a_block_of_the_kernels_four():
    """What the tenth cell's time and size hang on (PERF.md, PR 49):
    the kernels' VMEM at the cell's shape is compiled for the described
    chip in ``tests/ops/test_chip_compile.py``; and the lengths above
    cross the boundaries they say only while these hold."""
    assert (S.CHUNK, S.SEGMENT_CHUNKS, S.BLOCK_CHUNKS) == (128, 8, 4)
    assert S._blocks(32, jnp.bfloat16) == (8, 4)
    assert S._blocks(32, jnp.float32) == (16, 2)
    assert S._blocks(10, jnp.float32) == (5, 2)
    assert S._blocks(3, jnp.bfloat16) == (1, 3)
