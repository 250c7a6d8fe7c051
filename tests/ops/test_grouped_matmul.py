"""``ops/grouped_matmul.py`` in interpret mode on the CPU: the three
kernels against a loop over groups in float64, and the contract for
the rows no group covers (zero in the result and in the rows'
gradient, nothing in the weights', whatever lies there). The
interpreter fills what a kernel never writes with NaN, so a row the
kernels left to chance shows here."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from realhf_tpu.ops import grouped_matmul as gm
from realhf_tpu.ops import moe as moe_ops

from test_moe import held_leaves, share_cfg, share_layer

K, N = 64, 96


@pytest.fixture(autouse=True)
def _interpreted(interpreted_kernels):
    with interpreted_kernels():
        yield


def loop_over_groups(x, w, ct, sizes):
    """``(out, d lhs, d rhs)`` in float64, the uncovered rows zero."""
    x, w, ct = (np.asarray(a, np.float64) for a in (x, w, ct))
    out = np.zeros((x.shape[0], w.shape[2]))
    d_lhs, d_rhs = np.zeros(x.shape), np.zeros(w.shape)
    at = 0
    for g, size in enumerate(sizes):
        rows = slice(at, at + size)
        out[rows] = x[rows] @ w[g]
        d_lhs[rows] = ct[rows] @ w[g].T
        d_rhs[g] = x[rows].T @ ct[rows]
        at += size
    return out, d_lhs, d_rhs


def operands(m, groups, dtype, seed=0, k=K, n=N):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(shape), dtype)
                 for shape in ((m, k), (groups, k, n), (m, n)))


def all_three(x, w, ct, sizes):
    out, vjp = jax.vjp(
        lambda x, w: gm.grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32)),
        x, w)
    return (out,) + vjp(ct)


def assert_close(got, want, dtype, scale):
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=tol, atol=tol * scale)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("sizes", [
    (200, 57, 300, 211), (0, 300, 468), (300, 0, 0, 468), (468, 300, 0),
    (768,), (0, 0, 768, 0), (256, 256, 256)],
    ids=["unaligned", "empty_first", "empty_in_the_middle", "empty_last",
         "one_group", "one_group_of_four", "on_the_tiles"])
def test_every_row_covered(sizes, dtype):
    """768 rows, three row tiles: result, rows' gradient and weights'
    gradient against the loop, where a tile is shared by two and three
    groups, a group spans three tiles, and a group is empty (its
    weights' gradient exactly zero)."""
    x, w, ct = operands(sum(sizes), len(sizes), dtype)
    want = loop_over_groups(x, w, ct, sizes)
    for got, ref, scale in zip(all_three(x, w, ct, sizes), want,
                               (8, 10, 20)):
        assert_close(got, ref, dtype, scale)
    d_rhs = np.asarray(all_three(x, w, ct, sizes)[2], np.float32)
    for g, size in enumerate(sizes):
        assert (size > 0) == bool(d_rhs[g].any())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("m,sizes", [
    (768, (100, 0, 143, 57)), (768, (0, 0, 0)), (768, (256, 0)),
    (1024, (300, 212)), (200, (31, 70))],
    ids=["ends_inside_a_tile", "no_row_at_all", "ends_on_a_tile",
         "whole_tiles_unvisited", "one_small_tile"])
def test_rows_no_group_covers(m, sizes, dtype):
    """``sum(sizes) < M`` with the uncovered rows of BOTH operands and
    of the cotangent NaN: the result and the rows' gradient are
    exactly zero there, the weights' gradient is finite and equal to
    the covered rows' alone."""
    x, w, ct = operands(m, len(sizes), dtype)
    total = sum(sizes)
    poisoned = [a.at[total:].set(jnp.nan) for a in (x, ct)]
    out, d_lhs, d_rhs = all_three(poisoned[0], w, poisoned[1], sizes)
    want = loop_over_groups(x[:total], w, ct[:total], sizes)
    for got, ref, scale in ((out, want[0], 8), (d_lhs, want[1], 10)):
        assert not np.asarray(got[total:], np.float32).any()
        assert_close(got[:total], ref, dtype, scale)
    assert np.isfinite(np.asarray(d_rhs, np.float32)).all()
    assert_close(d_rhs, want[2], dtype, 20)


@pytest.mark.parametrize("m", [300, 513, 40, 7])
def test_rows_that_do_not_divide_into_tiles_are_padded(m):
    """A call of more rows than one tile whose rows do not divide is
    padded up to whole tiles (the pad lies past every group) and cut
    back; a call of fewer rows than a tile is one tile of its own
    size."""
    sizes = (m // 3, 0, m - m // 3 - 5)
    x, w, ct = operands(m, 3, jnp.float32, seed=1)
    want = loop_over_groups(x, w, ct, sizes)
    got = all_three(x, w, ct, sizes)
    assert [g.shape for g in got] == [(m, N), (m, K), (3, K, N)]
    for g, ref, scale in zip(got, want, (8, 10, 20)):
        assert_close(g, ref, jnp.float32, scale)


def test_weights_too_wide_for_the_budget_go_by_column_tiles(monkeypatch):
    """Where a group's weights and the blocks beside them pass the
    budget the weight block is cut, columns first (``gmm``, ``gmm_t``)
    and for ``tgmm``'s result rows too, in multiples of 128 lanes; a
    last block that runs past the array is cut by the grid. The
    cells' widths all fit whole."""
    for k, n, acc in ((2048, 1536, True), (1536, 2048, False),
                      (2048, 1408, True), (2048, 1024, False)):
        assert gm._tiles(4096, k, n, 2, acc)[:3] == (256, k, n)
    monkeypatch.setattr(gm, "VMEM_BUDGET", 2 ** 19)
    assert gm._tiles(512, 256, 320, 4, False)[:3] == (256, 256, 128)
    assert gm._tiles(512, 256, 320, 4, True)[:3] == (256, 128, 128)
    sizes = (100, 0, 250, 99)
    x, w, ct = operands(512, 4, jnp.float32, seed=2, k=256, n=320)
    x, ct = x.at[449:].set(jnp.nan), ct.at[449:].set(jnp.nan)
    want = loop_over_groups(x[:449], w, ct[:449], sizes)
    out, d_lhs, d_rhs = all_three(x, w, ct, sizes)
    assert_close(out[:449], want[0], jnp.float32, 16)
    assert_close(d_lhs[:449], want[1], jnp.float32, 18)
    assert_close(d_rhs, want[2], jnp.float32, 20)
    assert not np.asarray(out[449:]).any()
    assert not np.asarray(d_lhs[449:]).any()


def test_group_sizes_take_no_gradient_and_the_call_jits():
    x, w, ct = operands(512, 2, jnp.float32)
    sizes = jnp.asarray([200, 100], jnp.int32)
    loss = jax.jit(lambda x, w, s: (gm.grouped_matmul(x, w, s) * ct).sum())
    grads = jax.grad(loss, argnums=(0, 1))(x, w, sizes)
    want = loop_over_groups(x[:300], w, ct[:300], (200, 100))
    assert_close(grads[0][:300], want[1], jnp.float32, 10)
    assert_close(grads[1], want[2], jnp.float32, 20)


# ----------------------------------------------------------------------
# Through the layer: the kernels' path against lax.ragged_dot's
# ----------------------------------------------------------------------
def layer(monkeypatch, kernel, cfg, m, x):
    """Result, statistics and every gradient leaf of a share through
    ``moe_mlp_with_losses`` on one of the two paths."""
    monkeypatch.setattr(moe_ops, "pallas_enabled", lambda: kernel)
    # (the fallback's chunks are rematerialised, and the interpreter's
    # callbacks are effects a ``checkpoint`` cannot split: the values
    # are the same without it)
    monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: f)
    calls = []
    real = gm.grouped_matmul

    def counted(*a):
        calls.append(a[0].shape[0])
        return real(*a)

    monkeypatch.setattr(moe_ops, "grouped_matmul", counted)

    def loss(m_, x_):
        out, aux = moe_ops.moe_mlp_with_losses(cfg, m_, x_)
        return (out * jnp.cos(jnp.arange(32.0))).sum(), (out, aux)

    (_, (out, aux)), grads = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(m, x)
    assert bool(calls) == kernel
    return out, aux, grads


@pytest.mark.parametrize("tokens,pulled,count,slow", [
    (64, 20, 2, 0), (64, 64, 2, 1), (50, 50, 3, 1), (40, None, 16, 0)],
    ids=["fast_path", "fallback", "fallback_rows_do_not_divide",
         "every_expert_held"])
def test_a_share_by_the_kernels_is_the_share_by_ragged_dot(
        monkeypatch, tokens, pulled, count, slow):
    """The share's result and every gradient leaf (the input, the
    router, each held expert's three matrices) by the kernels equal
    ``lax.ragged_dot``'s, on the fast path (the held pairs a part of
    the gathered rows: the rest lie in no group), on the forced
    fallback in chunks (an expert's pairs straddle chunks; the last
    chunk runs past ``T x k``) and with every expert held."""
    m, x = share_layer(seed=4, tokens=tokens)
    cfg = share_cfg(held=(0 if count == 16 else 4, count))
    if pulled is not None:
        sign = np.zeros(16, np.float32)
        sign[[4, 5, 9, 13]], sign[[0, 1, 2, 3]] = 1.0, -1.0
        m = dict(m, expert_bias=jnp.zeros(16),
                 router=m["router"] * 0.05 + jnp.zeros((32, 16)).at[
                     -1].set(6.0 * sign))
        x = x.at[0, :, -1].set(jnp.where(jnp.arange(tokens) < pulled,
                                         1.0, -1.0))
    m = held_leaves(m, cfg.moe.experts_held[0], count)
    want, aux, want_grads = layer(monkeypatch, False, cfg, m, x)
    assert float(aux[moe_ops.SHARE_OVERFLOW_STAT]) == slow
    assert 0 < float(aux[moe_ops.HELD_PAIRS_STAT]) <= tokens * 4
    got, _, grads = layer(monkeypatch, True, cfg, m, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    flat, _ = jax.tree.flatten(grads)
    for g, w in zip(flat, jax.tree.leaves(want_grads)):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=1e-6)
    for leaf in ("wg", "wu", "wd", "router"):
        assert np.abs(np.asarray(grads[0][leaf])).max() > 0, leaf


def test_the_uncut_layer_by_the_kernels_is_ragged_dots(monkeypatch):
    """``_ragged_moe`` (no share: every row lies in a group) takes the
    same call."""
    m, x = share_layer(seed=6, tokens=72)
    want, _, want_grads = layer(monkeypatch, False, share_cfg(), m, x)
    got, _, grads = layer(monkeypatch, True, share_cfg(), m, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=1e-6)


def test_sharded_stacks_keep_ragged_dot(monkeypatch):
    """The engine's word for a mesh (``SHARDED_STACKS``) keeps the
    grouped products ``lax.ragged_dot`` where the kernels are enabled;
    a callable constraint in the ragged mode is still refused."""
    m, x = share_layer()
    monkeypatch.setattr(moe_ops, "pallas_enabled", lambda: True)
    monkeypatch.setattr(moe_ops, "grouped_matmul", None)  # never called
    out, _ = moe_ops.moe_mlp_with_losses(
        share_cfg(), m, x, ep_constraint=moe_ops.SHARDED_STACKS)
    monkeypatch.setattr(moe_ops, "pallas_enabled", lambda: False)
    want, _ = moe_ops.moe_mlp_with_losses(share_cfg(), m, x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    with pytest.raises(ValueError, match="expert_parallel"):
        moe_ops.moe_mlp_with_losses(share_cfg(), m, x,
                                    ep_constraint=lambda a: a)
