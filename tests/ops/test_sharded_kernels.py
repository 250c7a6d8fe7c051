"""Pallas kernels composed with GSPMD meshes via shard_map.

A bare pallas_call under jit has no partitioning rule; the wrappers in
ops/attention.py (make_sharded_attention) and ops/decode_attention.py
(sharded_decode_attention) run the kernels on LOCAL shards -- B over
"data", heads over "model" -- which is what the tp16 70B decode story
relies on (docs/distributed.md). Validated here on the virtual CPU
mesh with the interpret-mode kernels injected."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from realhf_tpu.ops.attention import (
    decode_attention,
    make_sharded_attention,
    packed_attention_xla,
)
from realhf_tpu.ops.decode_attention import (
    decode_shardable,
    flash_decode_attention_stacked,
    sharded_decode_attention,
)
from realhf_tpu.ops.flash_attention import flash_attention
from realhf_tpu.parallel.mesh import ParallelismConfig, make_mesh


def _mesh(dp=2, tp=2):
    par = ParallelismConfig(data_parallel_size=dp,
                            tensor_parallel_size=tp)
    return make_mesh(par, devices=jax.devices("cpu")[:par.world_size])


def test_sharded_packed_attention_matches_xla(interpreted_kernels):
    rng = np.random.default_rng(0)
    b, l, nq, nkv, hd = 4, 128, 8, 4, 128
    q = jnp.asarray(rng.standard_normal((b, l, nq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, l, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, l, nkv, hd)), jnp.float32)
    seg = np.ones((b, l), np.int32)
    seg[:, l // 2:] = 2
    seg[0, -16:] = 0
    seg = jnp.asarray(seg)

    ref = packed_attention_xla(q, k, v, seg, causal=True)
    inner = functools.partial(_interp_packed, interpreted_kernels)
    attn = make_sharded_attention(_mesh(), inner=inner)
    got = jax.jit(lambda *a: attn(*a))(q, k, v, seg)
    valid = np.asarray(seg) != 0  # pad-row outputs are don't-care
    np.testing.assert_allclose(np.asarray(got)[valid],
                               np.asarray(ref)[valid],
                               atol=2e-5, rtol=2e-5)


def test_sharded_packed_attention_takes_a_selection(interpreted_kernels):
    """A sparse layer's selection [B, L, L] goes to each shard with its
    rows over "data" and WHOLE over "model" (the heads of a token share
    it), through the kernels that take it: forward and the three
    gradients as the XLA path's on one device."""
    rng = np.random.default_rng(1)
    b, l, nq, nkv, hd = 4, 128, 8, 4, 128
    q, k, v = (jnp.asarray(rng.standard_normal((b, l, n, hd)), jnp.float32)
               for n in (nq, nkv, nkv))
    seg = np.ones((b, l), np.int32)
    seg[:, l // 2:] = 2
    seg = jnp.asarray(seg)
    select = jnp.asarray(rng.random((b, l, l)) < 0.5, jnp.int8) \
        | jnp.eye(l, dtype=jnp.int8)  # every query keeps itself

    def grads_of(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: (fn(q, k, v, seg, select=select) ** 2).sum(),
            argnums=(0, 1, 2)))(q, k, v)

    want = grads_of(functools.partial(packed_attention_xla, causal=True))
    inner = functools.partial(_interp_packed, interpreted_kernels)
    with interpreted_kernels():  # the backward is traced late
        got = grads_of(make_sharded_attention(_mesh(), inner=inner))
    for a, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=2e-4)
    # and it changes the result: the mask is not dropped on the way
    plain = grads_of(lambda q, k, v, seg, select: packed_attention_xla(
        q, k, v, seg, causal=True))
    assert abs(float(plain[0]) - float(want[0])) > 1e-3 * float(want[0])


def _interp_packed(interpreted_kernels, q, k, v, seg, causal=True,
                   scale=None, sliding_window=None, **blocks):
    assert sliding_window is None
    with interpreted_kernels():
        return flash_attention(q, k, v, seg, causal=causal, scale=scale,
                               **blocks)


def test_sharded_packed_attention_ranges_per_shard(interpreted_kernels):
    """Each shard bounds its kernels' loops from its LOCAL segment
    ids: rows packed differently on the two data shards, several
    blocks a row, forward and the three gradients against XLA."""
    rng = np.random.default_rng(2)
    b, l, nq, nkv, hd = 2, 256, 4, 2, 64
    q, k, v, w = (jnp.asarray(rng.standard_normal((b, l, n, hd)),
                              jnp.float32) for n in (nq, nkv, nkv, nq))
    seg = np.zeros((b, l), np.int32)
    seg[0, :64], seg[0, 64:192], seg[0, 192:240] = 3, 1, 2  # pad tail
    seg[1, :200], seg[1, 200:] = 2, 1
    valid = jnp.asarray(seg != 0)[..., None, None]
    seg = jnp.asarray(seg)

    def grads_of(attn):
        def loss(q, k, v):
            return (jnp.where(valid, attn(q, k, v, seg), 0.0) * w).sum()
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    ref = grads_of(packed_attention_xla)
    with interpreted_kernels():  # the backward is traced late
        got = grads_of(make_sharded_attention(
            _mesh(), inner=functools.partial(
                _interp_packed, interpreted_kernels, block_q=64,
                block_k=64)))
    for name, a, b_ in zip("qkv", got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name}")


def test_sharded_packed_attention_indivisible_falls_back():
    rng = np.random.default_rng(1)
    b, l, nq, nkv, hd = 3, 64, 8, 4, 128  # b=3 not divisible by dp=2
    q = jnp.asarray(rng.standard_normal((b, l, nq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, l, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, l, nkv, hd)), jnp.float32)
    seg = jnp.ones((b, l), jnp.int32)
    attn = make_sharded_attention(_mesh(), inner=_boom)
    ref = packed_attention_xla(q, k, v, seg, causal=True)
    got = attn(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def _boom(*a, **k):
    raise AssertionError("kernel path must not run for odd shapes")


def test_sharded_decode_kernel_matches_xla():
    rng = np.random.default_rng(2)
    b, s, nq, nkv, hd = 4, 128, 8, 4, 128
    q = jnp.asarray(rng.standard_normal((b, nq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, nkv, s, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, nkv, s, hd)), jnp.float32)
    valid = np.zeros((b, s), bool)
    valid[:, :100] = True
    valid = jnp.asarray(valid)
    mesh = _mesh()
    assert decode_shardable(mesh, b, nq, nkv)

    ref = decode_attention(q, k, v, valid)

    def fn(q_l, k_l, v_l, valid_l, slot_l, lidx):
        return flash_decode_attention_stacked(q_l, k_l, v_l, valid_l,
                                              lidx, interpret=True)

    # one layer's cache is a stack of one
    got = jax.jit(lambda *a: sharded_decode_attention(
        fn, mesh, a[0], (a[1][None], a[2][None]), a[3], None,
        jnp.zeros((), jnp.int32)))(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_sharded_stacked_decode_kernel_matches_xla():
    rng = np.random.default_rng(3)
    nl, b, s, nq, nkv, hd = 3, 4, 64, 8, 4, 128
    q = jnp.asarray(rng.standard_normal((b, nq, hd)), jnp.float32)
    k_all = jnp.asarray(rng.standard_normal((nl, b, nkv, s, hd)),
                        jnp.float32)
    v_all = jnp.asarray(rng.standard_normal((nl, b, nkv, s, hd)),
                        jnp.float32)
    valid = jnp.ones((b, s), bool)
    mesh = _mesh()
    layer = jnp.asarray(1, jnp.int32)

    ref = decode_attention(q, k_all[1], v_all[1], valid)

    def fn(q_l, k_l, v_l, valid_l, slot_l, lidx):
        return flash_decode_attention_stacked(q_l, k_l, v_l, valid_l,
                                              lidx, interpret=True)

    got = jax.jit(lambda *a: sharded_decode_attention(
        fn, mesh, a[0], (a[1], a[2]), a[3], None, a[4]))(
            q, k_all, v_all, valid, layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_choose_decode_partitioning():
    from realhf_tpu.ops.decode_attention import (
        choose_decode_partitioning,
    )
    mesh = _mesh(dp=2, tp=4)
    # heads divide: fast path
    assert choose_decode_partitioning(mesh, 4, 8, 4, 256) == "heads"
    # GQA at tp > nkv: KV-sequence split
    assert choose_decode_partitioning(mesh, 4, 8, 2, 256) == "seq"
    # nothing divides (cache length odd vs tp): einsum fallback
    assert choose_decode_partitioning(mesh, 4, 8, 2, 255) is None
    # divisible globally but the LOCAL shard (2304/4 = 576) violates
    # the stacked kernel's K-block constraint (>512 and not a 128
    # multiple): must fall back, not crash at trace time
    assert choose_decode_partitioning(mesh, 4, 8, 2, 2304) is None
    # 4096/4 = 1024 local: fine (128 multiple)
    assert choose_decode_partitioning(mesh, 4, 8, 2, 4096) == "seq"


def _stats_of_one_layer(q_l, k_l, v_l, keep_l, lidx):
    return flash_decode_attention_stacked(
        q_l, k_l, v_l, keep_l.astype(bool), lidx, interpret=True,
        return_stats=True)


def test_seqsplit_decode_matches_xla():
    """GQA at tp > n_kv_heads: KV sequence shards over "model" and the
    cross-shard flash merge must reproduce dense decode attention,
    including rows with partially-valid caches and a fully-empty row."""
    from realhf_tpu.ops.decode_attention import (
        sharded_decode_attention_seqsplit,
        window_keep,
    )
    rng = np.random.default_rng(4)
    b, s, nq, nkv, hd = 4, 256, 8, 2, 128
    q = jnp.asarray(rng.standard_normal((b, nq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, nkv, s, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, nkv, s, hd)), jnp.float32)
    valid = np.zeros((b, s), bool)
    valid[0, :200] = True
    valid[1, 64:192] = True   # valid region split across seq shards
    valid[2, :40] = True      # valid only on shard 0
    # row 3 fully empty: merge must emit zeros, not NaNs
    valid = jnp.asarray(valid)
    mesh = _mesh(dp=2, tp=4)

    ref = decode_attention(q, k, v, valid)

    keep = window_keep(valid, None, None)
    got = jax.jit(lambda *a: sharded_decode_attention_seqsplit(
        _stats_of_one_layer, mesh, a[0], (a[1][None], a[2][None]), a[3],
        jnp.zeros((), jnp.int32)))(q, k, v, keep)
    # rows 0-2 must match dense attention; row 3's cache is fully
    # empty -- a don't-care (prefill always writes >= 1 token) where
    # the flash kernels emit zeros while XLA softmax degenerates to
    # mean-of-v. Pin zeros/no-NaN for it instead.
    np.testing.assert_allclose(np.asarray(got)[:3], np.asarray(ref)[:3],
                               atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got)[3], 0.0)


def test_seqsplit_decode_sliding_window():
    from realhf_tpu.ops.decode_attention import (
        sharded_decode_attention_seqsplit,
        window_keep,
    )
    rng = np.random.default_rng(5)
    b, s, nq, nkv, hd = 2, 256, 4, 1, 128
    q = jnp.asarray(rng.standard_normal((b, nq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, nkv, s, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, nkv, s, hd)), jnp.float32)
    valid = np.zeros((b, s), bool)
    valid[:, :220] = True
    valid = jnp.asarray(valid)
    slot = jnp.asarray([219, 219], jnp.int32)
    window = 100
    mesh = _mesh(dp=2, tp=4)

    ref = decode_attention(q, k, v, valid, sliding_window=window,
                           slot=slot)

    # window applied via the precomputed GLOBAL keep mask
    keep = window_keep(valid, window, slot)
    got = jax.jit(lambda *a: sharded_decode_attention_seqsplit(
        _stats_of_one_layer, mesh, a[0], (a[1][None], a[2][None]), a[3],
        jnp.zeros((), jnp.int32)))(q, k, v, keep)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_seqsplit_stacked_decode_matches_xla():
    from realhf_tpu.ops.decode_attention import (
        sharded_decode_attention_seqsplit,
        window_keep,
    )
    rng = np.random.default_rng(6)
    nl, b, s, nq, nkv, hd = 3, 4, 256, 8, 2, 128
    q = jnp.asarray(rng.standard_normal((b, nq, hd)), jnp.float32)
    k_all = jnp.asarray(rng.standard_normal((nl, b, nkv, s, hd)),
                        jnp.float32)
    v_all = jnp.asarray(rng.standard_normal((nl, b, nkv, s, hd)),
                        jnp.float32)
    valid = np.zeros((b, s), bool)
    valid[:, :130] = True
    valid = jnp.asarray(valid)
    mesh = _mesh(dp=2, tp=4)
    layer = jnp.asarray(2, jnp.int32)

    ref = decode_attention(q, k_all[2], v_all[2], valid)

    keep = window_keep(valid, None, None)
    got = jax.jit(lambda *a: sharded_decode_attention_seqsplit(
        _stats_of_one_layer, mesh, a[0], (a[1], a[2]), a[3], a[4]))(
            q, k_all, v_all, keep, layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_return_stats_consistency():
    """(out, m, l) from return_stats recombine to the plain output:
    the invariant the seqsplit merge relies on."""
    rng = np.random.default_rng(7)
    b, s, nq, nkv, hd = 2, 128, 4, 2, 128
    q = jnp.asarray(rng.standard_normal((b, nq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, nkv, s, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, nkv, s, hd)), jnp.float32)
    valid = jnp.asarray(np.ones((b, s), bool))
    plain = flash_decode_attention_stacked(q, k[None], v[None], valid, 0,
                                           interpret=True)
    out, m, l = flash_decode_attention_stacked(
        q, k[None], v[None], valid, 0, interpret=True, return_stats=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain),
                               atol=1e-6)
    assert np.asarray(l).min() > 0 and np.isfinite(np.asarray(m)).all()


def _delta_case(b=2, l=100, h=2, d=128):
    """A packed batch of a delta layer at heads a whole lane wide (the
    rows packed differently), a ``Prepare`` whose decay differs head by
    head, and weights for a loss over the outputs and the last
    states."""
    rng = np.random.default_rng(41)
    q, k, v, f = (jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
                  for _ in range(4))
    beta = jnp.asarray(1 / (1 + np.exp(-rng.normal(size=(b, l, h)))),
                       jnp.float32)
    seg = np.ones((b, l), np.int32)
    seg[0, 37:90], seg[0, 90:] = 2, 0  # a pad tail
    seg[1::2, 70:] = 2
    a_log = jnp.asarray(rng.uniform(0, 2, size=(h,)), jnp.float32)
    dt_bias = jnp.asarray(rng.normal(size=(h, d)) - 3, jnp.float32)
    w = jnp.asarray(rng.normal(size=v.shape) * (seg != 0)[..., None, None],
                    jnp.float32)
    w_last = jnp.asarray(rng.normal(size=(b, h, d, d)), jnp.float32)
    return (a_log, dt_bias, q, k, v, f, beta), jnp.asarray(seg), w, w_last


def test_sharded_delta_scan_matches_one_device(interpreted_kernels):
    """``ops/delta_rule.py``'s two kernels on a dp2 x tp2 mesh: a bare
    Mosaic call cannot be partitioned (on the chip jax refuses to lower
    one on a mesh), so each device runs them on its own rows and heads
    under ``shard_map``. Forward and every gradient against the XLA
    products on one device; d of the decay's two tensors, which every
    data shard holds, is summed over "data"."""
    from realhf_tpu.ops import delta_rule as D
    args, seg, w, w_last = _delta_case()
    d = args[2].shape[-1]

    def grads_of(scan):
        def loss(a_log, dt_bias, q, k, v, f, beta):
            prepare = D.Prepare(rate=-jnp.exp(a_log), dt_bias=dt_bias,
                                scale=d ** -0.5, eps=1e-6)
            o, last = scan(q, k, v, f, beta, seg, prepare)
            return (o * w).sum() + (last * w_last).sum()
        return jax.jit(jax.grad(loss, argnums=tuple(range(7))))

    def sharded(*a):
        return D.chunked_delta_rule(*a, mesh=_mesh())

    with jax.default_matmul_precision("highest"):
        want = grads_of(D._by_xla)(*args)
        with interpreted_kernels():  # the backward is traced late
            text = grads_of(sharded).lower(*args).as_text(debug_info=True)
            got = grads_of(sharded)(*args)
    assert "shard_map" in text
    assert D.DELTA_FWD in text and D.DELTA_BWD in text
    for name, a, b_ in zip("a_log dt_bias q k v f beta".split(), got, want):
        err = float(jnp.abs(a - b_).max())
        assert err < 1e-4 * float(jnp.abs(b_).max()), (name, err)


def test_delta_scan_goes_by_xla_where_a_mesh_cannot_take_the_kernels(
        monkeypatch):
    """One device (or no mesh): the bare kernels. Rows or heads that do
    not divide the mesh, and a mesh that cuts a row along its length
    (context parallelism): the XLA products, which GSPMD partitions,
    with no Mosaic call in the program."""
    from realhf_tpu.ops import delta_rule as D
    monkeypatch.setattr(D, "pallas_enabled", lambda: True)
    assert D._scan_over(None, 3, 5) is D._scan
    assert D._scan_over(_mesh(1, 1), 3, 5) is D._scan
    mesh = _mesh()
    assert D._scan_over(mesh, 4, 4) not in (None, D._scan)
    assert D._scan_over(mesh, 3, 4) is None
    assert D._scan_over(mesh, 4, 3) is None
    par = ParallelismConfig(data_parallel_size=2, context_parallel_size=2)
    cut = make_mesh(par, devices=jax.devices("cpu")[:par.world_size])
    assert D._scan_over(cut, 4, 4) is None
    args, seg, _, _ = _delta_case(b=3)
    prepare = D.Prepare(rate=-jnp.exp(args[0]), dt_bias=args[1],
                        scale=1.0, eps=1e-6)
    text = jax.jit(lambda *a: D.chunked_delta_rule(
        *a, seg, prepare, mesh=mesh)).lower(*args[2:]).as_text(
            debug_info=True)
    assert D.DELTA_FWD not in text and "tpu_custom_call" not in text


def _ssm_case(b=2, l=200, h=4, g=2, p=64, n=128):
    """A packed batch of an ssm layer at groups a whole lane wide (the
    rows packed differently), three leaves that differ head by head,
    and weights for a loss over the outputs and the last states."""
    rng = np.random.default_rng(43)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    seg = np.ones((b, l), np.int32)
    seg[0, 37:150], seg[0, 150:] = 2, 0  # a pad tail
    seg[1::2, 130:] = 2
    args = (f(b, l, h, p), f(b, l, h), f(b, l, g, n), f(b, l, g, n),
            -jnp.exp(f(h)), f(h), 1 + f(h))
    w = f(b, l, h, p) * (seg != 0)[..., None, None]
    return args, jnp.asarray(seg), w, f(b, h, p, n)


def test_sharded_ssm_scan_matches_one_device(interpreted_kernels):
    """``ops/ssm_scan.py``'s two kernels on a dp2 x tp2 mesh: each
    device runs them on its own rows and GROUPS of heads under
    ``shard_map``. Forward and every gradient against the XLA products
    on one device; d of the three leaves a head, which every data shard
    holds, is summed over "data"."""
    from realhf_tpu.ops import ssm_scan as S
    args, seg, w, w_last = _ssm_case()

    def grads_of(**kw):
        def loss(x, dt, b, c, rate, dt_bias, skip):
            y, last = S.chunked_ssm_scan(x, dt, b, c, seg, rate=rate,
                                         dt_bias=dt_bias, skip=skip, **kw)
            return (y * w).sum() + (last * w_last).sum()
        return jax.jit(jax.grad(loss, argnums=tuple(range(7))))

    with jax.default_matmul_precision("highest"):
        want = grads_of()(*args)  # outside the interpreter: XLA
        with interpreted_kernels():  # the backward is traced late
            sharded = grads_of(mesh=_mesh())
            text = sharded.lower(*args).as_text(debug_info=True)
            got = sharded(*args)
    assert "shard_map" in text
    assert S.SSM_FWD in text and S.SSM_BWD in text
    for name, a, b_ in zip("x dt b c rate dt_bias skip".split(), got, want):
        err = float(jnp.abs(a - b_).max())
        # (a leaf's is a sum over every token of terms of both signs)
        tol = 1e-4 if a.ndim > 1 else 1e-3
        assert err < tol * float(jnp.abs(b_).max()), (name, err)


def test_ssm_scan_goes_by_xla_where_a_mesh_cannot_take_the_kernels(
        monkeypatch):
    """One device (or no mesh): the bare kernels. Rows or GROUPS that
    do not divide the mesh, and a mesh that cuts a row along its length
    (context parallelism): the XLA products, which GSPMD partitions,
    with no Mosaic call in the program."""
    from realhf_tpu.ops import ssm_scan as S
    monkeypatch.setattr(S, "pallas_enabled", lambda: True)
    assert S._scan_over(None, 3, 5) is S._scan
    assert S._scan_over(_mesh(1, 1), 3, 5) is S._scan
    mesh = _mesh()
    assert S._scan_over(mesh, 4, 4) not in (None, S._scan)
    assert S._scan_over(mesh, 3, 4) is None
    assert S._scan_over(mesh, 4, 3) is None
    par = ParallelismConfig(data_parallel_size=2, context_parallel_size=2)
    cut = make_mesh(par, devices=jax.devices("cpu")[:par.world_size])
    assert S._scan_over(cut, 4, 4) is None
    (x, dt, b, c, rate, dt_bias, skip), seg, _, _ = _ssm_case(b=3)
    text = jax.jit(lambda *a: S.chunked_ssm_scan(
        *a, seg, rate=rate, dt_bias=dt_bias, skip=skip,
        mesh=mesh)).lower(x, dt, b, c).as_text(debug_info=True)
    assert S.SSM_FWD not in text and "tpu_custom_call" not in text
