"""Flash-attention kernel vs the XLA reference path: forward and
gradients, with packed segments, GQA, and padding. Runs the Pallas
interpreter on CPU (the kernel-vs-reference tier of the reference's
``tests/cpp_extensions``)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from realhf_tpu.ops.attention import (_segment_mask, packed_attention,
                                      packed_attention_xla)
from realhf_tpu.ops import flash_attention as fa


def make_inputs(rng, b=2, l=256, nq=4, nkv=2, hd=32, n_segs=3,
                with_pad=True):
    q = jnp.asarray(rng.standard_normal((b, l, nq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, l, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, l, nkv, hd)), jnp.float32)
    seg = np.zeros((b, l), np.int32)
    for bi in range(b):
        bounds = np.sort(rng.choice(
            np.arange(1, l - 1), size=n_segs - 1, replace=False))
        bounds = np.concatenate([[0], bounds, [l]])
        for s in range(n_segs):
            seg[bi, bounds[s]:bounds[s + 1]] = s + 1
        if with_pad:
            pad_start = int(bounds[-2] + (l - bounds[-2]) // 2)
            seg[bi, pad_start:] = 0
    return q, k, v, jnp.asarray(seg)


@pytest.fixture
def interp_flash(interpreted_kernels):
    def run(q, k, v, seg, **kw):
        with interpreted_kernels():
            return fa.flash_attention(q, k, v, seg, **kw)
    return run


@pytest.mark.parametrize("blocks", [(64, 64), (128, 64), (64, 128)])
def test_forward_matches_xla(blocks, interp_flash):
    rng = np.random.default_rng(0)
    q, k, v, seg = make_inputs(rng)
    ref = packed_attention_xla(q, k, v, seg)
    got = interp_flash(q, k, v, seg, block_q=blocks[0], block_k=blocks[1])
    # rows that are entirely padding are unspecified in the XLA path
    valid = np.asarray(seg) != 0
    np.testing.assert_allclose(np.asarray(got)[valid],
                               np.asarray(ref)[valid], rtol=2e-3, atol=2e-3)


def test_gradients_match_xla(interpreted_kernels):
    rng = np.random.default_rng(1)
    q, k, v, seg = make_inputs(rng, l=128, n_segs=2)

    def loss_ref(q, k, v):
        o = packed_attention_xla(q, k, v, seg)
        return (o * jnp.where(seg[..., None, None] != 0, 1.0, 0.0)).sum()

    def loss_flash(q, k, v):
        o = fa.flash_attention(q, k, v, seg, block_q=64, block_k=64)
        return (o * jnp.where(seg[..., None, None] != 0, 1.0, 0.0)).sum()

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    with interpreted_kernels():
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gr, gf, "qkv"):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-3, atol=5e-3,
                                   err_msg=f"d{name} mismatch")


def test_segment_isolation(interp_flash):
    """Perturbing segment 2's K/V must not change segment 1's output."""
    rng = np.random.default_rng(2)
    q, k, v, seg = make_inputs(rng, b=1, l=128, n_segs=2, with_pad=False)
    out1 = interp_flash(q, k, v, seg, block_q=64, block_k=64)
    seg_np = np.asarray(seg)[0]
    second = np.where(seg_np == 2)[0]
    k2 = k.at[0, second].add(1.0)
    v2 = v.at[0, second].add(1.0)
    out2 = interp_flash(q, k2, v2, seg, block_q=64, block_k=64)
    first = np.where(seg_np == 1)[0]
    np.testing.assert_allclose(np.asarray(out1)[0, first],
                               np.asarray(out2)[0, first], rtol=1e-5,
                               atol=1e-6)


def test_non_causal(interp_flash):
    rng = np.random.default_rng(3)
    q, k, v, seg = make_inputs(rng, l=128, n_segs=2, with_pad=False)
    ref = packed_attention_xla(q, k, v, seg, causal=False)
    got = interp_flash(q, k, v, seg, causal=False, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_padding_rows_emit_zeros(interp_flash):
    """All-padding rows must output exactly zero (contract for the
    residual stream at pad slots)."""
    rng = np.random.default_rng(4)
    q, k, v, seg = make_inputs(rng, b=1, l=128, n_segs=2, with_pad=True)
    out = interp_flash(q, k, v, seg, block_q=64, block_k=64)
    pad = np.asarray(seg)[0] == 0
    assert pad.any()
    assert np.abs(np.asarray(out)[0, pad]).max() == 0.0


def test_dispatch_guards():
    """Soft cap and traced scales must route to the XLA path, not
    crash in the flash wrapper."""
    rng = np.random.default_rng(5)
    q, k, v, seg = make_inputs(rng, b=1, l=128, nq=2, nkv=2, hd=64,
                               n_segs=2, with_pad=False)
    out = functools.partial(packed_attention, q, k, v, seg)
    # traced scale inside jit: must not hit float(tracer)
    f = jax.jit(lambda s: out(scale=s))
    f(jnp.float32(0.1))
    # soft cap: must not raise NotImplementedError
    out(logits_soft_cap=30.0)


# ----------------------------------------------------------------------
# Block ranges from the segment ids
# ----------------------------------------------------------------------
def packed_rows(rng, l, align, kind):
    """[4, l] segment ids as the packer may lay them: each id one
    contiguous run, ids in no order, boundaries on multiples of
    ``align``; ``kind`` says where the padding is. Row 3 is padding
    alone."""
    seg = np.zeros((4, l), np.int32)
    for row in range(3):
        cuts = np.sort(rng.choice(np.arange(1, l // align),
                                  size=min(5, l // align - 1),
                                  replace=False)) * align
        bounds = np.concatenate([[0], cuts, [l]])
        ids = rng.permutation(len(bounds) - 1) + 1
        for i, (a, b) in enumerate(zip(bounds[:-1], bounds[1:])):
            seg[row, a:b] = ids[i]
        if kind in ("tail", "both"):
            seg[row, bounds[-2]:] = 0
        if kind in ("between", "both"):
            seg[row, bounds[2]:bounds[3]] = 0
    return seg


def pair_mask(seg, causal=True, window=None):
    """[B, L, L] bool, built the long way: the pairs attended."""
    idx = np.arange(seg.shape[1])
    mask = (seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] != 0)
    if causal:
        mask &= (idx[:, None] >= idx[None, :])[None]
    if window is not None:
        mask &= (idx[:, None] - idx[None, :] < window)[None]
    return mask


def blocks_of(mask, bq, bk, reduce):
    """[B, L // bq, L // bk]: ``reduce`` (``any`` / ``all``) over each
    block pair of a [B, L, L] mask."""
    b, l, _ = mask.shape
    return getattr(mask.reshape(b, l // bq, bq, l // bk, bk), reduce)(
        axis=(2, 4))


def long_rows(l):
    """[4, l] rows whose documents span several blocks: one document;
    two that meet off every block's edge; padding inside and at the
    end; ids in no order; a document that ends at a block's edge."""
    seg = np.ones((4, l), np.int32)
    seg[1, :l * 5 // 8 + 3] = 7
    seg[2, :l * 3 // 8 + 5] = 3
    seg[2, l * 3 // 8 + 5:l // 2 - 3] = 0
    seg[2, -l // 16 - 1:] = 0
    seg[3, l // 2:] = 2
    return seg


def assert_no_edge_pairs_are_exactly_the_all_true(ranges, mask, bq, bk):
    """``block_ranges``' third answer against the mask built the long
    way: every pair of ``[full_lo, full_hi)`` has an all-true ``[bq,
    bk]`` mask, every all-true pair lies in it, from the query blocks'
    side and from the key blocks', inside what is visited."""
    n_q, n_k = mask.shape[1] // bq, mask.shape[2] // bk
    all_true = blocks_of(mask, bq, bk, "all")
    by_q, by_k = visited_blocks(ranges[2], n_q, n_k)
    np.testing.assert_array_equal(by_q, all_true)
    np.testing.assert_array_equal(by_k, all_true)
    for (lo, hi), (full_lo, full_hi) in zip(ranges[:2], ranges[2]):
        lo, hi, full_lo, full_hi = (
            np.asarray(x) for x in (lo, hi, full_lo, full_hi))
        hi = np.maximum(hi, lo)
        assert (lo <= full_lo).all() and (full_lo <= full_hi).all() \
            and (full_hi <= hi).all()
    return int(all_true.sum())


def visited_blocks(ranges, n_q, n_k):
    """The same shape from ``block_ranges``' two answers: what the
    forward and dq loops visit, and what the dkv loop visits."""
    (kv_lo, kv_hi), (q_lo, q_hi) = [
        [np.asarray(x) for x in r] for r in ranges[:2]]
    kj, qi = np.arange(n_k), np.arange(n_q)
    by_q = (kv_lo[:, :, None] <= kj) & (kj < kv_hi[:, :, None])
    by_k = (q_lo[:, None, :] <= qi[:, None]) & (qi[:, None] < q_hi[:, None, :])
    return by_q, by_k


def _hull(needed, axis):
    """True from the first to the last True along ``axis``."""
    after_first = np.maximum.accumulate(needed, axis=axis)
    before_last = np.flip(np.maximum.accumulate(
        np.flip(needed, axis), axis=axis), axis)
    return after_first & before_last


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("kind", ["none", "tail", "between", "both"])
@pytest.mark.parametrize("bq,bk,align", [(32, 64, 64), (64, 32, 64),
                                         (32, 64, 1), (64, 64, 8),
                                         (256, 512, 128), (256, 512, 1)])
def test_block_ranges_against_brute_force(bq, bk, align, kind, causal):
    """No block that holds an unmasked pair is left out, and where the
    segments' boundaries fall on the blocks nothing else is visited;
    the pairs no edge crosses are exactly those whose mask, built the
    long way, is all true."""
    l = 8 * max(bq, bk)
    rng = np.random.default_rng(abs(hash((bq, bk, align, kind))) % 2**31)
    seg = np.concatenate([packed_rows(rng, l, align, kind), long_rows(l)])
    mask = pair_mask(seg, causal)
    needed = blocks_of(mask, bq, bk, "any")
    assert not needed[3].any() and needed[:3].any()

    on_host = fa.block_ranges(seg, bq, bk, causal, xp=np)
    in_program = jax.jit(fa.block_ranges, static_argnums=(1, 2, 3))(
        jnp.asarray(seg), bq, bk, causal)
    assert len(jax.tree.leaves(on_host)) == 8
    for a, b in zip(jax.tree.leaves(on_host), jax.tree.leaves(in_program)):
        assert a.dtype == b.dtype == np.int32
        np.testing.assert_array_equal(a, np.asarray(b))

    for visited in visited_blocks(on_host, l // bq, l // bk):
        assert not (needed & ~visited).any()
        assert not visited[3].any()  # padding alone: an empty range
        if align % max(bq, bk) == 0:
            np.testing.assert_array_equal(visited[:4], needed[:4])
    # wherever the boundaries fall, a range runs from the first to the
    # last block that is needed, no further
    by_q, by_k = visited_blocks(on_host, l // bq, l // bk)
    np.testing.assert_array_equal(by_q, _hull(needed, axis=2))
    np.testing.assert_array_equal(by_k, _hull(needed, axis=1))
    # a row of one document: every pair off the diagonal
    assert assert_no_edge_pairs_are_exactly_the_all_true(
        on_host, mask, bq, bk) >= (l // bq) * (l // bk) // 4


BENCHMARK_ROWS = {  # cell: (sequences a row, their length, row, counts)
    "qwen2.5-0.5b.sft": (4, 1024, 4096, (24, 72, 8)),
    "qwen2.5-0.5b.grpo": (8, 512, 4096, (16, 72, 0)),
    "mistral-7b-v0.3-l4.grpo-realloc": (4, 512, 2048, (8, 20, 0)),
    "olmoe-1b-7b-0125-l1.sft-2k": (1, 2048, 2048, (20, 20, 12)),
    "keye-vl-2.0-30b-a3b-l5-ep8.sft-4k": (1, 4096, 4096, (72, 72, 56)),
}


@pytest.mark.parametrize("cell", BENCHMARK_ROWS)
def test_block_counts_of_the_benchmark_rows(cell):
    n, length, row, want = BENCHMARK_ROWS[cell]
    seg = np.repeat(np.arange(1, n + 1, dtype=np.int32), length)
    assert seg.shape == (row,)
    assert fa.block_counts(seg[None]) == want
    # a stack of microbatches of rows counts each row
    assert fa.block_counts(np.tile(seg, (3, 2, 1))) == tuple(
        6 * n for n in want)


def _every_pair_masked(*args, ranges=fa.block_ranges, **kw):
    """The ranges as they are, the pairs that no edge crosses forced
    empty: every visited pair builds its mask, as before there was
    such a sub-range."""
    kv_range, q_range, _ = ranges(*args, **kw)
    return kv_range, q_range, ((kv_range[1],) * 2, (q_range[1],) * 2)


def _dense_ranges(seg, bq, bk, causal=True, sliding_window=None):
    """The ranges of rows of ONE segment: every key block up to the
    causal diagonal (or the row's end), as before the ranges existed."""
    return _every_pair_masked(jnp.ones_like(seg), bq, bk, causal)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("kind,align", [("both", 1), ("tail", 64),
                                        ("between", 8)])
def test_segment_ranges_change_no_bit(kind, align, causal, monkeypatch,
                                      interpreted_kernels):
    """Forward and all three gradients with the loops bounded by the
    segments' ranges are bitwise what the same kernels give when they
    visit every block, and within the XLA path's tolerances."""
    rng = np.random.default_rng(7)
    b, l, nq, nkv, hd = 3, 256, 2, 1, 32  # a small grid: interpreted
    seg_np = packed_rows(rng, l, align, kind)[1:]
    seg = jnp.asarray(seg_np)
    q, k, v, w = (jnp.asarray(rng.standard_normal((b, l, n, hd)),
                              jnp.float32) for n in (nq, nkv, nkv, nq))
    valid = jnp.asarray(seg_np != 0)[..., None, None]

    def run(attn):
        def loss(q, k, v):
            out = attn(q, k, v, seg, causal=causal)
            return (jnp.where(valid, out, 0.0) * w).sum(), out
        # one program: interpreted grid steps run op by op otherwise
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return [np.asarray(x) for x in (out,) + grads]

    flash = functools.partial(fa.flash_attention, block_q=32, block_k=64)
    with interpreted_kernels():
        by_segment = run(flash)
        with monkeypatch.context() as patch:
            patch.setattr(fa, "block_ranges", _dense_ranges)
            dense = run(flash)
    reference = run(packed_attention_xla)
    # fewer blocks were visited than the dense loops visit
    visited, diagonal, _ = fa.block_counts(seg_np, 32, 64)
    assert visited < diagonal
    for name, got, same, ref in zip(("out", "dq", "dk", "dv"), by_segment,
                                    dense, reference):
        assert np.array_equal(got, same), name
        keep = (seg_np != 0) if name == "out" else np.ones_like(seg_np, bool)
        np.testing.assert_allclose(got[keep], ref[keep], rtol=5e-3,
                                   atol=5e-3, err_msg=name)


def pairs_without_a_mask_change_no_bit(
        interpreted_kernels, monkeypatch, heads=(2, 1, 32, 32), rows=(0, 2),
        causal=True, window=None, select=False):
    """The three kernels over rows whose documents span several blocks
    (``long_rows``; blocks of 32 x 64), with no mask built over the
    pairs that no edge crosses and with that sub-range forced empty:
    output, log-sum-exp, dq, dk and dv equal to the bit, and within
    the XLA path's tolerances. ``heads``: query and key/value heads,
    the key's width and the value's."""
    rng = np.random.default_rng(7)
    l, (nq, nkv, hd, hv) = 256, heads  # a small grid: interpreted
    seg_np = long_rows(l)[list(rows)]
    b, seg = len(seg_np), jnp.asarray(seg_np)
    q, k, v, w = (jnp.asarray(rng.standard_normal((b, l, n, d)), jnp.float32)
                  for n, d in ((nq, hd), (nkv, hd), (nkv, hv), (nq, hv)))
    g = jnp.where(jnp.asarray(seg_np != 0)[..., None, None], w, 0.0)
    kw = dict(causal=causal, sliding_window=window)
    if select:
        kw["select"] = jnp.asarray(rng.random((b, l, l)) < 0.5, jnp.int8) \
            | jnp.eye(l, dtype=jnp.int8)
    static = (hd ** -0.5, causal, 32, 64, window)

    @jax.jit  # one program: interpreted grid steps run op by op otherwise
    def kernels(q, k, v):
        if select:
            out, res = fa._flash_attention_selected_fwd(
                q, k, v, seg, kw["select"], *static)
            grads = fa._flash_attention_selected_bwd(*static, res, g)
        else:
            out, res = fa._flash_attention_fwd(q, k, v, seg, *static)
            grads = fa._flash_bwd(res, g, *static)
        return (out, res[-1]) + tuple(grads[:3])

    with interpreted_kernels():
        got = [np.asarray(x) for x in kernels(q, k, v)]
        with monkeypatch.context() as patch:
            patch.setattr(fa, "block_ranges", _every_pair_masked)
            kernels.clear_cache()
            masked = [np.asarray(x) for x in kernels(q, k, v)]
    out, vjp = jax.vjp(lambda q, k, v: packed_attention_xla(
        q, k, v, seg, **kw), q, k, v)
    # the sub-range is not empty, nor is it everything
    visited, _, unmasked = fa.block_counts(seg_np, 32, 64,
                                           sliding_window=window)
    assert 0 < unmasked < visited
    for name, a, same in zip(("out", "lse", "dq", "dk", "dv"), got, masked):
        assert np.array_equal(a, same), name
    keep = seg_np != 0
    np.testing.assert_allclose(got[0][keep], np.asarray(out)[keep],
                               rtol=5e-3, atol=5e-3)
    for name, a, ref in zip(("dq", "dk", "dv"), got[2:], vjp(g)):
        np.testing.assert_allclose(a, np.asarray(ref), rtol=5e-3, atol=5e-3,
                                   err_msg=name)


@pytest.mark.parametrize("kw", [
    dict(), dict(rows=(1, 3), causal=False), dict(select=True), dict(heads=(2, 2, 192, 128)),
    dict(heads=(2, 2, 64, 64)), dict(heads=(4, 1, 32, 32)),
    dict(heads=(8, 1, 32, 32), rows=(2,))],
    ids=["causal", "full", "selection", "key192_value128",
         "heads64_group1", "group4", "group8"])
def test_pairs_without_a_mask_change_no_bit(kw, monkeypatch,
                                            interpreted_kernels):
    pairs_without_a_mask_change_no_bit(interpreted_kernels, monkeypatch,
                                       **kw)


# ----------------------------------------------------------------------
# A sliding window inside the kernels
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["none", "both"])
@pytest.mark.parametrize("window", [1, 31, 32, 33, 64, 100, 128, 511, 512,
                                    768, 1500, 4096])
@pytest.mark.parametrize("bq,bk,align", [(32, 64, 64), (64, 32, 1),
                                         (256, 512, 128)])
def test_block_ranges_with_a_window_leave_out_no_needed_block(
        bq, bk, align, window, kind):
    """Whatever the window against the blocks (below, at, above them,
    the whole row): no block that holds an unmasked pair is left out,
    the ranges on the host are those inside a program, and they run
    from the first needed block to the last, no further; the pairs no
    edge crosses (a window's edge among them) are exactly those whose
    mask, built the long way, is all true."""
    l = 8 * max(bq, bk)
    rng = np.random.default_rng(abs(hash((bq, bk, align, window))) % 2**31)
    seg = np.concatenate([packed_rows(rng, l, align, kind), long_rows(l)])
    mask = pair_mask(seg, True, window)
    needed = blocks_of(mask, bq, bk, "any")
    assert not needed[3].any() and needed[:3].any()
    on_host = fa.block_ranges(seg, bq, bk, True, xp=np,
                              sliding_window=window)
    in_program = jax.jit(
        lambda s: fa.block_ranges(s, bq, bk, True, sliding_window=window))(
        jnp.asarray(seg))
    for a, b in zip(jax.tree.leaves(on_host), jax.tree.leaves(in_program)):
        assert a.dtype == b.dtype == np.int32
        np.testing.assert_array_equal(a, np.asarray(b))
    by_q, by_k = visited_blocks(on_host, l // bq, l // bk)
    for visited in (by_q, by_k):
        assert not (needed & ~visited).any()
        assert not visited[3].any()
    np.testing.assert_array_equal(by_q, _hull(needed, axis=2))
    np.testing.assert_array_equal(by_k, _hull(needed, axis=1))
    # a pair is whole inside no window narrower than its two blocks
    assert (assert_no_edge_pairs_are_exactly_the_all_true(
        on_host, mask, bq, bk) > 0) == (window >= bq + bk)
    # and a window changes nothing of the ranges without one
    causal = fa.block_ranges(seg, bq, bk, True, xp=np)
    if window >= l:
        for a, b in zip(jax.tree.leaves(on_host), jax.tree.leaves(causal)):
            np.testing.assert_array_equal(a, b)
    else:
        assert (on_host[0][0] >= causal[0][0]).all()
        assert (on_host[1][1] <= causal[1][1]).all()


def test_no_window_is_the_ranges_of_before():
    """``sliding_window=None`` is the default everywhere and adds
    nothing: the same ranges (the kernels' own programs without a
    window are compared with the parent's by
    ``scripts/lowered_programs.py``); a window needs causality."""
    seg = packed_rows(np.random.default_rng(0), 512, 8, "both")
    for a, b in zip(
            jax.tree.leaves(fa.block_ranges(seg, 32, 64, xp=np)),
            jax.tree.leaves(fa.block_ranges(seg, 32, 64, xp=np,
                                            sliding_window=None))):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(*make_inputs(np.random.default_rng(0), l=128),
                           causal=False, sliding_window=8)


@pytest.mark.parametrize("row,window,want", [
    (4096, 512, (30, 72, 0)), (4096, None, (72, 72, 56)),
    (4096, 4096, (72, 72, 56)), (2048, 512, (14, 20, 0)),
    (4096, 1, (16, 72, 0)), (4096, 768, (36, 72, 7)),
    (4096, 1024, (42, 72, 14))])
def test_block_counts_under_a_window(row, window, want):
    """One document a row at the kernels' blocks (256 x 512): the
    benchmark's sixth cell visits 30 of its 72 causal block pairs in a
    window layer, and an edge (the diagonal or the window's) crosses
    every one of them; in a full layer 56 of the 72 lie off the
    diagonal and are not masked."""
    seg = np.ones((1, row), np.int32)
    assert fa.block_counts(seg, sliding_window=window) == want


@pytest.mark.parametrize("window", [5, 32, 64, 70, 200])
def test_windowed_kernels_match_the_xla_mask(window, interpreted_kernels):
    """Forward and all three gradients of the windowed kernels (in
    interpret mode, blocks of 32 x 64: windows below, at and above
    both) against the XLA path's explicit mask, on packed rows whose
    documents' edges lie inside a window's reach."""
    rng = np.random.default_rng(11)
    b, l, nq, nkv, hd = 3, 256, 2, 1, 32
    seg_np = packed_rows(rng, l, 1, "both")[1:]
    seg = jnp.asarray(seg_np)
    q, k, v, w = (jnp.asarray(rng.standard_normal((b, l, n, hd)),
                              jnp.float32) for n in (nq, nkv, nkv, nq))
    valid = jnp.asarray(seg_np != 0)[..., None, None]

    def run(attn):
        def loss(q, k, v):
            out = attn(q, k, v, seg, sliding_window=window)
            return (jnp.where(valid, out, 0.0) * w).sum(), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return [np.asarray(x) for x in (out,) + grads]

    with interpreted_kernels():
        got = run(functools.partial(fa.flash_attention, block_q=32,
                                    block_k=64))
    want = run(packed_attention_xla)
    visited, diagonal, _ = fa.block_counts(seg_np, 32, 64,
                                           sliding_window=window)
    assert visited < diagonal
    for name, a, ref in zip(("out", "dq", "dk", "dv"), got, want):
        keep = (seg_np != 0) if name == "out" else np.ones_like(seg_np, bool)
        np.testing.assert_allclose(a[keep], ref[keep], rtol=5e-3,
                                   atol=5e-3, err_msg=name)
    # a window of 1 more is another function where a document is
    # longer than the window: the comparison can tell
    longest = max(int(np.bincount(row)[1:].max()) for row in seg_np
                  if row.any())
    wider = np.asarray(packed_attention_xla(q, k, v, seg,
                                            sliding_window=window + 1))
    assert (np.abs(wider - want[0])[seg_np != 0].max() > 1e-2) \
        == (window < longest)


def test_packed_attention_hands_the_window_to_the_kernel(monkeypatch):
    """The dispatcher no longer turns a windowed layer away from the
    flash kernel: with Pallas on it gets the window."""
    from realhf_tpu.ops import attention
    seen = {}

    def fake(q, k, v, seg, **kw):
        seen.update(kw)
        return q
    monkeypatch.setattr(attention, "pallas_enabled", lambda: True)
    monkeypatch.setattr(fa, "flash_attention", fake)
    q, k, v, seg = make_inputs(np.random.default_rng(0), l=128, hd=64)
    attention.packed_attention(q, k, v, seg, sliding_window=17)
    assert seen["sliding_window"] == 17
    assert attention.flash_takes(128, 64) and not attention.flash_takes(
        100, 64)


@pytest.mark.parametrize("heads,row,asked", [
    ((14, 2, 64), 4096, False), ((32, 8, 128), 2048, False),
    ((16, 16, 128), 2048, False), ((32, 8, 64), 4096, False),
    ((48, 8, 128), 4096, True), ((64, 8, 128), 4096, True)],
    ids=["qwen", "mistral", "olmoe", "lfm2", "laguna_full",
         "laguna_window"])
def test_only_a_row_of_4096_at_heads_of_128_asks_for_more_vmem(
        heads, row, asked, monkeypatch):
    """The dkv pass keeps Q, dO, lse, delta and the query-side segment
    ids whole a head: at heads of 128 and rows of 4096 that is past
    the default scoped VMEM inside a whole train program (PERF.md, PR
    33), so the call asks for what it holds and a quarter more. Every
    shape the accepted cells run fits the default and passes NO
    compiler parameter: its program is the parent's."""
    limits = []
    real = fa._vmem_limit
    monkeypatch.setattr(fa, "_vmem_limit", lambda *a: limits.append(
        real(*a)) or limits[-1])
    nq, nkv, hd = heads
    q, k, v = (jax.ShapeDtypeStruct((1, row, n, hd), jnp.bfloat16)
               for n in (nq, nkv, nkv))
    seg = jax.ShapeDtypeStruct((1, row), jnp.int32)
    jax.make_jaxpr(lambda q, k, v, s: jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v, s).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v))(q, k, v, seg)
    fwd, dq, dkv = limits
    assert fwd is None and dq is None
    assert (dkv is not None) == asked
    if asked:
        assert fa.DEFAULT_SCOPED_VMEM < dkv < 2 * fa.DEFAULT_SCOPED_VMEM


@pytest.mark.parametrize("nq,nkv,hd,window", [(4, 2, 32, None),
                                              (2, 2, 64, None),
                                              (4, 1, 128, 40)],
                         ids=["gqa_hd32", "mha_hd64", "window_hd128"])
def test_the_saved_log_sum_exp_broadcasts_back_to_the_kernels_own(
        nq, nkv, hd, window, interpreted_kernels):
    """The forward rule keeps ONE float32 a (row, head) of the
    kernel's lane-broadcast ``[B, nq, L, 128]`` log-sum-exp
    (``RESIDUAL_NAMES``) and the backward broadcasts it again: bit for
    bit the array the kernel wrote, padding rows' ``NEG_INF``
    included, so the backward kernels read what they always read."""
    rng = np.random.default_rng(7)
    q, k, v, seg = make_inputs(rng, b=2, l=128, nq=nq, nkv=nkv, hd=hd)
    with interpreted_kernels():
        out, lanes = fa._flash_fwd(q, k, v, seg, hd ** -0.5, True, 64, 64,
                                   window)
        kept_out, res = fa._flash_attention_fwd(
            q, k, v, seg, hd ** -0.5, True, 64, 64, window)
    assert lanes.shape == (2, nq, 128, fa.LANES)
    kept = res[-1]
    assert kept.shape == (2, nq, 128) and kept.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(jnp.broadcast_to(kept[..., None], lanes.shape)),
        np.asarray(lanes))
    assert (np.asarray(kept)[:, 0][np.asarray(seg) == 0] == fa.NEG_INF).all()
    # the output is kept head-major, as the kernel wrote it
    np.testing.assert_array_equal(np.asarray(res[-2]), np.asarray(out))
    np.testing.assert_array_equal(
        np.asarray(kept_out), np.asarray(out).transpose(0, 2, 1, 3))


# ----------------------------------------------------------------------
# A key wider than its value (latent attention)
# ----------------------------------------------------------------------
def _two_widths(rng, b, l, nq, nkv, hd, hv, n_segs=3):
    q, k, _, seg = make_inputs(rng, b=b, l=l, nq=nq, nkv=nkv, hd=hd,
                               n_segs=n_segs)
    v = jnp.asarray(rng.standard_normal((b, l, nkv, hv)), jnp.float32)
    return q, k, v, seg


@pytest.mark.parametrize("nq,nkv,hd,hv,window", [
    (16, 16, 192, 128, None), (4, 2, 24, 12, None), (4, 4, 64, 128, None),
    (2, 2, 192, 128, 40)],
    ids=["moonlight_16x192x128", "gqa_24x12", "value_wider", "windowed"])
def test_kernels_at_two_widths_match_the_xla_mask(nq, nkv, hd, hv, window,
                                                  interpreted_kernels):
    """Scores over a key ``hd`` wide, values and output ``hv`` wide
    (Moonlight's 192 and 128 among them, 192 no multiple of the 128
    lanes): the forward and all three gradients of the kernels, in
    interpret mode, against the XLA path's explicit mask on packed
    rows with padding; the scale is the KEY's width's."""
    rng = np.random.default_rng(11)
    q, k, v, seg = _two_widths(rng, 1, 128, nq, nkv, hd, hv, n_segs=2)
    valid = jnp.where(seg[..., None, None] != 0, 1.0, 0.0)
    w = jnp.asarray(rng.standard_normal((1, 128, nq, hv)), jnp.float32)

    def loss(attn):
        def f(q, k, v):
            o = attn(q, k, v, seg, sliding_window=window)
            assert o.shape == (1, 128, nq, hv)
            return (o * valid * w).sum(), o
        return f

    (_, want), gr = jax.value_and_grad(
        loss(packed_attention_xla), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    with interpreted_kernels():
        (_, got), gf = jax.value_and_grad(loss(functools.partial(
            fa.flash_attention, block_q=64, block_k=64)),
            argnums=(0, 1, 2), has_aux=True)(q, k, v)
    keep = np.asarray(seg) != 0
    np.testing.assert_allclose(np.asarray(got)[keep], np.asarray(want)[keep],
                               rtol=2e-3, atol=2e-3)
    for a, b, name in zip(gr, gf, "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=5e-3,
                                   atol=5e-3, err_msg=f"d{name} mismatch")
    # the scale: 192 ** -0.5, not the value's 128 ** -0.5
    if (hd, hv) == (192, 128) and window is None:
        other = packed_attention_xla(q, k, v, seg, scale=hv ** -0.5)
        assert np.abs(np.asarray(other - want))[keep].max() > 0.05


def test_equal_widths_keep_the_kernels_jaxprs():
    """With the value as wide as the key the kernels are what they
    were: the jaxpr of forward and gradients names no shape or
    constant that the second width could have moved (the six accepted
    cells' lowered programs are byte-equal to the parent's,
    ``scripts/lowered_programs.py``); here: the residuals and every
    ``pallas_call``'s operands carry ONE width."""
    q, k, v = (jax.ShapeDtypeStruct((1, 256, n, 128), jnp.bfloat16)
               for n in (4, 2, 2))
    seg = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    text = str(jax.make_jaxpr(lambda q, k, v, s: jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v, s).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v))(q, k, v, seg))
    assert text.count("pallas_call") == 3
    assert ",192]" not in text
    wide = jax.ShapeDtypeStruct((1, 256, 2, 192), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda q, k, v, s: jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v, s).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v))(
        jax.ShapeDtypeStruct((1, 256, 4, 192), jnp.bfloat16), wide, v, seg))
    assert "f32[1,4,256,192]" in text and "f32[1,4,256,128]" in text


@pytest.mark.parametrize("window,loops", [(None, 3), (768, 3), (767, 1),
                                          (512, 1)])
def test_a_window_narrower_than_a_pair_keeps_one_loop(window, loops):
    """A pair that no edge crosses spans ``bq + bk - 1`` positions: a
    window under 256 + 512 holds none whatever the segments, and each
    of that layer's three kernels keeps the ONE loop it had (the
    benchmark's sixth cell's window of 512); every other call runs the
    pairs before, inside and after the sub-range as three."""
    q, k, v = (jax.ShapeDtypeStruct((1, 4096, n, 128), jnp.bfloat16)
               for n in (4, 2, 2))
    seg = jax.ShapeDtypeStruct((1, 4096), jnp.int32)
    text = str(jax.make_jaxpr(lambda q, k, v, s: jax.grad(
        lambda q, k, v: fa.flash_attention(
            q, k, v, s, sliding_window=window).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v))(q, k, v, seg))
    assert text.count("pallas_call") == 3
    assert text.count(" while[") == 3 * loops
    assert (fa.block_counts(np.ones((1, 4096), np.int32),
                            sliding_window=window)[2] > 0) == (loops == 3)


def test_the_kept_residuals_have_the_values_width(interpreted_kernels):
    """``flash_out`` is the VALUE's width (128 of Moonlight's 192): the
    kept output head-major ``[B, nq, L, hv]``, q kept at the key's."""
    rng = np.random.default_rng(13)
    q, k, v, seg = _two_widths(rng, 1, 128, 2, 2, 24, 12)
    with interpreted_kernels():
        out, res = fa._flash_attention_fwd(q, k, v, seg, 24 ** -0.5, True,
                                           64, 64, None)
    assert out.shape == (1, 128, 2, 12)
    assert res[0].shape == (1, 128, 2, 24)       # q, the key's width
    assert res[4].shape == (1, 2, 128, 12)       # flash_out
    assert res[5].shape == (1, 2, 128)           # flash_lse


def test_flash_takes_and_the_limit_speak_of_the_keys_width():
    from realhf_tpu.ops import attention
    assert attention.flash_takes(4096, key_dim=192)
    assert not attention.flash_takes(4096, key_dim=32)
    q = jnp.zeros((1, fa.FLASH_MAX_LEN + 128, 1, 192), jnp.bfloat16)
    v = jnp.zeros((1, fa.FLASH_MAX_LEN + 128, 1, 128), jnp.bfloat16)
    # (past the rows whose K and V the kernels hold whole, two widths
    # are refused by name: the kernels that stream them take one)
    with pytest.raises(NotImplementedError, match="key 192, value 128"):
        fa.flash_attention(q, q, v, jnp.ones(q.shape[:2], jnp.int32))


def test_the_dkv_pass_at_moonlights_widths_asks_for_more_vmem(monkeypatch):
    """Q whole a head is 192 wide and dO 128: with the lane-broadcast
    log-sum-exp and delta the dkv pass at rows of 4096 holds 17.3 MB
    twice-buffered and asks for its own limit, as Laguna's does; the
    forward and dq passes fit the default."""
    limits = []
    real = fa._vmem_limit
    monkeypatch.setattr(fa, "_vmem_limit", lambda *a: limits.append(
        real(*a)) or limits[-1])
    q, k = (jax.ShapeDtypeStruct((1, 4096, 16, 192), jnp.bfloat16),) * 2
    v = jax.ShapeDtypeStruct((1, 4096, 16, 128), jnp.bfloat16)
    seg = jax.ShapeDtypeStruct((1, 4096), jnp.int32)
    jax.make_jaxpr(lambda q, k, v, s: jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v, s).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v))(q, k, v, seg)
    fwd, dq, dkv = limits
    assert fwd is None and dq is None
    assert fa.DEFAULT_SCOPED_VMEM < dkv < 2 * fa.DEFAULT_SCOPED_VMEM


# ----------------------------------------------------------------------
# Rows past FLASH_MAX_LEN: the kernels that stream their blocks
# ----------------------------------------------------------------------
@pytest.fixture
def stream_above(monkeypatch):
    """Lower the longest row the whole-row kernels take, so that a row
    the interpreter can afford goes to the ``*_stream`` kernels (the
    dispatch reads the module's constant when a call is traced)."""
    def at(limit):
        monkeypatch.setattr(fa, "FLASH_MAX_LEN", limit)
    return at


def _stream_rows(kind, l):
    """``[2, l]`` segment ids: ``one`` document a row; ``packed``:
    documents off the block grid and padding at the end; ``padding``:
    whole blocks of padding between and after two documents (a query
    block and a key block that visit nothing); ``late``: a document
    that starts inside a query block and past that block's first
    visited key block (rows whose FIRST block of keys is masked whole
    and a later one is not: the running maximum stays ``NEG_INF``
    there, and the next block's ``alpha`` wipes what it gathered), and
    a row whose padding ends inside a query block (rows that see no
    key beside rows that do)."""
    seg = np.ones((2, l), np.int32)
    if kind == "late":
        start = l // 2 + l // 16 + 6
        seg[0, start:l - l // 8], seg[0, l - l // 8:] = 2, 0
        seg[1, :l // 4 + 5], seg[1, l // 4 + 5:] = 0, 3
    if kind == "packed":
        edges = [0, l // 5 + 3, l // 2 - 7, l - l // 8 - 1]
        seg[:] = 0
        for j, (a, b) in enumerate(zip(edges, edges[1:])):
            seg[0, a:b] = j + 1
        seg[1, :l // 3 + 1], seg[1, l // 3 + 1:] = 5, 2
    if kind == "padding":
        seg[:] = 0
        seg[0, :l // 4], seg[0, l // 2:l // 2 + l // 8] = 1, 2
        seg[1, l // 4:l // 2] = 3
    return seg


_GQA = (4, 2, 32, 512, (64, 128))
_HEADS = {"smallthinkers_28x4": (28, 4, 32, 256, (32, 64)),
          "mha": (8, 8, 32, 256, (64, 32)),
          "two_steps_a_kv_head": (16, 1, 32, 256, (32, 64))}
#: the chip's lane arithmetic: a value of 128 lanes and key blocks of
#: 512, where the forward TILES its lane-broadcast maximum over the
#: scores (under 128 it takes the first lanes)
_CHIP_LANES = (2, 1, 128, 1024, (256, 512))


def _reference_lse(q, k, seg, window):
    """The log-sum-exp a (row, head) of the masked scores, ``[B, nq,
    L]``, ``NEG_INF`` where a row sees no key: what the forward kernel
    hands its backward."""
    b, l, nq, hd = q.shape
    mask = np.asarray(_segment_mask(seg, seg, True, window))[:, None]
    scores = np.einsum("blhd,bmhd->bhlm", np.asarray(q, np.float64),
                       np.repeat(np.asarray(k, np.float64),
                                 nq // k.shape[2], axis=2)) * hd ** -0.5
    scores = np.where(mask, scores, -np.inf)
    top = np.where(mask.any(-1), scores.max(-1), 0.0)
    with np.errstate(divide="ignore"):
        lse = top + np.log(np.exp(scores - top[..., None]).sum(-1))
    return np.where(mask.any(-1), lse, fa.NEG_INF)


@pytest.mark.parametrize("nq,nkv,hd,l,blocks,kind,window", [
    pytest.param(*_GQA, kind, window, id=f"gqa_4x2-{kind}-{window}")
    for kind, window in (("one", None), ("packed", None),
                         ("padding", None), ("one", 100), ("packed", 70),
                         ("packed", 300), ("late", None), ("late", 70))] + [
    pytest.param(*heads, kind, window, id=f"{name}-{kind}-{window}")
    for name, heads in _HEADS.items()
    for kind, window in (("packed", None), ("packed", 70), ("late", None))
] + [pytest.param(*_CHIP_LANES, kind, window,
                  id=f"chip_lanes-{kind}-{window}")
     for kind, window in (("late", None), ("padding", 600))])
def test_stream_kernels_match_the_xla_mask(nq, nkv, hd, l, blocks, kind,
                                           window, stream_above,
                                           interpreted_kernels):
    """Forward and all three gradients of the kernels that stream their
    blocks (interpret mode; the whole-row limit lowered to 64) against
    the XLA path's explicit mask: one document, packed documents off
    the block grid, whole blocks of padding, windows under and over a
    pair of blocks, SmallThinker's 28 query heads over 4 (seven heads a
    grid step from one fetched block), every head with keys of its own
    (one head a step) and 16 heads over ONE key/value head (two steps
    of 8, whose partial dK and dV are summed outside). The forward's
    second output too, the log-sum-exp its online softmax ends on
    (every lane of a row the same number), and what a row that sees no
    key is left with: output 0, ``NEG_INF`` there, finite gradients."""
    stream_above(64)
    rng = np.random.default_rng(l + nq)
    seg_np = _stream_rows(kind, l)
    seg = jnp.asarray(seg_np)
    q, k, v, w = (jnp.asarray(rng.standard_normal((2, l, n, hd)),
                              jnp.float32) for n in (nq, nkv, nkv, nq))
    valid = jnp.asarray(seg_np != 0)[..., None, None]
    assert fa.stream_heads(nq // nkv) == {2: 2, 7: 7, 1: 1, 16: 8}[nq // nkv]
    if kind == "late":  # the late document's first key block is masked whole
        late = int(np.argmax(seg_np[0] == 2))
        (lo, _), _, _ = fa.block_ranges(seg_np, *blocks, xp=np,
                                        sliding_window=window)
        assert (lo[0, late // blocks[0]] + 1) * blocks[1] <= late

    def run(attn):
        def loss(q, k, v):
            out = attn(q, k, v, seg, sliding_window=window)
            return (jnp.where(valid, out, 0.0) * w).sum(), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return [np.asarray(x) for x in (out,) + grads]

    with interpreted_kernels():
        got = run(functools.partial(fa.flash_attention, block_q=blocks[0],
                                    block_k=blocks[1]))
        lse = np.asarray(jax.jit(lambda q, k, v: fa._flash_fwd(
            q, k, v, seg, hd ** -0.5, True, *blocks, window)[1])(q, k, v))
    want = run(packed_attention_xla)
    assert all(np.isfinite(a).all() for a in got)
    assert (lse == lse[..., :1]).all()
    np.testing.assert_allclose(lse[..., 0], _reference_lse(q, k, seg, window),
                               rtol=1e-4, atol=1e-4)
    for name, a, ref in zip(("out", "dq", "dk", "dv"), got, want):
        keep = (seg_np != 0) if name == "out" else np.ones_like(seg_np, bool)
        np.testing.assert_allclose(a[keep], ref[keep], rtol=5e-3,
                                   atol=5e-3, err_msg=name)
    # padding rows are exactly zero, as the whole-row kernels leave them
    if (seg_np == 0).any():
        assert np.abs(got[0][seg_np == 0]).max() == 0.0


def test_stream_kernels_on_a_row_of_8192(interpreted_kernels):
    """At the module's own limit: a row of 8192 (small heads, blocks of
    256 x 512 as on the chip) takes the stream kernels with nothing
    lowered, under a window of 4096 that bites, and gives the XLA
    path's output and gradients."""
    rng = np.random.default_rng(8)
    l, nq, nkv, hd, window = 8192, 2, 1, 8, 4096
    assert fa.row_streams(l) and not fa.row_streams(fa.FLASH_MAX_LEN)
    seg_np = np.ones((1, l), np.int32)
    seg_np[0, 6000:] = 2
    seg = jnp.asarray(seg_np)
    q, k, v, w = (jnp.asarray(rng.standard_normal((1, l, n, hd)),
                              jnp.float32) for n in (nq, nkv, nkv, nq))

    def run(attn):
        def loss(q, k, v):
            out = attn(q, k, v, seg, sliding_window=window)
            return (out * w).sum(), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return [np.asarray(x) for x in (out,) + grads]

    with interpreted_kernels():
        got = run(fa.flash_attention)
    want = run(packed_attention_xla)
    visited, causal, _ = fa.block_counts(seg_np, sliding_window=window)
    assert visited < fa.block_counts(seg_np)[0] < causal
    for name, a, ref in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, ref, rtol=5e-3, atol=5e-3,
                                   err_msg=name)


@pytest.mark.parametrize("l,window,steps", [
    (16384, None, (32, 64)), (16384, 4096, (9, 18)), (8192, 4096, (9, 18)),
    (8192, 100, (2, 3))])
def test_the_stream_grids_last_axis_is_the_longest_range(l, window, steps):
    """The kernels' last grid axis is as long as the longest range a
    block of a row of ONE document has: 32 key blocks of 512 a query
    block in a full layer at 16,384 and 9 under a window of 4096 (the
    twelfth cell's two kinds of layer); and every packed row's ranges
    lie inside those, block by block."""
    assert fa._stream_steps(l, 256, 512, True, window) == steps
    rng = np.random.default_rng(l)
    seg = packed_rows(rng, l, 1, "both")
    (lo, hi), (q_lo, q_hi), _ = fa.block_ranges(
        seg, 256, 512, xp=np, sliding_window=window)
    assert np.maximum(hi - lo, 0).max() <= steps[0]
    assert np.maximum(q_hi - q_lo, 0).max() <= steps[1]


def test_stream_kernels_are_three_calls_named_apart():
    """A row past ``FLASH_MAX_LEN`` lowers to three ``pallas_call``s
    named ``flash_fwd_stream``, ``flash_bwd_dq_stream`` and
    ``flash_bwd_dkv_stream`` on a grid of FOUR axes with no loop inside
    (the range is the last axis); a row at the limit keeps the kernels
    it had, their three loops and their names; the residuals a
    rematerialised block keeps are the same two, by the same names."""
    def jaxpr(l, window=None):
        q, k, v = (jax.ShapeDtypeStruct((1, l, n, 128), jnp.bfloat16)
                   for n in (28, 4, 4))
        seg = jax.ShapeDtypeStruct((1, l), jnp.int32)
        return str(jax.make_jaxpr(lambda q, k, v, s: jax.grad(
            lambda q, k, v: fa.flash_attention(
                q, k, v, s, sliding_window=window).astype(
                    jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v))(q, k, v, seg))

    long = jaxpr(16384, 4096)
    assert long.count("pallas_call") == 3 and " while[" not in long
    for name in ("flash_fwd_stream", "flash_bwd_dq_stream",
                 "flash_bwd_dkv_stream"):
        assert f"name={name}" in long
    assert "grid=(1, 4, 64, 9)" in long and "grid=(1, 4, 32, 18)" in long
    for name in fa.RESIDUAL_NAMES:
        assert f"name={name}" in long
    # K and V a key block of 512, Q seven heads of a query block of 256
    assert "bf16[1,1,512,128]" in long or "(1, 1, 512, 128)" in long
    short = jaxpr(fa.FLASH_MAX_LEN)
    assert short.count("pallas_call") == 3 and "_stream" not in short
    assert short.count(" while[") == 3 * 3


def test_what_the_stream_kernels_do_not_take_raises_by_name():
    q, k, v = (jnp.zeros((1, fa.FLASH_MAX_LEN + 512, n, 64), jnp.bfloat16)
               for n in (2, 1, 1))
    seg = jnp.ones((1, q.shape[1]), jnp.int32)
    with pytest.raises(NotImplementedError, match="flash_\\*_stream"):
        fa.flash_attention(q, k, v, seg, select=jnp.ones(
            (1, q.shape[1], q.shape[1]), jnp.int8))
    with pytest.raises(NotImplementedError, match="key 64, value 128"):
        fa.flash_attention(q, k, jnp.zeros(
            (1, q.shape[1], 1, 128), jnp.bfloat16), seg)
    long = jnp.zeros((1, fa.FLASH_STREAM_MAX_LEN + 512, 1, 64),
                     jnp.bfloat16)
    with pytest.raises(ValueError, match="FLASH_STREAM_MAX_LEN=32768"):
        fa.flash_attention(long, long, long, jnp.ones(
            (1, long.shape[1]), jnp.int32))
