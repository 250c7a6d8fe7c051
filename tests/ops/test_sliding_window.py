"""Sliding-window attention (mistral/mixtral/qwen2 checkpoints set
``sliding_window``): the (q_idx - k_idx) < window mask must be applied
on every attention path -- packed XLA, ring, and decode -- with
identical semantics."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from realhf_tpu.ops.attention import (
    decode_attention,
    packed_attention,
    packed_attention_xla,
)
from test_flash_attention import pairs_without_a_mask_change_no_bit


def _naive(q, k, v, seg, window, causal=True):
    b, l, nq, hd = q.shape
    nkv = k.shape[2]
    group = nq // nkv
    out = np.zeros_like(np.asarray(q))
    for bi in range(b):
        for qi in range(l):
            if seg[bi, qi] == 0:
                continue
            for h in range(nq):
                kv_h = h // group
                scores = []
                idxs = []
                for ki in range(l):
                    if seg[bi, ki] != seg[bi, qi]:
                        continue
                    if causal and ki > qi:
                        continue
                    if window is not None and (qi - ki) >= window:
                        continue
                    scores.append(
                        float(np.dot(q[bi, qi, h], k[bi, ki, kv_h]))
                        * hd ** -0.5)
                    idxs.append(ki)
                if not idxs:
                    continue
                p = np.exp(scores - np.max(scores))
                p /= p.sum()
                out[bi, qi, h] = sum(
                    pi * np.asarray(v[bi, ki, kv_h])
                    for pi, ki in zip(p, idxs))
    return out


def make_inputs(rng, b=2, l=24, nq=4, nkv=2, hd=8):
    q = jnp.asarray(rng.standard_normal((b, l, nq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, l, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, l, nkv, hd)), jnp.float32)
    seg = np.zeros((b, l), np.int32)
    seg[:, :l // 2] = 1
    seg[:, l // 2:] = 2
    seg[:, -3:] = 0
    return q, k, v, np.asarray(seg)


@pytest.mark.parametrize("window", [1, 4, 100])
def test_packed_xla_matches_naive(window):
    rng = np.random.default_rng(0)
    q, k, v, seg = make_inputs(rng)
    got = np.asarray(packed_attention_xla(q, k, v, jnp.asarray(seg),
                                          sliding_window=window))
    want = _naive(np.asarray(q), np.asarray(k), np.asarray(v), seg, window)
    valid = seg != 0  # pad-row outputs are don't-care
    np.testing.assert_allclose(got[valid], want[valid], atol=1e-5)


@pytest.mark.parametrize("kw", [
    dict(window=100, rows=(0, 1)), dict(window=130, select=True),
    dict(window=97, heads=(8, 1, 32, 32), rows=(3,))],
    ids=["window", "selection", "group8"])
def test_flash_builds_no_mask_between_the_windows_edge_and_the_diagonal(
        kw, monkeypatch, interpreted_kernels):
    """The flash kernels under a window (blocks of 32 x 64: a pair
    whole inside a window of 96 or more, off the diagonal, builds no
    mask), held to the same kernels with every pair masked, bit for
    bit, and to the XLA mask above."""
    pairs_without_a_mask_change_no_bit(interpreted_kernels, monkeypatch,
                                       **kw)


def test_window_larger_than_seq_is_full_attention():
    rng = np.random.default_rng(1)
    q, k, v, seg = make_inputs(rng)
    full = packed_attention(q, k, v, jnp.asarray(seg))
    win = packed_attention(q, k, v, jnp.asarray(seg), sliding_window=10_000)
    np.testing.assert_allclose(np.asarray(full), np.asarray(win), atol=1e-6)


@pytest.mark.parametrize("n_ctx", [2, 4])
def test_ring_matches_packed(n_ctx):
    from jax.sharding import Mesh
    from realhf_tpu.ops.ring_attention import ring_attention

    rng = np.random.default_rng(2)
    q, k, v, seg = make_inputs(rng, l=32)
    mesh = Mesh(np.array(jax.devices("cpu")[:n_ctx]).reshape(1, n_ctx),
                ("data", "ctx"))
    ref = np.asarray(packed_attention_xla(q, k, v, jnp.asarray(seg),
                                          sliding_window=5))
    got = np.asarray(ring_attention(q, k, v, jnp.asarray(seg), mesh, "ctx",
                                    sliding_window=5))
    valid = seg != 0  # pad-row outputs are don't-care
    np.testing.assert_allclose(got[valid], ref[valid], atol=1e-5, rtol=1e-5)


def test_decode_matches_prefill_last_token():
    """The decode path (padded KV cache + slot index) must produce the
    same attention output as the packed path's last row."""
    rng = np.random.default_rng(3)
    b, l, nq, nkv, hd = 2, 12, 4, 2, 8
    q = jnp.asarray(rng.standard_normal((b, l, nq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, l, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, l, nkv, hd)), jnp.float32)
    seg = jnp.ones((b, l), jnp.int32)
    window = 4

    ref = packed_attention_xla(q, k, v, seg, sliding_window=window)

    s = l + 3  # padded cache (head-major [B, nkv, S, hd])
    pad = jnp.zeros((b, s - l, nkv, hd), jnp.float32)
    k_cache = jnp.concatenate([k, pad], axis=1).transpose(0, 2, 1, 3)
    v_cache = jnp.concatenate([v, pad], axis=1).transpose(0, 2, 1, 3)
    valid = jnp.concatenate(
        [jnp.ones((b, l), bool), jnp.zeros((b, s - l), bool)], axis=1)
    slot = jnp.full((b,), l - 1, jnp.int32)  # the last written token
    got = decode_attention(q[:, l - 1], k_cache, v_cache, valid,
                           sliding_window=window, slot=slot)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref[:, l - 1]),
                               atol=1e-5)


def test_transformer_forward_decode_consistency_with_window():
    """End-to-end: a model with sliding_window produces identical
    logits from the packed forward and the decode_step loop."""
    from realhf_tpu.models import transformer as T
    from realhf_tpu.models.config import TransformerConfig

    cfg = TransformerConfig(
        n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
        intermediate_dim=64, vocab_size=97, apply_rotary=True,
        layer_norm_type="rms", mlp_type="llama", use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu", compute_dtype="float32",
        sliding_window=5)
    params = T.init_params(cfg, jax.random.PRNGKey(0))

    rng = np.random.default_rng(4)
    b, l = 2, 14
    ids = jnp.asarray(rng.integers(2, cfg.vocab_size, (b, l)), jnp.int32)
    seg = jnp.ones((b, l), jnp.int32)
    h, _ = T.forward(cfg, params, ids, seg)
    want = T.lm_logits(cfg, params, h)  # [B, L, V]

    cache = T.init_kv_cache(cfg, b, l, jnp.float32)
    outs = []
    for t in range(l):
        pos = jnp.full((b,), t, jnp.int32)
        x, cache = T.decode_step(cfg, params, cache, ids[:, t], pos)
        outs.append(T.lm_logits(cfg, params, x[:, None])[:, 0])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
