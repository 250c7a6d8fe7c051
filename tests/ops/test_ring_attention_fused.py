"""Fused-RDMA ring attention vs the shard_map/ppermute formulation:
token-for-token parity on the virtual mesh (Pallas TPU interpret mode
emulates the remote DMAs and remote semaphore signals on CPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from realhf_tpu.ops.ring_attention import ring_attention
from realhf_tpu.ops.ring_attention_fused import ring_attention_fused


def ctx_mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]).reshape(n), ("ctx",))


def make_inputs(b=1, l=64, nq=2, nkv=1, hd=8, seed=0, n_seqs=2):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(b, l, nq, hd)).astype(np.float32)
    k = rng.normal(size=(b, l, nkv, hd)).astype(np.float32)
    v = rng.normal(size=(b, l, nkv, hd)).astype(np.float32)
    # packed segments: n_seqs per row plus trailing padding
    seg = np.zeros((b, l), np.int32)
    for bi in range(b):
        bounds = np.sort(rng.choice(
            np.arange(8, l - 8), size=n_seqs - 1, replace=False))
        prev, sid = 0, 1
        for e in list(bounds) + [l - 4]:  # last 4 tokens = padding
            seg[bi, prev:e] = sid
            prev, sid = e, sid + 1
    return (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(seg))


@pytest.mark.parametrize("causal", [True, False])
def test_fused_matches_ppermute(causal):
    mesh = ctx_mesh(4)
    # the one case with a batch of two rows; interpret-mode cost grows
    # with the grid, so the other cases keep one
    q, k, v, seg = make_inputs(b=2 if causal else 1)
    ref = jax.jit(lambda *a: ring_attention(
        *a, mesh=mesh, causal=causal))(q, k, v, seg)
    got = jax.jit(lambda *a: ring_attention_fused(
        *a, mesh=mesh, causal=causal, interpret=True))(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fused_sliding_window():
    mesh = ctx_mesh(4)
    q, k, v, seg = make_inputs(seed=3)
    ref = jax.jit(lambda *a: ring_attention(
        *a, mesh=mesh, sliding_window=24))(q, k, v, seg)
    got = jax.jit(lambda *a: ring_attention_fused(
        *a, mesh=mesh, sliding_window=24, interpret=True))(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fused_ring8_blocked():
    """8-way ring with a local shard bigger than one block (several
    inner k-blocks per round) and uneven GQA grouping."""
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("ctx",))
    # the emulated ring costs ~6 s per (head, q-block) cell on eight
    # devices, whatever the block holds: two heads sharing one KV
    # head, one q-block, two k-blocks a round
    q, k, v, seg = make_inputs(b=1, l=128, nq=2, nkv=1, seed=5)
    ref = jax.jit(lambda *a: ring_attention(
        *a, mesh=mesh))(q, k, v, seg)
    got = jax.jit(lambda *a: ring_attention_fused(
        *a, mesh=mesh, block_q=16, block_k=8,
        interpret=True))(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fused_gradients_match():
    """custom_vjp delegates backward to the unfused path: grads match
    the pure shard_map formulation exactly (same bwd computation)."""
    mesh = ctx_mesh(4)
    q, k, v, seg = make_inputs(b=1, l=32, nq=2, nkv=1, hd=8, seed=7)

    def loss_ref(q, k, v):
        return (ring_attention(q, k, v, seg, mesh) ** 2).sum()

    def loss_fused(q, k, v):
        return (ring_attention_fused(
            q, k, v, seg, mesh, interpret=True) ** 2).sum()

    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    g_fused = jax.jit(jax.grad(loss_fused, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_engine_wiring_flag(monkeypatch):
    """REALHF_TPU_FUSED_RING=1 routes a ctx-mesh engine's attention
    through the fused kernel; forward logprobs match the unfused
    engine on the same weights."""
    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.models import transformer as T
    from realhf_tpu.models.config import TransformerConfig
    from realhf_tpu.parallel.mesh import (
        MeshContext,
        ParallelismConfig,
        make_mesh,
    )

    cfg = TransformerConfig(
        n_layers=1, n_kv_heads=1, n_q_heads=2, hidden_dim=32,
        intermediate_dim=64, vocab_size=128, apply_rotary=True,
        layer_norm_type="rms", mlp_type="llama",
        use_attention_bias=False, use_attn_proj_bias=False,
        use_mlp_bias=False, activation_function="silu",
        compute_dtype="float32")
    par = ParallelismConfig(data_parallel_size=2,
                            context_parallel_size=4)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(
        1, 100, size=(2, 32)).astype(np.int32)
    seg = np.ones_like(ids)

    def build(flag):
        if flag:
            monkeypatch.setenv("REALHF_TPU_FUSED_RING", "1")
        else:
            monkeypatch.delenv("REALHF_TPU_FUSED_RING", raising=False)
        ctx = MeshContext(ModelName("t", 0), make_mesh(par), par)
        return Engine(cfg, ctx, jax.tree.map(jnp.copy, params))

    ref_eng = build(False)
    assert ref_eng.attention_fn_inference is None
    lp_ref = np.asarray(ref_eng.forward_logprobs(ids, seg))
    fused_eng = build(True)
    # the flag really engaged (guards against the parity assert
    # passing vacuously if the wiring regresses)
    assert fused_eng.attention_fn_inference is not None
    lp_fused = np.asarray(fused_eng.forward_logprobs(ids, seg))
    np.testing.assert_allclose(lp_fused, lp_ref, rtol=2e-4, atol=2e-4)


def test_bidirectional_plan_and_parity():
    """_plan_dirs splits when halves tile (and not otherwise), and the
    uni- vs bidirectional kernels agree exactly on the same inputs."""
    from realhf_tpu.ops.ring_attention_fused import _plan_dirs

    assert _plan_dirs(16, 512, True)[0] == 2   # halves of 8 tile
    assert _plan_dirs(8, 512, True)[0] == 1    # half of 4 would not
    assert _plan_dirs(16, 512, False)[0] == 1  # opt-out honored
    nd, lch, bk = _plan_dirs(64, 16, True)
    assert (nd, lch, bk) == (2, 32, 16)

    mesh = ctx_mesh(4)
    q, k, v, seg = make_inputs(seed=11)
    uni = jax.jit(lambda *a: ring_attention_fused(
        *a, mesh=mesh, bidirectional=False, interpret=True))(
            q, k, v, seg)
    bidi = jax.jit(lambda *a: ring_attention_fused(
        *a, mesh=mesh, bidirectional=True, interpret=True))(
            q, k, v, seg)
    np.testing.assert_allclose(np.asarray(bidi), np.asarray(uni),
                               rtol=2e-5, atol=2e-5)


def test_plan_dirs_falls_back_on_untileable_half():
    """lc=24, block_k=8: the half (12) has no >=8 divisor <= 8 but the
    full shard tiles (24 % 8 == 0) -- must fall back, not raise."""
    from realhf_tpu.ops.ring_attention_fused import _plan_dirs

    assert _plan_dirs(24, 8, True) == (1, 24, 8)
