"""Ring attention (context parallelism) vs the single-device reference:
forward and gradients on a mesh with a ctx axis, packed segments and
causal masking preserved across shards."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from realhf_tpu.ops.attention import packed_attention_xla
from realhf_tpu.ops.ring_attention import ring_attention


def ctx_mesh(n):
    devs = np.array(jax.devices("cpu")[:n]).reshape(1, n)
    return Mesh(devs, ("data", "ctx"))


def make_inputs(rng, b=2, l=64, nq=4, nkv=2, hd=16):
    q = jnp.asarray(rng.standard_normal((b, l, nq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, l, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, l, nkv, hd)), jnp.float32)
    seg = np.zeros((b, l), np.int32)
    for bi in range(b):
        cut = int(rng.integers(l // 4, 3 * l // 4))
        seg[bi, :cut] = 1
        seg[bi, cut:] = 2
        seg[bi, l - int(rng.integers(0, l // 8)):] = 0  # trailing pad
    return q, k, v, jnp.asarray(seg)


@pytest.mark.parametrize("n_ctx", [2, 4, 8])
@pytest.mark.parametrize("causal", [True, False])
# a window shorter than the smallest shard (64 / 8 positions): the mask
# is on GLOBAL positions, across shard boundaries
@pytest.mark.parametrize("sliding_window", [None, 5])
def test_matches_reference(n_ctx, causal, sliding_window):
    rng = np.random.default_rng(0)
    q, k, v, seg = make_inputs(rng)
    ref = packed_attention_xla(q, k, v, seg, causal=causal,
                               sliding_window=sliding_window)
    mesh = ctx_mesh(n_ctx)

    @jax.jit
    def run(q, k, v, seg):
        return ring_attention(q, k, v, seg, mesh, "ctx", causal=causal,
                              sliding_window=sliding_window)

    got = run(q, k, v, seg)
    valid = np.asarray(seg) != 0
    np.testing.assert_allclose(np.asarray(got)[valid],
                               np.asarray(ref)[valid], rtol=2e-4, atol=2e-4)


def test_gradients_match_reference():
    rng = np.random.default_rng(1)
    q, k, v, seg = make_inputs(rng, l=32)
    mesh = ctx_mesh(4)
    w = jnp.where(seg[..., None, None] != 0, 1.0, 0.0)

    def loss_ref(q, k, v):
        return (packed_attention_xla(q, k, v, seg) * w).sum()

    def loss_ring(q, k, v):
        return (ring_attention(q, k, v, seg, mesh, "ctx") * w).sum()

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gg = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gr, gg, "qkv"):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-3,
                                   atol=1e-4, err_msg=f"d{name}")


def test_sharded_inputs_stay_sharded():
    """With inputs actually sharded over ctx, the output keeps the
    sharding (no implicit all-gather of the sequence dim)."""
    rng = np.random.default_rng(2)
    q, k, v, seg = make_inputs(rng, l=64)
    mesh = ctx_mesh(8)
    sh4 = NamedSharding(mesh, P(None, "ctx", None, None))
    sh2 = NamedSharding(mesh, P(None, "ctx"))
    qs, ks, vs = (jax.device_put(x, sh4) for x in (q, k, v))
    segs = jax.device_put(seg, sh2)

    @jax.jit
    def run(q, k, v, seg):
        return ring_attention(q, k, v, seg, mesh, "ctx")

    out = run(qs, ks, vs, segs)
    assert out.sharding.spec == P(None, "ctx", None, None)
    ref = packed_attention_xla(q, k, v, seg)
    valid = np.asarray(seg) != 0
    np.testing.assert_allclose(np.asarray(out)[valid],
                               np.asarray(ref)[valid], rtol=2e-4, atol=2e-4)


def test_engine_ctx_parallel_matches_and_trains():
    """Engine with dp x ctx x tp (+Megatron-SP): forward matches the
    single-device engine and training decreases the loss through the
    ring-attention backward."""
    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.engine.optim import OptimizerConfig
    from realhf_tpu.models import transformer as T
    from realhf_tpu.models.config import TransformerConfig
    from realhf_tpu.ops import functional as F
    from realhf_tpu.parallel.mesh import (
        MeshContext, ParallelismConfig, make_mesh)

    cfg = TransformerConfig(
        n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
        intermediate_dim=64, vocab_size=64, apply_rotary=True,
        layer_norm_type="rms", mlp_type="llama", use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu", compute_dtype="float32")
    par = ParallelismConfig(data_parallel_size=2, context_parallel_size=2,
                            tensor_parallel_size=2, sequence_parallel=True)
    eng = Engine(cfg, MeshContext(ModelName("m", 0), make_mesh(par), par),
                 T.init_params(cfg, jax.random.PRNGKey(0)),
                 optimizer=OptimizerConfig(lr=5e-3,
                                           warmup_steps_proportion=0.0,
                                           lr_scheduler_type="constant"),
                 total_train_steps=50)
    single = ParallelismConfig()
    ref = Engine(cfg, MeshContext(ModelName("r", 0),
                                  make_mesh(single,
                                            devices=jax.devices("cpu")[:1]),
                                  single),
                 T.init_params(cfg, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 64, size=(2, 32)).astype(np.int32)
    seg = np.ones_like(ids)
    np.testing.assert_allclose(np.asarray(eng.forward_logprobs(ids, seg)),
                               np.asarray(ref.forward_logprobs(ids, seg)),
                               rtol=1e-4, atol=1e-5)

    def loss_fn(p, h, mb):
        lp = F.shifted_logprobs_from_hidden(cfg, p, h, mb["input_ids"],
                                            mb["seg_ids"])
        return -lp.mean(), {}

    s0 = eng.train_batch([dict(input_ids=ids, seg_ids=seg)], loss_fn,
                         loss_fn_key="cp")
    for _ in range(5):
        st = eng.train_batch([dict(input_ids=ids, seg_ids=seg)], loss_fn,
                             loss_fn_key="cp")
    assert st["loss"] < s0["loss"]

    # generation on the ctx mesh runs on the collapsed dp x tp decode
    # view (engine.decode_engine; parity pinned in
    # tests/engine/test_pp_generate.py::test_ctx_generate_matches_dense)
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    out = eng.generate(
        np.zeros((2, 8), np.int32), np.ones((2, 8), np.int32),
        np.tile(np.arange(8, dtype=np.int32), (2, 1)),
        jax.random.PRNGKey(0),
        GenerationHyperparameters(max_new_tokens=2, min_new_tokens=1),
        eos_token_id=None, pad_token_id=0)
    assert np.asarray(out.tokens).shape == (2, 2)
    assert eng.decode_engine() is not eng


@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_matches_dense(causal):
    """Long-context path: per-step attention computed in [bq, bk]
    tiles must equal the dense per-step computation."""
    rng = np.random.default_rng(7)
    q, k, v, seg = make_inputs(rng, l=64)
    mesh = ctx_mesh(2)
    dense = ring_attention(q, k, v, seg, mesh, "ctx", causal=causal,
                           block_q=1024, block_k=1024)  # lc=32: dense
    blocked = ring_attention(q, k, v, seg, mesh, "ctx", causal=causal,
                             block_q=8, block_k=16)     # lc=32: tiled
    valid = np.asarray(seg) != 0
    np.testing.assert_allclose(np.asarray(blocked)[valid],
                               np.asarray(dense)[valid],
                               atol=1e-5, rtol=1e-5)


def test_blockwise_gradients_match_dense():
    rng = np.random.default_rng(8)
    q, k, v, seg = make_inputs(rng, l=64)
    mesh = ctx_mesh(2)

    def loss(fn_kwargs):
        def f(q_, k_, v_):
            o = ring_attention(q_, k_, v_, seg, mesh, "ctx",
                               **fn_kwargs)
            return (o.astype(jnp.float32) ** 2).sum()
        # jitted: an eager shard_map runs the nested scans op by op
        return jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)

    gd = loss(dict(block_q=1024, block_k=1024))
    gb = loss(dict(block_q=8, block_k=16))
    for a, b in zip(gd, gb):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-4, rtol=1e-4)


def test_blockwise_sliding_window():
    rng = np.random.default_rng(9)
    q, k, v, seg = make_inputs(rng, l=64)
    mesh = ctx_mesh(2)
    from realhf_tpu.ops.attention import packed_attention_xla
    ref = packed_attention_xla(q, k, v, seg, sliding_window=9)
    got = ring_attention(q, k, v, seg, mesh, "ctx", sliding_window=9,
                         block_q=8, block_k=16)
    valid = np.asarray(seg) != 0
    np.testing.assert_allclose(np.asarray(got)[valid],
                               np.asarray(ref)[valid],
                               atol=1e-5, rtol=1e-5)


def test_long_context_8k_forward_backward():
    """Long-context smoke: 8k tokens at ctx=4 run forward+backward
    through the blockwise path (tile memory only -- the dense per-step
    scores would need [2k, 2k] * nq * fp32 per device)."""
    rng = np.random.default_rng(10)
    b, l, nq, nkv, hd = 1, 8192, 2, 2, 16
    q = jnp.asarray(rng.standard_normal((b, l, nq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, l, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, l, nkv, hd)), jnp.float32)
    seg = jnp.ones((b, l), jnp.int32)
    devs = np.array(jax.devices("cpu")[:4]).reshape(1, 4)
    mesh = Mesh(devs, ("data", "ctx"))

    def f(q_, k_, v_):
        o = ring_attention(q_, k_, v_, seg, mesh, "ctx",
                           block_q=512, block_k=512)
        return (o.astype(jnp.float32) ** 2).mean()

    loss, grads = jax.jit(
        jax.value_and_grad(f, argnums=(0,)))(q, k, v)
    assert np.isfinite(float(loss))
    assert np.isfinite(np.asarray(grads[0])).all()
