"""Coverage of the PALLAS decode wiring at the transformer level.

The kernel gates key on ``pallas_enabled()`` (real TPU, or the
``REALHF_TPU_FORCE_PALLAS=1`` test hook). With the hook set and
``pltpu.force_tpu_interpret_mode()`` active, ``T.prefill`` +
``T.decode_step`` run the SAME plumbing a TPU runs -- the decode
partitioning chooser and the heads-sharded / KV-sequence-split
shard_map kernel wrappers -- with interpret-mode kernels on the
virtual CPU mesh, instead of CI only ever exercising the XLA
fallbacks. One eager step keeps interpret-mode cost tractable (a full
jitted generate loop under interpret is minutes per case; the deep
scalar-prefetch stacked kernel is covered at kernel level in
tests/ops/test_sharded_kernels.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.parallel.mesh import ParallelismConfig, make_mesh

# Capability detect: interpret-mode coverage of the Pallas decode
# wiring needs jax's force_tpu_interpret_mode (newer Pallas API). On
# older jax these tests cannot run the kernel plumbing at all --
# report an attributed skip, not a permanent expected failure; the
# XLA-fallback paths stay covered by tests/engine/test_inflight.py
# and the kernel-level compiled tier in tests/ops.
pytestmark = pytest.mark.skipif(
    not hasattr(pltpu, "force_tpu_interpret_mode"),
    reason="jax.experimental.pallas.tpu lacks force_tpu_interpret_mode "
           "(old Pallas API): interpret-mode kernel plumbing cannot "
           "be exercised on this jax; XLA fallbacks covered elsewhere")


def _cfg():
    # head_dim 64: the kernel gates require hd >= 64
    return TransformerConfig(
        n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=256,
        head_dim=64, intermediate_dim=512, vocab_size=128,
        apply_rotary=True, layer_norm_type="rms", mlp_type="llama",
        use_attention_bias=False, use_attn_proj_bias=False,
        use_mlp_bias=False, activation_function="silu",
        compute_dtype="float32")


def _mesh(dp, tp):
    par = ParallelismConfig(data_parallel_size=dp,
                            tensor_parallel_size=tp)
    return make_mesh(par, devices=jax.devices("cpu")[:par.world_size])


def _one_decode_step(cfg, params, mesh):
    rng = np.random.default_rng(0)
    b, lp = 4, 8
    ids = jnp.asarray(rng.integers(1, 120, size=(b, lp)), jnp.int32)
    seg = jnp.ones((b, lp), jnp.int32)
    pos = jnp.tile(jnp.arange(lp, dtype=jnp.int32), (b, 1))
    tok = jnp.asarray(rng.integers(1, 120, size=(b,)), jnp.int32)

    # one jitted program, as generation runs it (eagerly, the
    # interpret-mode kernels under shard_map run op by op); traced
    # inside the caller's interpret-mode context and env
    @jax.jit
    def step(params, ids, seg, pos, tok):
        _, cache = T.prefill(cfg, params, ids, seg, pos,
                             total_len=lp + 8)
        new_hidden, _ = T.decode_step(cfg, params, cache, tok,
                                      jnp.full((b,), lp, jnp.int32),
                                      uniform_slot=True, mesh=mesh)
        return new_hidden

    return np.asarray(step(params, ids, seg, pos, tok))


@pytest.mark.parametrize("dp,tp,path", [(4, 2, "heads"), (2, 4, "seq")])
def test_decode_step_via_pallas_kernels(dp, tp, path, monkeypatch):
    cfg = _cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0))

    ref = _one_decode_step(cfg, params, mesh=None)  # XLA path

    from realhf_tpu.ops.decode_attention import (
        choose_decode_partitioning,
    )
    mesh = _mesh(dp, tp)
    # assert with the REAL cache length the decode below runs with
    # (round_cache_len(8 + 8) = 16), so this cannot silently claim a
    # path the exercised step does not take
    assert choose_decode_partitioning(
        mesh, 4, cfg.n_q_heads, cfg.n_kv_heads, 16) == path

    monkeypatch.setenv("REALHF_TPU_FORCE_PALLAS", "1")
    with pltpu.force_tpu_interpret_mode():
        got = _one_decode_step(cfg, params, mesh=mesh)
    np.testing.assert_allclose(got, ref, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("dp,tp", [(4, 2), (2, 4)])
def test_decode_step_stacked_scan_path(dp, tp, monkeypatch):
    """Deep-model wiring: dropping the unroll threshold forces the
    layer lax.scan with a TRACED layer index, so decode_step routes
    through the scalar-prefetch stacked kernel
    (flash_decode_attention_stacked) under both shard_map
    partitionings -- the exact path an 80-layer model decodes with."""
    cfg = _cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ref = _one_decode_step(cfg, params, mesh=None)  # unrolled XLA path

    monkeypatch.setattr(T, "_DECODE_UNROLL_MAX_LAYERS", 0)
    monkeypatch.setenv("REALHF_TPU_FORCE_PALLAS", "1")
    with pltpu.force_tpu_interpret_mode():
        got = _one_decode_step(cfg, params, mesh=_mesh(dp, tp))
    np.testing.assert_allclose(got, ref, atol=2e-4, rtol=2e-4)
