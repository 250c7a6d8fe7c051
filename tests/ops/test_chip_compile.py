"""The main path's Pallas kernels, asked of the TPU v5e compiler without
a chip (section 2 of /opt/skills/guides/on-chip-measurement/SKILL.md).

Interpret mode cannot show a slice that misses the tiling or a kernel
that wants more VMEM than it may use; the chip's compiler can, and it is
installed here. Shapes are Qwen2.5-0.5B's (14 query and 2 key/value
heads of 64, bf16) at the lengths chip_smoke.py runs: packed train and
inference rows of 4096 tokens, a generation prefill of 128 prompts of
256, and a decode cache of 512 slots.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU's library, and
under pytest-xdist every worker imports this file. All such compiles
live in this one file, run in the test's own process, and go around the
persistent compile cache (a described-device executable is written to
it but cannot be read back without a chip).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from realhf_tpu.ops.attention import make_sharded_attention, packed_attention
from realhf_tpu.ops.decode_attention import (
    flash_decode_attention,
    flash_decode_attention_stacked,
)
from realhf_tpu.ops.flash_attention import FLASH_MAX_LEN, flash_attention

NQ, NKV, HD = 14, 2, 64       # Qwen2.5-0.5B attention widths
N_LAYERS = 24
ROW_LEN = 4096                # packed train / inference row of the smoke run
GEN_BATCH, PROMPT_LEN, CACHE_LEN = 128, 256, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or its lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    """Compile for the described chip; the Mosaic kernel must be in
    the program."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _qkv(sharding, b, l, nq=NQ, nkv=NKV, hd=HD):
    """Abstract q, k, v, seg_ids of one packed batch on ``sharding``."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return (sds((b, l, nq, hd), jnp.bfloat16),
            sds((b, l, nkv, hd), jnp.bfloat16),
            sds((b, l, nkv, hd), jnp.bfloat16),
            sds((b, l), jnp.int32))


def _flash_grads(q, k, v, seg):
    def loss(q, k, v):
        return flash_attention(q, k, v, seg).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("b,l", [(1, ROW_LEN), (GEN_BATCH, PROMPT_LEN)],
                         ids=["train_row", "gen_prefill"])
def test_flash_forward_compiles(one_chip, b, l):
    _compile(flash_attention, *_qkv(one_chip, b, l))


def test_flash_backward_compiles(one_chip):
    _compile(_flash_grads, *_qkv(one_chip, 1, ROW_LEN))


@pytest.mark.parametrize("nq,nkv,hd", [(NQ, NKV, HD), (32, 8, 128)],
                         ids=["hd64", "hd128"])
def test_flash_compiles_at_its_stated_limit(one_chip, nq, nkv, hd):
    """FLASH_MAX_LEN is a promise about the compiler: forward and
    backward both fit at it, for both head sizes the families use."""
    args = _qkv(one_chip, 1, FLASH_MAX_LEN, nq, nkv, hd)
    _compile(flash_attention, *args)
    _compile(_flash_grads, *args)


def test_flash_compiles_under_shard_map(topo):
    """Cell 3's layout: rows over "data", heads over "model" on a 2x2
    mesh, each shard's kernels taking their ranges from the local
    segment ids (Mistral's heads, the cell's rows of 2048)."""
    import numpy as np

    from realhf_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2),
                (DATA_AXIS, MODEL_AXIS))
    heads = NamedSharding(mesh, P(DATA_AXIS, None, MODEL_AXIS, None))
    rows = NamedSharding(mesh, P(DATA_AXIS, None))
    q, k, v, seg = _qkv(None, 2, 2048, 32, 8, 128)
    q, k, v = (jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=heads)
               for x in (q, k, v))
    seg = jax.ShapeDtypeStruct(seg.shape, seg.dtype, sharding=rows)
    attn = make_sharded_attention(
        mesh, inner=lambda *a, **kw: flash_attention(
            *a, causal=kw["causal"], scale=kw["scale"]))

    def grads(q, k, v, seg):
        def loss(q, k, v):
            return attn(q, k, v, seg).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compile(grads, q, k, v, seg).as_text()
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert kernel in text


def test_row_above_the_limit_raises_not_xla():
    """One bucket above the limit, ``packed_attention`` raises a clear
    error; it does not drop to the O(L^2) XLA path in silence."""
    q, k, v, seg = _qkv(None, 1, FLASH_MAX_LEN + 128)
    with pytest.raises(ValueError, match="FLASH_MAX_LEN"):
        jax.eval_shape(
            lambda *a: packed_attention(*a, use_flash=True), q, k, v, seg)


def _decode_args(sharding, stacked):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    cache = (GEN_BATCH, NKV, CACHE_LEN, HD)
    if stacked:
        cache = (N_LAYERS,) + cache
    return (sds((GEN_BATCH, NQ, HD), jnp.bfloat16),
            sds(cache, jnp.bfloat16), sds(cache, jnp.bfloat16),
            sds((GEN_BATCH, CACHE_LEN), jnp.bool_))


def test_decode_per_layer_compiles(one_chip):
    _compile(flash_decode_attention, *_decode_args(one_chip, False))


def test_decode_stacked_compiles(one_chip):
    layer = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _compile(flash_decode_attention_stacked,
             *_decode_args(one_chip, True), layer)
