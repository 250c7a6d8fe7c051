"""Parameter reallocation round-trip tests -- the TPU analog of the
reference's crown-jewel suite ``tests/comm/test_param_realloc.py``
(:515-528): world of 8 virtual devices, parameterized over source and
target (dp, tp) layouts on overlapping and disjoint device subsets,
checking bit-equality after round-trips, inference consistency across
layouts, that training updates propagate through reallocation, and
EMA merging.
"""

import contextlib
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from realhf_tpu.api.config import ModelName
from realhf_tpu.engine.engine import Engine
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.obs import tracing
from realhf_tpu.parallel.mesh import MeshContext, ParallelismConfig, make_mesh
from realhf_tpu.parallel.realloc import offload_to_host, reallocate

VOCAB = 107  # deliberately prime: vocab padding differs per tp


def tiny_cfg(is_critic=False):
    return TransformerConfig(
        n_layers=2, n_kv_heads=4, n_q_heads=4, hidden_dim=32,
        intermediate_dim=64, vocab_size=VOCAB, apply_rotary=True,
        layer_norm_type="rms", mlp_type="llama", use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu", compute_dtype="float32",
        is_critic=is_critic)


def build_engine(cfg, dp, tp, devices=None, lr=None, name="m", seed=0):
    parallel = ParallelismConfig(data_parallel_size=dp,
                                 tensor_parallel_size=tp)
    if devices is None:
        devices = jax.devices("cpu")[:parallel.world_size]
    ctx = MeshContext(ModelName(name, 0), make_mesh(parallel, devices),
                      parallel)
    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    opt = None if lr is None else OptimizerConfig(
        lr=lr, warmup_steps_proportion=0.0, lr_scheduler_type="constant")
    return Engine(cfg, ctx, params, optimizer=opt, total_train_steps=100)


LAYOUTS = [(4, 1), (2, 2), (1, 4), (8, 1), (2, 4), (1, 8)]


def _train_once(engine, cfg, ids=None, seg=None):
    """One optimizer step (it donates the engine's parameters)."""
    from realhf_tpu.ops import functional as F
    if ids is None:
        ids = np.random.default_rng(1).integers(
            0, VOCAB, size=(2, 16)).astype(np.int32)
        seg = np.ones_like(ids)

    def loss_fn(p, h, mb):
        lp = F.shifted_logprobs_from_hidden(cfg, p, h, mb["input_ids"],
                                            mb["seg_ids"])
        return -lp.mean(), {}

    engine.train_batch([dict(input_ids=ids, seg_ids=seg)], loss_fn,
                       loss_fn_key="t")


def _canonical(engine):
    """Host pytree with padding stripped, for comparison."""
    return engine.params_numpy()


@pytest.mark.parametrize("src", LAYOUTS[:4])
@pytest.mark.parametrize("dst", LAYOUTS[:4])
def test_roundtrip_equality(src, dst):
    cfg = tiny_cfg()
    devs = jax.devices("cpu")
    e_src = build_engine(cfg, *src, devices=devs[:src[0] * src[1]], seed=3)
    e_dst = build_engine(cfg, *dst, devices=devs[-dst[0] * dst[1]:], seed=7)

    before = _canonical(e_src)
    reallocate(cfg, e_src.params, e_dst)
    mid = _canonical(e_dst)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(mid)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # round-trip back
    reallocate(cfg, e_dst.params, e_src)
    after = _canonical(e_src)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_inference_consistent_across_layouts():
    cfg = tiny_cfg()
    devs = jax.devices("cpu")
    e1 = build_engine(cfg, 4, 2, devices=devs, seed=1)
    e2 = build_engine(cfg, 2, 2, devices=devs[:4], seed=2)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, VOCAB, size=(4, 16)).astype(np.int32)
    seg = np.ones_like(ids)
    lp1 = np.asarray(e1.forward_logprobs(ids, seg))
    reallocate(cfg, e1.params, e2)
    lp2 = np.asarray(e2.forward_logprobs(ids, seg))
    np.testing.assert_allclose(lp1, lp2, rtol=1e-5, atol=1e-6)


def test_training_updates_propagate():
    """Train on layout A, realloc to B: B must produce the updated
    outputs (reference test_param_realloc:381-512)."""
    cfg = tiny_cfg()
    devs = jax.devices("cpu")
    train_e = build_engine(cfg, 2, 2, devices=devs[:4], lr=1e-2, seed=5)
    gen_e = build_engine(cfg, 1, 4, devices=devs[4:], seed=9)

    rng = np.random.default_rng(1)
    ids = rng.integers(0, VOCAB, size=(2, 16)).astype(np.int32)
    seg = np.ones_like(ids)

    reallocate(cfg, train_e.params, gen_e)
    lp_before = np.asarray(gen_e.forward_logprobs(ids, seg))
    for _ in range(3):
        _train_once(train_e, cfg, ids, seg)
    reallocate(cfg, train_e.params, gen_e)
    lp_after = np.asarray(gen_e.forward_logprobs(ids, seg))
    assert np.abs(lp_after - lp_before).max() > 1e-3  # updates visible
    # and match the trainable layout's own outputs exactly
    lp_train = np.asarray(train_e.forward_logprobs(ids, seg))
    np.testing.assert_allclose(lp_after, lp_train, rtol=1e-5, atol=1e-6)


def test_ema_reallocation():
    cfg = tiny_cfg()
    devs = jax.devices("cpu")
    src = build_engine(cfg, 2, 2, devices=devs[:4], seed=11)
    dst = build_engine(cfg, 2, 2, devices=devs[4:], seed=12)
    a = _canonical(src)
    b = _canonical(dst)
    reallocate(cfg, src.params, dst, eta=0.3)
    merged = _canonical(dst)
    for x, y, z in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                       jax.tree.leaves(merged)):
        np.testing.assert_allclose(
            np.asarray(z), 0.3 * np.asarray(x) + 0.7 * np.asarray(y),
            rtol=1e-5, atol=1e-6)


def test_offload_roundtrip():
    cfg = tiny_cfg()
    e = build_engine(cfg, 2, 2, seed=13)
    before = _canonical(e)
    host = offload_to_host(e.params)
    assert all(leaf.sharding.memory_kind == "pinned_host"
               for leaf in jax.tree.leaves(host))
    e.set_params(jax.device_put(host, e._param_shardings),
                 already_sharded=True)
    after = _canonical(e)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_critic_roundtrip():
    cfg = tiny_cfg(is_critic=True)
    devs = jax.devices("cpu")
    e1 = build_engine(cfg, 4, 1, devices=devs[:4], seed=20)
    e2 = build_engine(cfg, 1, 2, devices=devs[4:6], seed=21)
    before = _canonical(e1)
    reallocate(cfg, e1.params, e2)
    reallocate(cfg, e2.params, e1)
    after = _canonical(e1)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_parse_parallelism_permutations():
    from realhf_tpu.parallel.mesh import parse_parallelism
    a = parse_parallelism("d4t2")
    assert (a.data_parallel_size, a.tensor_parallel_size,
            a.pipeline_parallel_size) == (4, 2, 1)
    b = parse_parallelism("d4p1m2")  # reference's documented order
    assert (b.data_parallel_size, b.tensor_parallel_size,
            b.pipeline_parallel_size) == (4, 2, 1)
    c = parse_parallelism("m2d4")
    assert c.tensor_parallel_size == 2 and c.data_parallel_size == 4
    d = parse_parallelism("d1t8s")
    assert d.sequence_parallel
    import pytest as _pytest
    for bad in ("x9z", "", "d", "d4q2"):
        with _pytest.raises(ValueError):
            parse_parallelism(bad)


def test_sub_fleet_replica_layouts():
    """Runner-style build of engines whose world size is smaller than
    the fleet must work (review regression)."""
    cfg = tiny_cfg()
    e_small = build_engine(cfg, 2, 2)  # 4 of 8 devices
    e_full = build_engine(cfg, 2, 4)
    reallocate(cfg, e_full.params, e_small)
    a = _canonical(e_full)
    b = _canonical(e_small)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_roundtrip_through_pipeline_layout():
    """Realloc between a tp-only layout and a pipeline-parallel layout
    (blocks layer-sharded over "pipe"): the training layout of a large
    model vs the dp/tp generation layout of the same role."""
    cfg = tiny_cfg()
    devs = jax.devices("cpu")
    e_src = build_engine(cfg, 2, 2, devices=devs[:4], seed=3)

    pparallel = ParallelismConfig(data_parallel_size=2,
                                  tensor_parallel_size=2,
                                  pipeline_parallel_size=2)
    pctx = MeshContext(ModelName("pp", 0),
                       make_mesh(pparallel, devs[:8]), pparallel)
    e_dst = Engine(cfg, pctx, T.init_params(cfg, jax.random.PRNGKey(7)))

    before = _canonical(e_src)
    reallocate(cfg, e_src.params, e_dst)
    for a, b in zip(jax.tree.leaves(before),
                    jax.tree.leaves(_canonical(e_dst))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    reallocate(cfg, e_dst.params, e_src)
    for a, b in zip(jax.tree.leaves(before),
                    jax.tree.leaves(_canonical(e_src))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------
# The two paths of `reallocate`: compiled programs where the source
# lies on the target's devices in the target's order, `device_put`
# everywhere else. Chosen from the input, reported on the span and in
# `realloc_puts_total`.
# ----------------------------------------------------------------------
def _put_paths(capture):
    return [s["attributes"]["path"] for s in capture.named("realloc:put")]


def _assert_same_weights(a, b):
    for x, y in zip(jax.tree.leaves(_canonical(a)),
                    jax.tree.leaves(_canonical(b))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


SAME_DEVICES = [((2, 2), (4, 1)), ((4, 1), (2, 2)), ((2, 2), (1, 4)),
                ((1, 4), (4, 1)), ((2, 2), (2, 2)), ((8, 1), (2, 4)),
                ((2, 4), (1, 8)), ((1, 8), (8, 1))]


@pytest.mark.parametrize("src,dst", SAME_DEVICES)
def test_same_devices_take_the_device_path(src, dst):
    cfg = tiny_cfg()
    devs = jax.devices("cpu")
    e_src = build_engine(cfg, *src, devices=devs[:src[0] * src[1]], seed=3)
    e_dst = build_engine(cfg, *dst, devices=devs[:dst[0] * dst[1]], seed=7)
    tracing.start()
    reallocate(cfg, e_src.params, e_dst, role="actor")
    reallocate(cfg, e_dst.params, e_src, role="actor")
    capture = tracing.stop()
    assert _put_paths(capture) == ["device", "device"]
    assert capture.counter("realloc_puts_total", role="actor",
                           path="device") == 2
    assert capture.counter("realloc_puts_total", role="actor",
                           path="host") == 0
    _assert_same_weights(e_src, e_dst)
    for leaf, sharding in zip(jax.tree.leaves(e_dst.params),
                              jax.tree.leaves(e_dst._param_shardings)):
        assert leaf.sharding == sharding


#: (source layout, its devices, target layout, its devices) of the
#: eight virtual ones
OTHER_DEVICES = {
    "4_to_8": ((2, 2), slice(0, 4), (2, 4), slice(0, 8)),
    "8_to_4": ((2, 4), slice(0, 8), (2, 2), slice(0, 4)),
    "disjoint": ((2, 2), slice(0, 4), (4, 1), slice(4, 8)),
    # here `device_put` itself reshards on the device
    "other_order": ((2, 2), slice(0, 4), (4, 1), slice(3, None, -1)),
}


@pytest.mark.parametrize("case", OTHER_DEVICES)
def test_other_devices_take_the_host_path(case):
    cfg = tiny_cfg()
    devs = jax.devices("cpu")
    src, src_devs, dst, dst_devs = OTHER_DEVICES[case]
    e_src = build_engine(cfg, *src, devices=devs[src_devs], seed=3)
    e_dst = build_engine(cfg, *dst, devices=devs[dst_devs], seed=7)
    tracing.start()
    reallocate(cfg, e_src.params, e_dst, role="actor")
    capture = tracing.stop()
    assert _put_paths(capture) == ["host"]
    assert capture.counter("realloc_puts_total", role="actor",
                           path="host") == 1
    assert capture.counter("realloc_puts_total", role="actor",
                           path="device") == 0
    _assert_same_weights(e_src, e_dst)


@pytest.mark.parametrize("source,dst", [("numpy", (4, 1)),
                                        ("offloaded", (2, 2))])
def test_host_trees_take_the_host_path(source, dst):
    """`ModelHost.install_node_params` hands over a numpy tree; an
    offloaded primary lies in pinned host memory (`device_put` brings
    it back onto its own layout and no other)."""
    cfg = tiny_cfg()
    devs = jax.devices("cpu")
    e_src = build_engine(cfg, 2, 2, devices=devs[:4], seed=3)
    e_dst = build_engine(cfg, *dst, devices=devs[:4], seed=7)
    tree = (jax.tree.map(np.asarray, e_src.params) if source == "numpy"
            else offload_to_host(e_src.params))
    tracing.start()
    reallocate(cfg, tree, e_dst)
    capture = tracing.stop()
    assert _put_paths(capture) == ["host"]
    assert capture.counter("realloc_puts_total", role="",
                           path="host") == 1
    _assert_same_weights(e_src, e_dst)


@contextlib.contextmanager
def _programs_lowered():
    """Names of the programs JAX lowers meanwhile, counted the way
    `benchmark/observe.py:CompileWatch` counts them."""
    names = []

    def on_log(rec):
        if rec.getMessage().startswith("Compiling "):
            names.append(rec.getMessage().split()[1])
        return False

    loggers = [logging.getLogger("jax._src.interpreters.pxla"),
               logging.getLogger("jax._src.dispatch")]
    before = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    for logger in loggers:
        logger.addFilter(on_log)
    try:
        yield names
    finally:
        for logger in loggers:
            logger.removeFilter(on_log)
        jax.config.update("jax_log_compiles", before)


def test_a_second_reshard_lowers_no_program():
    """The programs of a pair of layouts are compiled in the first
    step; every later step, on newly trained weights too, runs them."""
    cfg = tiny_cfg()
    devs = jax.devices("cpu")
    primary = build_engine(cfg, 2, 2, devices=devs[:4], lr=1e-2, seed=5)
    replica = build_engine(cfg, 4, 1, devices=devs[:4], seed=9)
    _train_once(primary, cfg)  # the train program is not counted below
    jax.clear_caches()  # whatever earlier tests compiled
    with _programs_lowered() as first:
        reallocate(cfg, primary.params, replica)
    # (beside the vocabulary's repadding from tp 2 to tp 1)
    assert "jit(_identity)" in first, first
    _train_once(primary, cfg)
    with _programs_lowered() as later:
        reallocate(cfg, primary.params, replica)
        reallocate(cfg, primary.params, replica)
    assert later == []
    _assert_same_weights(primary, replica)


@pytest.mark.parametrize("dst", [(4, 1), (2, 2)])
def test_replica_survives_the_primary_donating_its_weights(dst):
    """No leaf of the replica is a buffer of the primary: the norm
    scales are replicated on both meshes (with the same layout on both
    sides every leaf is), and the train step donates the primary's."""
    cfg = tiny_cfg()
    devs = jax.devices("cpu")
    primary = build_engine(cfg, 2, 2, devices=devs[:4], lr=1e-2, seed=5)
    replica = build_engine(cfg, *dst, devices=devs[:4], seed=9)
    tracing.start()
    reallocate(cfg, primary.params, replica)
    assert _put_paths(tracing.stop()) == ["device"]
    # read from the primary's own buffers, a host view of one would
    # keep the train step from donating it
    before = _canonical(replica)
    donated = jax.tree.leaves(primary.params)
    _train_once(primary, cfg)
    assert all(x.is_deleted() for x in donated)
    assert not any(y.is_deleted() for y in jax.tree.leaves(replica.params))
    for x, y, z in zip(jax.tree.leaves(before),
                       jax.tree.leaves(_canonical(replica)),
                       jax.tree.leaves(_canonical(primary))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert np.any(np.asarray(y) != np.asarray(z))  # z is trained


def test_ema_over_the_device_path_equals_the_host_path():
    cfg = tiny_cfg()
    devs = jax.devices("cpu")
    src = build_engine(cfg, 2, 2, devices=devs[:4], seed=11)
    on_device = build_engine(cfg, 4, 1, devices=devs[:4], seed=12)
    on_host = build_engine(cfg, 4, 1, devices=devs[4:], seed=12)
    tracing.start()
    reallocate(cfg, src.params, on_device, eta=0.3)
    reallocate(cfg, src.params, on_host, eta=0.3)
    capture = tracing.stop()
    assert _put_paths(capture) == ["device", "host"]
    assert len(capture.named("realloc:ema")) == 2
    _assert_same_weights(on_device, on_host)
    assert any(np.any(np.asarray(x) != np.asarray(z)) for x, z in zip(
        jax.tree.leaves(_canonical(src)),
        jax.tree.leaves(_canonical(on_device))))
