"""Parameter reallocation: reshard live weights between meshes.

TPU-native replacement for the reference's signature feature
(``realhf/impl/model/comm/param_realloc.py`` + ``nn/flatten_param.py``
+ ``nn/real_llm_parallel.py``): there, every (layer range, TP shard)
pair is sliced out of a flat buffer and NCCL-broadcast between groups.
Here a model's weights are one sharded pytree, and moving them between
two `jax.sharding.Mesh`es -- different dp/tp degrees, overlapping or
disjoint device sets -- is one call, on one of two paths that
`reallocate` chooses from what the source tree shows:

- **device**: every leaf is a committed `jax.Array` in device memory
  on the target mesh's devices, listed in the same order (a role's
  d2t2 primary and its d4t1 replica, both from `mesh.make_mesh`). Each
  leaf goes through a compiled program, the identity with
  ``out_shardings`` = the leaf's target sharding, so the SPMD
  partitioner emits the all-gathers and slices over the interconnect
  (the interval arithmetic the reference implements by hand in
  ``param_intervals_from_keys``, flatten_param.py:301).
- **host**: anything else (numpy trees, another device set or order,
  offloaded sources) is `jax.device_put` onto the target shardings.

Why not `device_put` throughout (jax 0.9.0): in
``jax/_src/dispatch.py:_device_put_sharding_impl`` the only on-device
branch for fully addressable arrays (``_different_device_order_reshard``,
itself a jitted identity) is taken when the device ORDER differs. With
the same devices in the same order the call falls to
``_DeferredShardArg``, and ``jax/_src/array.py:_array_shard_arg`` sends
every leaf whose shard indices change to
``shard_sharded_device_array_slow_path``: the whole array to numpy on
the host, sliced there and put again, one leaf after another (2.28 GB
from d2t2 to d4t1 on four v5e chips: 4.7 s there, 0.034 s on the
device path; PERF.md, PR 25).

EMA reallocation (``target = eta*src + (1-eta)*target``, reference
``patch_reparallelization``, real_llm_api.py:762) runs as a jitted
lerp on the target mesh after resharding.

Only the vocab dimension needs host arithmetic: replicas with
different tp degrees carry different Megatron-style vocab padding,
so wte/head are unpadded/repadded in transit.
"""

import functools
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from realhf_tpu.base import logging
from realhf_tpu.models import sharding as shard_rules
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.obs import metrics, tracing
from realhf_tpu.parallel import param_stream

logger = logging.getLogger("param_realloc", "benchmark")


def _repad_for_target(cfg: TransformerConfig, params: Any,
                      target_tp: int) -> Any:
    """Adjust vocab padding from the source tp to the target tp."""
    vp_target = shard_rules.padded_vocab_size(cfg, target_tp)
    if params["embed"]["wte"].shape[0] == vp_target:
        return params
    params = shard_rules.unpad_vocab(cfg, params)
    return shard_rules.pad_vocab(cfg, params, target_tp)


@jax.jit
def _ema_lerp(src, dst, eta):
    return jax.tree.map(
        lambda x, y: (eta * x.astype(jnp.float32)
                      + (1.0 - eta) * y.astype(jnp.float32)).astype(y.dtype),
        src, dst)


def _devices_in_order(sharding) -> Optional[tuple]:
    """The devices of a mesh sharding as a jitted program lists them;
    None for a sharding without a mesh."""
    mesh = getattr(sharding, "mesh", None)
    return None if mesh is None else tuple(mesh.devices.flat)


def _on_target_devices(params: Any, shardings: Any) -> bool:
    """Whether every leaf is a committed `jax.Array` in the memory and
    on the devices of its target sharding, in the same order: what a
    `jax.jit` asks of its arguments, and where `jax.device_put` goes
    through the host (module docstring)."""
    def placed(x, target):
        if not (isinstance(x, jax.Array) and x.committed):
            return False
        devices = _devices_in_order(x.sharding)
        return (devices is not None
                and devices == _devices_in_order(target)
                and x.sharding.memory_kind == target.memory_kind)

    leaves, targets = jax.tree.leaves(params), jax.tree.leaves(shardings)
    return len(leaves) == len(targets) and all(map(placed, leaves, targets))


def _identity(x):
    return x


@functools.lru_cache(maxsize=256)
def _reshard_program(sharding):
    """The jitted identity onto one leaf's target sharding, held as
    long as the layout is in use: a `jax.jit` made anew on every call
    would lower anew on every step. The leaf's shape and source
    sharding are part of jit's own key. Nothing is donated, and
    explicit ``out_shardings`` switch off jit's forwarding of inputs,
    so the output is a fresh buffer (`reallocate`).

    One program a leaf, not one for the tree: the partitioner gathers
    into a temporary and copies that into the output, so a program's
    temporaries are as large as its outputs (compiled for a v5e 2x2:
    2.28 GB beside 2.28 GB of outputs for four layers of Mistral-7B),
    and leaf by leaf they are one leaf's, at no cost in time (0.032
    against 0.034 s on four v5e chips; PERF.md, PR 25). Each program
    also holds ONE collective: XLA:CPU executables loaded from the
    persistent cache deadlock on two independent ones
    (tests/conftest.py)."""
    return jax.jit(_identity, out_shardings=sharding)


def tree_bytes(params: Any) -> int:
    """Logical bytes of a tree: every leaf's global shape times its
    item size. No device query; not the bytes that land on each chip
    (a replicated leaf lands once a chip)."""
    return sum(param_stream.leaf_nbytes(x) for x in jax.tree.leaves(params))


def reallocate(
    cfg: TransformerConfig,
    src_params: Any,
    dst_engine,
    eta: float = 1.0,
    role: str = "",
) -> float:
    """Move (or EMA-merge) src weights onto dst_engine's mesh.

    Returns the wall-clock seconds of the resharding transfer (the
    north-star reshard-latency metric). The caller's span (``realloc``)
    gets the bytes moved; ``realloc_bytes_total{role}`` counts them,
    ``realloc_puts_total{role,path}`` the path taken (module
    docstring).
    """
    t0 = time.monotonic()
    with tracing.span("realloc:repad"):
        params = _repad_for_target(cfg, src_params,
                                   dst_engine.ctx.tp_size)
    nbytes = tree_bytes(params)
    shardings = dst_engine._param_shardings
    on_device = _on_target_devices(params, shardings)
    path = "device" if on_device else "host"
    metrics.inc("realloc_bytes_total", nbytes, role=role)
    metrics.inc("realloc_puts_total", role=role, path=path)
    tracing.current_span().set_attribute("bytes", nbytes)
    with tracing.span("realloc:put", bytes=nbytes, path=path) as sp:
        # Fresh buffers on both paths (may_alias=False; an undonated
        # input of a program is never an output): a leaf whose layout
        # is the same on both meshes (the replicated norm scales)
        # would otherwise BE the source's buffer, and the next train
        # step donates that buffer away from under the replica.
        if on_device:
            moved = jax.tree.map(lambda x, s: _reshard_program(s)(x),
                                 params, shardings)
        else:
            moved = jax.device_put(params, shardings, may_alias=False)
        sp.result(moved)
    if eta != 1.0:
        with tracing.span("realloc:ema", eta=eta) as sp:
            moved = sp.result(_ema_lerp(
                moved, dst_engine.params, jnp.asarray(eta, jnp.float32)))
    jax.block_until_ready(moved)
    dt = time.monotonic() - t0
    dst_engine.set_params(moved, already_sharded=True)
    return dt


def install_param_chunks(cfg: TransformerConfig, dst_engine, n_chunks: int,
                         fetch_chunk, eta: float = 1.0):
    """Streamed receiver install: ``fetch_chunk(i) -> {path: ndarray}``
    chunks land on the target mesh one at a time (vocab repad + dtype
    cast + optional EMA per leaf), so peak host overhead is one chunk,
    not one model (VERDICT r3 missing #2; reference streams per
    (layer-range, shard) step, comm/param_realloc.py:312).

    Returns (seconds, bytes_received)."""
    t0 = time.monotonic()
    tp = dst_engine.ctx.tp_size
    pdt = jnp.dtype(cfg.param_dtype)
    shardings = dict(param_stream.flatten_params(
        dst_engine._param_shardings))
    old = dict(param_stream.flatten_params(dst_engine.params))
    eta_dev = jnp.asarray(eta, jnp.float32)
    moved = {}
    total = 0
    for i in range(n_chunks):
        chunk = fetch_chunk(i)
        # sorted: every host must issue the per-leaf device_puts in
        # the same order -- a chunk dict deserialized from the wire
        # carries the SENDER's insertion order (det-unsorted-iter)
        for path, arr in sorted(chunk.items()):
            path = tuple(path)
            total += param_stream.leaf_nbytes(arr)
            arr = shard_rules.repad_vocab_leaf(cfg, path, arr, tp)
            if arr.dtype != pdt:
                arr = arr.astype(pdt)
            leaf = jax.device_put(arr, shardings[path])
            if eta != 1.0:
                # a bare array is a valid pytree: reuse the jitted lerp
                leaf = _ema_lerp(leaf, old[path], eta_dev)
            moved[path] = leaf
    missing = set(shardings) - set(moved)
    assert not missing, f"param stream missed leaves: {sorted(missing)}"
    params = param_stream.unflatten_params(moved)
    jax.block_until_ready(params)
    dst_engine.set_params(params, already_sharded=True)
    return time.monotonic() - t0, total


def offload_to_host(params: Any) -> Any:
    """Move a pytree to pinned host memory (reference async_offload,
    real_llm_api.py:274), keeping every leaf's sharding: each shard
    lands in the host memory of its own device, so this needs no CPU
    backend beside the TPU's (``JAX_PLATFORMS=tpu``). A ``device_put``
    onto the device shardings brings it back."""
    return jax.device_put(params, jax.tree.map(
        lambda x: x.sharding.with_memory_kind("pinned_host"), params))


class ReplicaManager:
    """Keeps secondary engines (replicas with different meshes) of a
    role in sync with the trainable primary.

    Mirrors reference ``resolve_replica_ids`` + ``resolve_rpc_hooks``
    (experiments/common/utils.py:126,143): the trainable replica is
    the source of truth; stale replicas are refreshed by reallocation
    before executing their MFC.
    """

    def __init__(self):
        # role -> replica engine id -> version of last sync
        self._synced: Dict[str, Dict[int, int]] = {}
        self.last_reshard_secs: Optional[float] = None

    def ensure_fresh(self, role: str, primary_model, replica_model,
                     eta: float = 1.0):
        if replica_model is primary_model:
            return
        pv = primary_model.version.global_step
        synced = self._synced.setdefault(role, {})
        rid = id(replica_model)
        if synced.get(rid) == pv:
            return
        with tracing.span(
                "realloc", role=role,
                src=str(primary_model.engine.ctx.parallel),
                dst=str(replica_model.engine.ctx.parallel)) as sp:
            dt = reallocate(primary_model.config,
                            primary_model.engine.params,
                            replica_model.engine, eta=eta, role=role)
            sp.result(replica_model.engine.params)
        self.last_reshard_secs = dt
        synced[rid] = pv
        logger.info(
            "Reallocated %s %s -> %s in %.3fs", role,
            primary_model.engine.ctx.parallel,
            replica_model.engine.ctx.parallel, dt)
