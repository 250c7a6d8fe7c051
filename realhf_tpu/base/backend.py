"""Backend selection helpers (the jax floor is 0.9.0).

JAX picks its platform from ``JAX_PLATFORMS`` (the TPU when unset on a
machine that has one). A chip belongs to one process at a time: a
process that initialises the TPU backend holds the chip until it
exits, so launchers that spawn workers stay off JAX.
"""

import os
from typing import Optional

#: Compile cache of a checkout when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: one fixed path found from this file, never the working
#: directory (the path is part of what a cache hit depends on).
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def force_cpu_backend(n_devices: Optional[int] = None) -> None:
    """Pin this process's JAX to the CPU backend, optionally with
    ``n_devices`` virtual devices. Call before the first jax
    computation (the XLA flag is read when the CPU backend starts)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. Called wherever the program starts
    (quickstart, the runners, ``apps/remote.py`` workers).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it
    and no other directory is set here; otherwise the cache lives in
    ``.jax_cache`` at the checkout's root. No floor on compile time or
    entry size: an RLHF step is many small programs, and a run's
    second start should compile none of them."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def pallas_enabled() -> bool:
    """Whether the Pallas kernel paths (flash attention, flash decode,
    the grouped products, the delta scan, their shard_map wrappers)
    engage: a TPU backend, or a trace made under
    ``pltpu.force_tpu_interpret_mode()``, where the SAME wiring runs
    the kernels through jax's interpreter on the CPU (the tests'
    fixture ``interpreted_kernels`` is the one way in).

    Read at TRACE time: a program traced outside the interpreter keeps
    its XLA paths when it is called inside it (the context is no part
    of a jit's cache key)."""
    import jax
    from jax._src import config as _jcfg

    return (jax.default_backend() == "tpu"
            or _jcfg.pallas_tpu_interpret_mode_context_manager.value
            is not None)
