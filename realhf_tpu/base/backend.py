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
    (quickstart, the runners, ``apps/remote.py`` workers, bench).

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
    their shard_map wrappers) should engage: a real TPU backend, or
    ``REALHF_TPU_FORCE_PALLAS=1`` -- the test hook that runs the SAME
    wiring with interpret-mode kernels on CPU (callers then execute
    under ``pltpu.force_tpu_interpret_mode()``), so the kernel
    plumbing is exercised in CI instead of only on hardware.

    The flag is read at TRACE time: set it before building engines /
    tracing jits, and do not expect a mid-process flip to invalidate
    already-compiled programs (the env var is not part of any jit
    cache key). Forcing the flag on a non-TPU backend OUTSIDE the
    interpret-mode context raises here -- the bare kernels would
    otherwise die deep in Mosaic lowering with an opaque error."""
    import jax

    # Escape hatch / A-B rig: force the GSPMD/XLA fallback paths even
    # on a real TPU (profile_decode --no-pallas sets this to compare
    # the handwritten kernels against XLA on silicon).
    if os.environ.get("REALHF_TPU_DISABLE_PALLAS") == "1":
        return False
    if jax.default_backend() == "tpu":
        return True
    if os.environ.get("REALHF_TPU_FORCE_PALLAS") != "1":
        return False
    from jax._src import config as _jcfg
    if _jcfg.pallas_tpu_interpret_mode_context_manager.value is None:
        raise RuntimeError(
            "REALHF_TPU_FORCE_PALLAS=1 on a non-TPU backend requires "
            "running under pltpu.force_tpu_interpret_mode() (the bare "
            "Pallas kernels cannot lower for CPU); wrap the "
            "computation in that context or unset the flag.")
    return True
