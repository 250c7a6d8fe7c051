"""Test configuration: run all tests on a virtual 8-device CPU mesh.

Mirrors the reference's `LocalMultiProcessTest` harness
(`realhf/base/testing.py:112`) -- multi-device parallelism is emulated
without hardware. On TPU this is trivial: JAX exposes N virtual CPU
devices in one process via XLA flags, so sharded code paths (dp/tp/sp)
compile and run in CI.
"""

import collections
import contextlib
import faulthandler
import os
import sys

# The tests share many tiny programs: one persistent compile cache with
# no floor on compile time or entry size. It is the tests' own
# directory (listed in .gitignore and .chiprunignore), not the
# program's `.jax_cache`, so a chip call never copies CPU entries.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(_ROOT, ".jax_cache_tests"))

# The tests' programs are tiny and run once: compile them at LLVM -O0
# (children inherit the flags).
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_backend_optimization_level=0"
    + " --xla_llvm_disable_expensive_passes=true").strip()

from realhf_tpu.base.backend import (  # noqa: E402
    enable_compile_cache,
    force_cpu_backend,
)

force_cpu_backend(n_devices=8)
enable_compile_cache()

# Tests build the same tiny engines over and over, each with jit
# closures of its own, so the same program is compiled again and again.
# Single-device programs go through the persistent cache. Multi-device
# programs do NOT: an XLA:CPU executable that is LOADED from the cache
# runs its collectives in an order that differs between device threads,
# and a program with two independent collectives then deadlocks in the
# rendezvous until XLA aborts the process (seen: the ring-attention
# train step, `collective permute` on 7 threads against `all reduce` on
# one). They are compiled fresh in each process instead, and the last
# few hundred loaded executables are kept under JAX's own cache key
# (only so many: every live XLA:CPU executable holds memory mappings,
# and a session that kept them all ran the process into
# vm.max_map_count and segfaulted in LLVM). Children started by tests
# get no persistent cache at all, for the same reason as above.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"  # children only
import jax  # noqa: E402
from jax._src import cache_key as _cache_key  # noqa: E402
from jax._src import compiler as _compiler  # noqa: E402

jax.config.update("jax_enable_compilation_cache", True)
# The TPU interpreter (``interpreted_kernels`` below) reads and writes a
# kernel's memory in io_callbacks that run jitted ops of their own on
# the ``jax.Array``s they are handed (``device_id + 1``, ``tuple(idx)``)
# while the CPU program that called them is in flight. Under
# asynchronous dispatch the test's thread is issuing the next eager op
# at the same time, and now and then, under load, neither dispatch
# returns (CHANGES.md, PR 40, has both stacks). The CPU client reads
# this flag when it is made, so it is the process's and no block's;
# programs with collectives are launched asynchronously all the same.
jax.config.update("jax_cpu_enable_async_dispatch", False)
_compile_or_get_cached = _compiler.compile_or_get_cached
_compiled_here = collections.OrderedDict()
_KEEP_COMPILED = 256


def _compile_each_program_once(backend, computation, devices,
                               compile_options, host_callbacks,
                               executable_devices, *args, **kwargs):
    if devices.size == 1:
        return _compile_or_get_cached(
            backend, computation, devices, compile_options,
            host_callbacks, executable_devices, *args, **kwargs)

    def compile_():
        return _compiler.backend_compile_and_load(
            backend, computation, executable_devices, compile_options,
            host_callbacks)

    if host_callbacks:  # baked into the module by address: not shared
        return compile_()
    key = _cache_key.get(computation, devices, compile_options, backend)
    if key in _compiled_here:
        _compiled_here.move_to_end(key)
    else:
        _compiled_here[key] = compile_()
        if len(_compiled_here) > _KEEP_COMPILED:
            _compiled_here.popitem(last=False)
    return _compiled_here[key]


_compiler.compile_or_get_cached = _compile_each_program_once

import pytest  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402


@contextlib.contextmanager
def _interpreted_kernels():
    assert not jax.config.read("jax_cpu_enable_async_dispatch"), (
        "the TPU interpreter's callbacks deadlock with a caller that "
        "dispatches asynchronously: see the top of tests/conftest.py")
    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.fixture
def interpreted_kernels():
    """``with interpreted_kernels():`` runs the Pallas TPU kernels
    traced inside it through jax's interpreter on the CPU. The ONE way
    into ``pltpu.force_tpu_interpret_mode()`` (``test_tier1_budget.py``
    holds that): it is safe only in a process that dispatches
    synchronously, which this file sets and this checks."""
    return _interpreted_kernels


@pytest.fixture(scope="module")
def programs_compiled_by_this_tree():
    """JAX keys its persistent compile cache WITHOUT metadata: a
    program loaded from a cache that an older tree wrote carries that
    tree's ``jax.named_scope``s in its ``op_name``s. Modules whose
    tests READ scopes (``pytestmark = pytest.mark.usefixtures(...)``)
    compile their programs themselves."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


#: Seconds ONE test may take, with the set-up and tear-down it pays.
#: Past it the worker prints every thread's stack and exits: xdist
#: reports that test failed by name, starts a new worker on the rest
#: of the file, and the run goes on instead of sitting on a deadlock
#: (a native rendezvous, a callback waiting for its caller) until the
#: driver's clock cuts it with whatever stood behind it uncounted.
#: About three times the dearest tier-1 test (CHANGES.md, PR 40). A
#: test that needs more is marked ``slow``, which takes it out of
#: tier-1 and from under the limit (a whole train step compiled for
#: the described chip takes two to three minutes); there is
#: no other way to raise it.
TEST_LIMIT_S = 300


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_protocol(item):
    if item.get_closest_marker("slow") is not None:
        return (yield)
    from _pytest.faulthandler import fault_handler_stderr_fd_key
    # (pytest's own copy of stderr: fd 2 is captured while a test runs)
    log = item.config.stash.get(fault_handler_stderr_fd_key, sys.__stderr__)
    faulthandler.dump_traceback_later(TEST_LIMIT_S, exit=True, file=log)
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: full-trial e2e runs excluded from the tier-1 sweep "
        "(run directly: pytest -m slow <file>)")


#: Tests ONE LINE of which a later PR made stale and could not repair.
#: The contract a PR that adds a cell works under (the builder's
#: instructions, PR 33; PERF.md section 7 has the words): a new entry
#: goes at the END of BENCHMARK.json's lists, "one put first or in the
#: middle reads as a change to what was there", and a file under the
#: benchmark's ``paths`` (``tests/benchmark`` is one) may be edited
#: only by a PR of the ``benchmark`` kind. Each test still runs and
#: shows as ``x``; ``strict``: the day the line is repaired the entry
#: here fails the run until it is taken out.
#: ``tests/benchmark/test_benchmark_laguna.py::
#: test_lfm2s_manifest_test_holds_as_far_as_its_cell`` runs the WHOLE
#: body of the test below, every assertion of it, on the manifest as
#: far as PR 31's cell, so nothing it held is left unheld. PR 37
#: appended a cell and two per-layer metrics, which made that test's
#: own pin of the last TWO cells stale, and the count of per-layer
#: metrics in PR 35's manifest test; ``tests/benchmark/
#: test_benchmark_deepseek_v3.py`` runs the WHOLE body of each on the
#: manifest as far as the entries it was written for
#: (``test_lagunas_manifest_test_holds_as_far_as_its_cell``,
#: ``test_the_parts_manifest_test_holds_as_far_as_its_entries``).
_STALE_BENCHMARK_TESTS = {
    "tests/benchmark/test_benchmark_lfm2.py::"
    "test_real_manifest_names_the_cell_as_the_issue_does":
        "line 57 asserts that PR 31's cell is the LAST of "
        "BENCHMARK.json's workloads; PR 33 appended the sixth",
    "tests/benchmark/test_benchmark_laguna.py::"
    "test_lfm2s_manifest_test_holds_as_far_as_its_cell":
        "line 98 asserts that PR 31's and PR 33's cells are the LAST "
        "TWO of BENCHMARK.json's workloads; PR 37 appended the seventh",
    "tests/benchmark/test_benchmark_parts.py::"
    "test_manifest_gains_the_fourteen_at_its_end_and_nothing_else":
        "line 187 asserts that BENCHMARK.json has 34 per-layer metrics "
        "and PR 35's fourteen are the LAST; PR 37 appended two",
    # PR 62 appended its cell to flash.visited_share and flash.mxu_share
    # (the stream kernels carry the names those readers look for) and
    # added flash.stream_s and flash.stream_hbm_share;
    # ``tests/benchmark/test_benchmark_smallthinker.py::
    # test_lagunas_manifest_test_holds_as_far_as_its_entries`` runs the
    # WHOLE body on the manifest as it stood before
    "tests/benchmark/test_benchmark_laguna.py::"
    "test_real_manifest_names_the_cell_as_the_issue_does":
        "line 68 asserts that every flash. metric of BENCHMARK.json "
        "lists PR 33's cell ALONE; PR 62 appended its cell to two and "
        "added two flash. metrics of its own",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        reason = _STALE_BENCHMARK_TESTS.get(item.nodeid)
        if reason is not None:
            item.add_marker(pytest.mark.xfail(
                reason=reason, raises=AssertionError, strict=True))


@pytest.fixture(autouse=True)
def _fresh_name_resolve(tmp_path, monkeypatch):
    """Isolate name_resolve and file roots per test."""
    import realhf_tpu.base.constants as constants
    import realhf_tpu.base.name_resolve as name_resolve
    monkeypatch.setattr(constants, "ROOT_DIR", str(tmp_path / "realhf_tpu_root"))
    name_resolve.reconfigure("memory")
    yield


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Drop JAX's caches after every test file. Each live XLA:CPU
    executable holds memory mappings; a whole tier-1 run in one process
    otherwise ends near vm.max_map_count (49,011 of 65,530 seen), and
    past it LLVM segfaults the process."""
    yield
    jax.clear_caches()


@pytest.fixture
def seeded():
    from realhf_tpu.base import seeding
    seeding.set_random_seed(1)
    yield
