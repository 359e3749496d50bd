"""Test configuration.

Runs JAX on a virtual 8-device CPU platform (set BEFORE jax is imported) so
multi-chip sharding (TP/DP/EP meshes) is exercised without TPU hardware —
the TPU translation of the reference's loopback-libp2p strategy (SURVEY §4).
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import gc  # noqa: E402

import jax  # noqa: E402

from crowdllama_tpu.utils.jaxcache import enable_compile_cache  # noqa: E402

# Persistent XLA compilation cache: the suite compiles the same tiny-model
# programs over and over across runner instances and test files, and that
# compile time dominates tier-1 wall clock (without the cache the run does
# not fit its 870 s limit).  Entries are keyed by content hash of the
# lowered program + compile options, so a hit returns the identical
# executable — byte-identity tests see the same numerics either way.
# (Compile-telemetry tests count jit-entry claims, not XLA work, so they
# are unaffected by hits.)  Every program is cached, however quick its
# compile: each new runner instance re-jits the same ~50 ms programs, and
# a cache read (~10 ms) beats recompiling them — measured on the whole
# suite from an empty cache: 787 s at a 0.2 s threshold, 739 s at 0.
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
# Compressed intervals everywhere, mirroring CROWDLLAMA_TEST_MODE=1
# (/root/reference/pkg/peer/peer.go:159-175).
os.environ.setdefault("CROWDLLAMA_TPU_TEST_MODE", "1")

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    # Markers used across the suite:
    #   slow  — excluded from the tier-1 gate (pytest -m 'not slow');
    #           long-soak/benchmark tests.
    #   chaos — deterministic fault-injection tests (testing/faults.py):
    #           seeded FaultPlans kill streams/handshakes mid-request and
    #           assert the request plane heals (docs/ROBUSTNESS.md).  They
    #           run in tier 1 AND standalone via `make chaos`.
    config.addinivalue_line(
        "markers", "slow: long-running soak/benchmark tests "
                   "(excluded from the tier-1 gate)")
    config.addinivalue_line(
        "markers", "chaos: deterministic fault-injection tests driven by "
                   "crowdllama_tpu.testing.faults (see docs/ROBUSTNESS.md)")
    config.addinivalue_line(
        "markers", "train: draft-distillation training tests "
                   "(train/distill.py; run in tier 1 AND standalone via "
                   "`make distill-smoke`)")


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_executables():
    """Drop every compiled executable when a test file's last test ends.

    Each compiled or cache-loaded XLA:CPU executable keeps its memory
    mappings for as long as a jit cache or a runner holds it; the files
    that build engines add ~19,000 maps apiece, and the process dies
    inside whatever XLA does next once it nears vm.max_map_count (65,530)
    — around the 450th test, in a test that passes alone.  Measured on
    tests/test_engine.py: 17,994 maps before this call, 1,907 after."""
    yield
    jax.clear_caches()
    gc.collect()


@pytest.fixture
def _programs_go_with_their_test():
    """For the files whose tests each build an unrolled runner of their own
    (applied by name: ``pytestmark = pytest.mark.usefixtures(...)``): drop
    every compiled executable after EVERY test, not only the file's last.

    A runner and its jitted methods are a reference cycle, and every loaded
    CPU executable of these unrolled models holds memory maps by the
    hundred: left to the collector's own schedule the file's programs pile
    up in its one process (the driver runs a file in one worker) until a
    load from the compile cache dies of a segmentation fault, or XLA's
    compiler does with the process at vm.max_map_count (tests/test_afmoe.py
    at its 38th test; tests/test_kimi_linear.py: ISSUE 49's warning)."""
    yield
    jax.clear_caches()
    gc.collect()


# Minimal asyncio runner so tests don't depend on pytest-asyncio being
# installed: any `async def test_*` is run to completion on a fresh loop.
@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.function
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None
