"""Repo-root conftest: force tests onto a virtual 8-device CPU mesh.

XLA_FLAGS and JAX_PLATFORMS must be set before any backend initialises
(SURVEY.md §4: the CPU-mesh simulation stands in for the reference's envtest
"real API, fake kubelet" trick — real XLA SPMD partitioning, no TPU
hardware).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402

# Single-process suite robustness (round 5, VERDICT r4 #4): a full
# `pytest tests/` run compiles many hundreds of XLA programs in ONE
# process and segfaulted inside XLA's native compile (~85% in, during a
# model reload's warm_buckets) on this host in rounds 4 and 5 — per-file
# runs are all green, so the trigger is accumulated in-process compiler
# state, not any one test. Bound it:
# - persistent on-disk compilation cache, so the per-module cache clear
#   below costs disk reads, not recompiles (same mechanism the server
#   and bench use);
# - drop live executables between modules (jax.clear_caches) so the
#   in-process accumulation resets ~45 times instead of growing
#   monotonically.
# The persistent cache is OPT-IN (TPU_TEST_XLA_CACHE=1): on this host the
# CPU-backend executable deserialization path is itself unstable — with the
# cache enabled, a fresh cache dir reproducibly yields wrong decode tokens
# and then a native segfault within a couple of engine runs, while the
# identical workload with the cache disabled is deterministic across
# dozens of runs. Recompiling after each per-module clear costs seconds
# for test-sized CPU programs; silently-corrupt cached executables cost
# correctness.
if os.environ.get("TPU_TEST_XLA_CACHE", "") == "1":
    from ollama_operator_tpu.runtime import compile_cache  # noqa: E402

    # 0.0, NOT the 1.0 the server/bench use: test-sized CPU programs
    # compile in well under a second and would otherwise never be
    # persisted — the per-module clear would then force full recompiles
    # instead of disk reads
    compile_cache.enable(min_compile_secs=0.0)

import gc  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1 CI")
    config.addinivalue_line(
        "markers", "chaos: fault-injection recovery tests (runtime/faults"
        ".py); the CI chaos-smoke job runs exactly this set")


@pytest.fixture(autouse=True, scope="module")
def _bound_xla_state():
    yield
    jax.clear_caches()
    gc.collect()


@pytest.fixture(autouse=True)
def _page_accounting():
    """Refcount leaks fail loudly: after EVERY test, each PageTable still
    alive must satisfy its accounting invariant — every non-trash page
    free exactly once XOR quarantined exactly once XOR refcounted as
    mapped+pinned (ISSUE 4). With the scheduler idle (every test ends
    that way) the epoch-fence quarantine must also be EMPTY: a page
    parked there forever is a pool leak the refcount check alone cannot
    see (ISSUE 5) — the idle scheduler loop and shutdown() both drain it,
    so residue here means a fence ack went missing."""
    yield
    from ollama_operator_tpu.runtime.paged import live_tables
    for pt in live_tables():
        pt.check()
        assert pt.quarantined == 0, (
            f"{pt.quarantined} page(s) leaked in epoch quarantine "
            f"after test teardown")


@pytest.fixture(autouse=True)
def _disarm_faults():
    """No injected fault may leak across tests: the registry is process-
    global by design (the code under test reaches it via one module
    attribute), so every test starts and ends clean."""
    from ollama_operator_tpu.runtime.faults import FAULTS
    FAULTS.reset()
    yield
    FAULTS.reset()
