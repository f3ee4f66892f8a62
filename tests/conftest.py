"""Test configuration: force CPU JAX with 8 virtual devices.

This is the CI analog of the reference's portable fallback path
(roaring/assembly_generic.go) — everything must pass without a TPU.  The
8 virtual CPU devices let the sharded/mesh tests (parallel/) exercise real
GSPMD partitioning and collectives.

Must run before jax is imported anywhere.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running sweep excluded from tier-1 (-m 'not slow')",
    )


@pytest.fixture
def rng():
    return np.random.default_rng(42)


# -- native library build (native/Makefile) ---------------------------------
#
# Tier-1 builds native/libpilosa_native.so BEFORE the suite runs so every
# test exercises the same lanes CI ships (native.load() auto-builds via
# the Makefile on first use).  Without a compiler the Python fallbacks
# serve and the native-only tests (test_writelane) skip with a reason.

@pytest.fixture(scope="session", autouse=True)
def _native_library_build():
    import shutil

    from pilosa_tpu import native

    if native.load() is None and not os.environ.get("PILOSA_TPU_NO_NATIVE"):
        missing = [t for t in ("make", "g++") if shutil.which(t) is None]
        reason = (
            f"toolchain missing: {', '.join(missing)}" if missing
            else "make -C native failed"
        )
        sys.stderr.write(
            f"\n[conftest] native library unavailable ({reason}); "
            "Python fallbacks serve, native-only tests skip\n"
        )
    yield


# -- runtime lock checker (pilosa_tpu/analysis/lockcheck.py) ----------------
#
# The tier-1 concurrency/replica/qos/writelane/ingest/qcache suites run
# with the lock checker ON: every named lock created during these tests
# feeds the cross-thread acquisition-order graph, blocking calls under a
# lock are caught, declared guarded fields (`_guarded_by_`) refine
# per-field candidate locksets (the Eraser-style race detector), and a
# test that recorded any violation FAILS with the checker's report.
# Subprocess group workers inherit PILOSA_TPU_LOCK_CHECK=1 via the env
# and self-enable at import (violations print to their stderr at exit).

_LOCKCHECK_MODULES = ("test_concurrency", "test_replica", "test_qos",
                      "test_writelane", "test_ingest", "test_qcache",
                      "test_freethread")


def _lockcheck_wanted(item) -> bool:
    name = item.module.__name__ if item.module else ""
    return any(name.startswith(m) for m in _LOCKCHECK_MODULES)


@pytest.fixture(autouse=True)
def _lockcheck_gate(request):
    item = request.node
    try:
        wanted = _lockcheck_wanted(item)
    except Exception:
        wanted = False
    if not wanted:
        yield
        return
    from pilosa_tpu.analysis import lockcheck

    os.environ[lockcheck.ENV_VAR] = "1"  # spawned group workers inherit
    lockcheck.enable()
    lockcheck.reset()
    try:
        yield
    finally:
        os.environ.pop(lockcheck.ENV_VAR, None)
        violations = lockcheck.take_violations()
        lockcheck.disable()
        if violations:
            pytest.fail(
                f"lock checker recorded {len(violations)} violation(s):\n\n"
                + "\n\n".join(v.describe() for v in violations),
                pytrace=False,
            )


# -- replica-protocol trace conformance (pilosa_tpu/analysis/spec.py) -------
#
# The fault-seam e2e suite (test_replica_recovery) runs with the
# protocol event collector installed: every router/WAL/catch-up/resync
# transition emits an event record (zero cost when the collector is
# off), and at test teardown the recorded trace is validated against
# the executable write-protocol model — sequence monotonicity, quorum
# commits, tombstone/apply exclusion, per-epoch applied-mark
# monotonic-max, compaction floors, read-your-writes.  A reordering bug
# the assertions missed still fails the test with the exact protocol
# violation.  (Subprocess group events are invisible — the trace covers
# the in-process router side, which owns every invariant checked.)

_SPEC_TRACE_MODULES = ("test_replica_recovery", "test_replica_shard")


@pytest.fixture(autouse=True)
def _spec_trace_gate(request):
    item = request.node
    try:
        name = item.module.__name__ if item.module else ""
    except Exception:
        name = ""
    if not any(name.startswith(m) for m in _SPEC_TRACE_MODULES):
        yield
        return
    from pilosa_tpu.analysis import spec

    events = spec.install_collector()
    try:
        yield
    finally:
        spec.uninstall_collector()
        problems = spec.check_trace(events)
        if problems:
            pytest.fail(
                "replica-protocol trace conformance: "
                f"{len(problems)} violation(s) over {len(events)} event(s):\n"
                + "\n".join("  " + p for p in problems),
                pytrace=False,
            )
