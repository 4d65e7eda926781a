"""Test harness: force an 8-device virtual CPU mesh.

Plays the role docker-compose plays in the reference's systest/ (SURVEY §4):
multi-"node" behavior on one machine. Must run before jax is imported
anywhere in the test process.
"""

import os

import pytest

# Arm the lock-order sanitizer (utils/locks.py) for the WHOLE suite —
# the `go test -race` analog: every subsystem lock created after this
# point is instrumented, and the session gate below fails the run if
# any lock-order cycle was observed anywhere. Must be set before any
# dgraph_tpu module creates its registry locks at import time.
os.environ.setdefault("DGRAPH_TPU_LOCK_SANITIZER", "1")
# ... and the Eraser lockset RACE sanitizer (ISSUE 12): every class in
# the static lock-discipline inventory (analysis/guards.py) arms its
# guarded fields via locks.guarded(); an access whose candidate
# lockset empties after a cross-thread write is a data race, reported
# with both stacks and failing the session gate below.
os.environ.setdefault("DGRAPH_TPU_RACE_SANITIZER", "1")

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# The served path runs on the native library (JSON emitter, CSR builder,
# codec). libdgtpu.so is git-ignored, so a fresh checkout builds it here,
# before collection order can decide which tests see the numpy fallbacks.
import dgraph_tpu.native as _native  # noqa: E402  (jax-free)

if not _native.HAVE_NATIVE:
    _native.build()

import jax  # noqa: E402  (imported here so the flags above bind first)

# Tests run on the CPU (tier-1 sets JAX_PLATFORMS=cpu; this pins it for a
# bare `pytest` too, before the first backend init). The chip is reached
# only through the chip tool, with `python chip_smoke.py`.
jax.config.update("jax_platforms", "cpu")

assert jax.device_count() >= 8, "virtual device mesh failed to initialise"

# Sanitizer-equivalent mode (reference: `go test -race` in CI; SURVEY §5
# build equivalent): DGRAPH_TPU_DEBUG_CHECKS=1 runs the whole suite under
# jax_debug_nans (any NaN in a jitted program faults immediately) and
# jax_enable_checks (internal invariant checks + tracer leak detection).
if os.environ.get("DGRAPH_TPU_DEBUG_CHECKS") == "1":
    jax.config.update("jax_debug_nans", True)
    jax.config.update("jax_enable_checks", True)


@pytest.fixture(autouse=True, scope="session")
def _lock_order_session_gate():
    """Session-wide lock-order gate: after the LAST test, the global
    acquisition graph must be acyclic. A cycle here means two real
    subsystem locks were taken in opposite orders somewhere in the
    suite — a deadlock waiting for the right interleaving."""
    yield
    from dgraph_tpu.utils import locks
    cycles = locks.GRAPH.cycles()
    assert not cycles, (
        "lock-order cycle(s) observed during the test session:\n"
        + "\n".join(
            " -> ".join(c["cycle"] + [c["cycle"][0]])
            + "\n" + "\n".join(e["stack"] for e in c["edges"])
            for c in cycles))


@pytest.fixture(autouse=True, scope="session")
def _race_session_gate():
    """Session-wide DATA-RACE gate (ISSUE 12): after the LAST test, the
    Eraser lockset sanitizer must have zero reports. A report means a
    guarded field of some subsystem object was accessed with an empty
    candidate lockset after a cross-thread write — an actual unguarded
    access that happened during this run, with both stacks attached."""
    yield
    from dgraph_tpu.utils import locks
    reports = locks.RACES.snapshot()["reports"]
    assert not reports, (
        "data race(s) observed during the test session:\n"
        + "\n".join(
            f"{r['class']}.{r['field']} (lock {r['lock']}): "
            f"{r['kind']} with locksets {r['first']['lockset']} / "
            f"{r['second']['lockset']}\n--- first access:\n"
            f"{r['first']['stack']}\n--- racing access:\n"
            f"{r['second']['stack']}"
            for r in reports))
