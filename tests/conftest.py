"""Test harness configuration.

Forces JAX onto a virtual 8-device CPU platform so sharding/collective code
paths run deterministically without TPU hardware (SURVEY.md §4.3: the
multi-process ICI shuffle tests the reference lacks).

The driver runs the suite under ``JAX_PLATFORMS=cpu``; the config update
below holds a bare ``pytest`` run to the CPU too.  Backends are not
initialized until the first computation, so doing it in conftest is safe.
"""

import os
import threading

# XLA_FLAGS is read when the CPU client is created (lazily).
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Lock-order watchdog: the whole tier-1 suite runs with lockdep in
# record mode (raise only in the deliberate-inversion tests that opt
# in via lockdep.scoped).  Enabled HERE — before any test module
# imports the engine — so module-level locks are created tracked.
# TPUQ_LOCKDEP=0 opts out.
_LOCKDEP_ON = os.environ.get("TPUQ_LOCKDEP", "1") != "0"
if _LOCKDEP_ON:
    from spark_rapids_tpu.runtime import lockdep as _lockdep

    _lockdep.enable(raise_on_cycle=False)


def _lockdep_exempted(v) -> bool:
    """An observed violation whose acquisition site carries
    ``# lint: exempt(lockdep): <why>`` is deliberate."""
    rel, line = v.site
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), rel)
    try:
        from spark_rapids_tpu.utils.lint import SourceModule
        return SourceModule(path, rel).exempt_at(line, "lockdep")
    except OSError:
        return False


@pytest.fixture(autouse=True, scope="session")
def _lockdep_session_check():
    """Fail the run if the suite observed any unexempted lock-order
    cycle anywhere in the engine (an error in this finalizer fails the
    session even though no single test raised)."""
    yield
    if not _LOCKDEP_ON:
        return
    bad = [v for v in _lockdep.violations() if not _lockdep_exempted(v)]
    assert not bad, (
        "lockdep observed lock-order cycles during the suite:\n  "
        + "\n  ".join(str(v) for v in bad))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection chaos tests (deterministic smoke runs "
        "in tier 1; seed-randomized soaks are also marked slow)")
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1 runs")
    config.addinivalue_line(
        "markers",
        "distributed(timeout=90): rendezvous/multi-process tests run "
        "under a hard SIGALRM watchdog slightly above the rendezvous "
        "deadline — a regression that reintroduces a wedge fails tier-1 "
        "instead of hanging it")


def pytest_collection_modifyitems(config, items):
    # The kernel backend-identity matrix, the adaptive-plane
    # bit-identity matrix, and the attribution-plane closure tests are
    # the newest and most compile-heavy modules in the suite
    # (test_adaptive/test_attribution would otherwise run FIRST
    # alphabetically).  Tier-1 runs under a hard wall-clock budget (see
    # ROADMAP.md), so keep the long-established regression signal in
    # front and let the newest matrices run last — a harness-level
    # timeout then cuts into the newest tests first instead of
    # displacing the seed suite past the horizon.
    late = ("test_attribution.py", "test_adaptive.py", "test_kernels.py")
    items.sort(key=lambda it: (
        it.fspath.basename in late,
        it.fspath.basename in ("test_adaptive.py", "test_kernels.py"),
        it.fspath.basename == "test_kernels.py"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """Per-test watchdog for ``distributed``-marked tests."""
    import signal

    marker = item.get_closest_marker("distributed")
    use_alarm = (marker is not None and hasattr(signal, "SIGALRM")
                 and threading.current_thread()
                 is threading.main_thread())
    if not use_alarm:
        yield
        return
    budget = float(marker.kwargs.get("timeout", 90.0))

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"distributed-test watchdog: {item.nodeid} exceeded "
            f"{budget:.0f}s — a rendezvous wedge, not a slow test")

    prev = signal.signal(signal.SIGALRM, _on_alarm)
    signal.setitimer(signal.ITIMER_REAL, budget)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, prev)


@pytest.fixture
def rng_seed():
    return 0


@pytest.fixture(autouse=True, scope="module")
def _bound_live_xla_programs():
    """Clear kernel + jax executable caches after every test module.

    XLA:CPU JIT code space is finite: with several hundred live compiled
    programs in one process, a NEW compilation can SIGSEGV inside
    LLVM's emitter (reproduced: full suite crashes in
    test_window.py::test_running_aggregates_range_frame, any subset
    passes).  Kernels recompile lazily, so this only costs time."""
    yield
    from spark_rapids_tpu.runtime import kernel_cache
    kernel_cache.clear()
