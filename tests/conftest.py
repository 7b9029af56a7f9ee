import os
import signal
import threading

# smoke tests and benches must see the single real CPU device (the 512-device
# override is dryrun.py-local, never global)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)

#: wall-clock seconds a test's setup plus call may take before it fails, so a
#: hang costs that one test rather than the rest of the run
TEST_TIME_LIMIT_S = 300


def _alarm_usable():
    return (hasattr(signal, "SIGALRM")
            and threading.current_thread() is threading.main_thread())


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    if not _alarm_usable():
        yield
        return

    def _expired(signum, frame):
        pytest.fail(f"{item.nodeid} ran past the {TEST_TIME_LIMIT_S} s "
                    f"limit on setup plus call", pytrace=False)

    previous = signal.signal(signal.SIGALRM, _expired)
    signal.setitimer(signal.ITIMER_REAL, TEST_TIME_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_teardown(item, nextitem):
    if _alarm_usable():
        signal.setitimer(signal.ITIMER_REAL, 0)


@pytest.fixture(autouse=True, scope="module")
def _fresh_polyhedron_cache():
    """Emptiness-verdict memoization must not leak across test modules; the
    stats dict must stay well-formed whatever the module did to the cache."""
    from repro.core import clear_polyhedron_cache, polyhedron_cache_stats

    clear_polyhedron_cache()
    yield
    stats = polyhedron_cache_stats()
    assert {"hits", "misses", "empty_entries", "point_entries",
            "box_entries", "evictions", "loaded"} <= set(stats)
    assert all(isinstance(v, int) and v >= 0 for v in stats.values())
    # every resident entry came from a computed miss or a persistent-store /
    # worker merge ("loaded"); eviction only ever shrinks the caches
    assert (stats["empty_entries"] + stats["point_entries"]
            + stats["box_entries"] <= stats["misses"] + stats["loaded"])
