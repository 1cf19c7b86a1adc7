"""Root pytest config: gate the optional `hypothesis` dependency and the
nightly `slow` marker.

The target container does not ship hypothesis; registering the fallback
shim (tests/_hypothesis_fallback.py) under the `hypothesis` name keeps the
property tests collectable and running deterministically. A real
hypothesis install always wins — the shim is only used on ImportError.

Tests marked ``@pytest.mark.slow`` (large-budget randomized suites) are
skipped by the tier-1 run and selected by the nightly/manual CI lane via
``pytest -m slow`` (.github/workflows/nightly.yml).

``@pytest.mark.time_limit(seconds)`` fails a test that runs longer than
`seconds` (a ``SIGALRM`` timer, so only on the main thread of a POSIX
process; elsewhere the mark is ignored).
"""
import importlib.util
import signal
import sys
import threading
from pathlib import Path

import pytest

try:
    import hypothesis  # noqa: F401
except ImportError:
    _shim_path = Path(__file__).parent / "tests" / "_hypothesis_fallback.py"
    _spec = importlib.util.spec_from_file_location("hypothesis", _shim_path)
    _mod = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    sys.modules["hypothesis"] = _mod


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: large-budget randomized suite; tier-1 skips it, the nightly "
        "lane selects it with `pytest -m slow`")
    config.addinivalue_line(
        "markers", "time_limit(seconds): fail the test past `seconds`")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    mark = item.get_closest_marker("time_limit")
    if (mark is None or not hasattr(signal, "setitimer")
            or threading.current_thread() is not threading.main_thread()):
        yield
        return
    seconds = float(mark.args[0])

    def expire(signum, frame):
        raise TimeoutError(f"{item.nodeid} ran past its {seconds:g} s limit")

    previous = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


def pytest_collection_modifyitems(config, items):
    if "slow" in (config.option.markexpr or ""):
        return  # explicitly selected (nightly lane): run them
    skip_slow = pytest.mark.skip(
        reason="slow suite: nightly lane only (pytest -m slow)")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
