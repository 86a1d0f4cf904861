"""Shared test configuration.

``hypothesis`` is an optional dev dependency (requirements-dev.txt): the
property-based tests skip cleanly when it is absent, while the plain
parametrized tests in the same modules keep running.

Every wait in ``tests/`` is bounded, so a hung test fails by name and the
run still reaches its end:

- each test's setup, call and teardown runs under a ``SIGALRM`` ceiling
  of :data:`TEST_TIMEOUT_S`;
- the multi-device parity scripts run through :func:`run_script`, whose
  limit :data:`SCRIPT_TIMEOUT_S` sits under that ceiling, so a script
  that overruns fails its markers with its partial output instead of
  tripping the ceiling of the fixture that waits for it.
"""

from __future__ import annotations

import contextlib
import os
import signal
import subprocess
import sys

import pytest

try:
    import hypothesis  # noqa: F401
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

TEST_TIMEOUT_S = 300
SCRIPT_TIMEOUT_S = 240


def optional_hypothesis():
    """Return ``(given, settings, st)`` — real, or stubs that skip the test."""
    if HAVE_HYPOTHESIS:
        from hypothesis import given, settings, strategies as st
        return given, settings, st

    def given(*_args, **_kwargs):
        def deco(fn):
            return pytest.mark.skip(reason="hypothesis not installed")(fn)
        return deco

    def settings(*_args, **_kwargs):
        return lambda fn: fn

    class _Strategies:
        def __getattr__(self, name):
            return lambda *a, **k: None

    return given, settings, _Strategies()


# ---------------------------------------------------------------------------
# per-test ceiling
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _ceiling(item, when):
    def on_alarm(_signum, _frame):
        pytest.fail(f"{item.nodeid} {when} exceeded the {TEST_TIMEOUT_S} s "
                    f"per-test ceiling")

    prev = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, prev)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with _ceiling(item, "setup"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with _ceiling(item, "call"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item, nextitem):
    with _ceiling(item, "teardown"):
        return (yield)


# ---------------------------------------------------------------------------
# subprocess scripts (8 forced host devices)
# ---------------------------------------------------------------------------

def _text(out) -> str:
    return out.decode(errors="replace") if out else ""


def run_script(script: str) -> subprocess.CompletedProcess:
    """Run ``python -c script`` from the repo root with ``PYTHONPATH=src``.

    A script killed at :data:`SCRIPT_TIMEOUT_S` comes back with
    ``returncode`` None and the output it printed so far (unbuffered, so
    every marker it reached is there).
    """
    env = {**os.environ, "PYTHONPATH": "src", "PYTHONUNBUFFERED": "1"}
    try:
        return subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True,
                              timeout=SCRIPT_TIMEOUT_S, env=env)
    except subprocess.TimeoutExpired as e:
        return subprocess.CompletedProcess(e.cmd, None, _text(e.stdout),
                                           _text(e.stderr))


def assert_marker(run: subprocess.CompletedProcess, marker: str) -> None:
    """Fail unless the script exited cleanly and printed ``marker``."""
    if run.returncode is None:
        lines = run.stdout.strip().splitlines()
        last = lines[-1] if lines else "none"
        pytest.fail(f"script killed at its {SCRIPT_TIMEOUT_S} s limit before "
                    f"exiting; last marker printed: {last}; looking for "
                    f"{marker}\nstdout tail:\n{run.stdout[-2000:]}\n"
                    f"stderr tail:\n{run.stderr[-2000:]}")
    assert run.returncode == 0, run.stderr[-4000:]
    assert marker in run.stdout
