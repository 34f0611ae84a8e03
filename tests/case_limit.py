"""A case has a time limit of its own, and fails by name when it runs into it.

A case that waits for what never comes would spend the whole run's clock:
the driver's `timeout` cuts the run, no junit file is written, no name is
left.  Here a timer (`SIGALRM`, on the main thread, where pytest and xdist's
workers run a case) runs from a case's set-up to the end of its tear-down.
When it fires inside one of the three phases the case fails with "ran into
its limit of N s" and every thread's stack; the worker lives, the fixtures'
finalisers run (each wait of theirs under a tenth of the limit), the file's
next case runs, and the file's later cases get a tenth of the limit: a file
whose shared fixture is dead costs two limits, not the limit times its cases.

A run that is slow all over is still cut (SIGTERM, SIGKILL ten seconds
later): it then leaves its junit file, with the cases that had reported.

One constant, no marker, no option: a case that needs more is made smaller
or marked `slow`.  `tests/conftest.py` registers this module as a plugin;
`tests/README.md` says where the constant came from.
"""
import os
import re
import signal
import sys
import threading
import traceback

import pytest
from _pytest.junitxml import xml_key

# At least three times the longest case of the driver's command under
# `-n 6` (tests/README.md has the junit it was read from) and at least 180.
LIMIT_S = 240

_PYTEST_FRAME = re.compile(
    r"/(_pytest|pluggy|pytest|xdist|execnet)/|<frozen runpy>")

_in_phase = False       # the timer raises only inside set-up, call, tear-down
_budget = 0.0           # what the running case was given
_module = None          # the running case's file
_ran_out = set()        # files one of whose cases ran into its limit
_said = None            # the report, until the phase it was raised in ends


def _stacks():
    """Every thread's stack, without pytest's own frames."""
    names = {t.ident: t.name for t in threading.enumerate()}
    return "\n".join(
        f"--- thread {names.get(ident, '?')} ({ident}) ---\n" + "".join(
            traceback.format_list(
                [at for at in traceback.extract_stack(frame)
                 if not _PYTEST_FRAME.search(at.filename)]))
        for ident, frame in sys._current_frames().items())


def _on_alarm(signum, frame):
    global _said
    if not _in_phase:   # between phases pytest is writing a report: wait
        signal.setitimer(signal.ITIMER_REAL, 0.05)
        return
    _ran_out.add(_module)
    signal.setitimer(signal.ITIMER_REAL, LIMIT_S / 10)  # finalisers that wait
    _said = _said or f"ran into its limit of {_budget:g} s\n{_stacks()}"
    pytest.fail(_said, pytrace=False)


def pytest_sessionstart(session):
    xml = session.config.stash.get(xml_key, None)   # the run's, no worker's
    if xml is None:
        return

    def cut(signum, frame):
        signal.signal(signal.SIGTERM, signal.SIG_IGN)   # `timeout` sends two
        try:
            xml.pytest_sessionfinish()                  # writes the file
        finally:
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
            os.kill(os.getpid(), signal.SIGTERM)

    signal.signal(signal.SIGTERM, cut)


def _phase():
    global _in_phase, _said
    _in_phase = True
    try:
        result = yield
    except BaseException:
        _said = None        # it came out, or something else did: reported
        raise
    finally:
        _in_phase = False
    if _said:   # raised where nothing comes out (a `__del__`), or swallowed
        said, _said = _said, None
        pytest.fail(said, pytrace=False)
    return result


@pytest.hookimpl(wrapper=True, trylast=True)
def pytest_runtest_setup(item):
    global _budget, _module
    _module = item.path
    _budget = LIMIT_S / 10 if _module in _ran_out else LIMIT_S
    signal.signal(signal.SIGALRM, _on_alarm)    # the main thread's to set
    signal.setitimer(signal.ITIMER_REAL, _budget)
    return (yield from _phase())


@pytest.hookimpl(wrapper=True, trylast=True)
def pytest_runtest_call(item):
    return (yield from _phase())


@pytest.hookimpl(wrapper=True, trylast=True)
def pytest_runtest_teardown(item):
    try:
        return (yield from _phase())
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
