"""The inner runs of tests/test_case_limit.py: the limit is seconds here.

Run with `--rootdir` and `--confcutdir` at this directory, so that this is
the only conftest; tier-1 never comes here (`collect_ignore` in
tests/conftest.py), so the constant below never reaches a real run.
"""
import os
import signal
import sys

TESTS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [TESTS, os.path.dirname(TESTS)]    # case_limit, ray_tpu

import case_limit  # noqa: E402

case_limit.LIMIT_S = 3


def pytest_configure(config):
    config.pluginmanager.register(case_limit, "case_limit")


def pytest_terminal_summary(terminalreporter):
    left = signal.getitimer(signal.ITIMER_REAL)[0]
    terminalreporter.write_line(f"timer left at the end: {left:g}")
