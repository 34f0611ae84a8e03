"""Cases that end in time are untouched, and each starts with a whole limit."""
import signal
import time

import case_limit


def test_ends_in_time():
    time.sleep(1)


def test_starts_with_a_whole_limit():
    left = signal.getitimer(signal.ITIMER_REAL)[0]
    assert case_limit.LIMIT_S - 0.5 < left <= case_limit.LIMIT_S
