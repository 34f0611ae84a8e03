"""What the limit raises is swallowed, and the case fails all the same."""
import time


def test_swallows_what_the_limit_raised():
    try:
        time.sleep(10 ** 6)
    except BaseException:       # as an exception in a `__del__` is ignored
        pass


def test_the_next_case_is_not_blamed():
    pass
