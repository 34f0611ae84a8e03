"""Ten cases behind a module's fixture that never returns."""
import threading

import pytest


@pytest.fixture(scope="module")
def engines():
    threading.Event().wait()


@pytest.mark.parametrize("case", range(10))
def test_behind_a_fixture_that_never_returns(engines, case):
    pass
