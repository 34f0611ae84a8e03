"""Ten cases on a module's fixture that came back and answers nobody."""
import threading

import pytest


@pytest.fixture(scope="module")
def engine():
    return threading.Event()


@pytest.mark.parametrize("case", range(10))
def test_on_an_engine_that_answers_nobody(engine, case):
    engine.wait()
