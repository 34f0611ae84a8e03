"""A case that sleeps for ever beside a thread that waits; then the next."""
import threading
import time

_never = threading.Event()


def test_sleeps_for_ever():
    threading.Thread(target=_never.wait, name="bystander", daemon=True).start()
    time.sleep(10 ** 6)


def test_the_next_case_runs_on_the_main_thread():
    assert threading.current_thread() is threading.main_thread()
