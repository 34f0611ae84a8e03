"""The kinds of wait the suite's cases wait in, each with no timeout."""
import socket
import subprocess
import sys
import threading

import pytest

_children = []


@pytest.fixture
def child():
    proc = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(10 ** 6)"])
    _children.append(proc)
    yield proc
    proc.kill()
    proc.wait()


def test_event_wait():
    threading.Event().wait()


def test_thread_join():
    thread = threading.Thread(target=threading.Event().wait, daemon=True)
    thread.start()
    thread.join()


def test_popen_wait(child):
    child.wait()


def test_the_finaliser_reaped_the_child():
    (proc,) = _children
    assert proc.returncode is not None


def test_socket_recv():
    ours, theirs = socket.socketpair()
    ours.recv(1)
