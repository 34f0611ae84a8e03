"""Log monitor: worker stdout/stderr → GCS → driver/CLI.

Mirrors the reference's log monitor behavior (ref: python/ray/_private/
log_monitor.py + worker.py print_logs): a remote task's print() appears
on the driver's stdout with a prefix, and a DEAD worker's last lines
stay readable from the GCS ring buffer.
"""
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu.core.distributed.log_monitor import LogMonitor, _Tail


def test_tail_reads_incrementally(tmp_path):
    p = tmp_path / "worker-abc.out"
    p.write_bytes(b"one\ntwo\npart")
    t = _Tail(str(p))
    assert t.read_new_lines() == ["one", "two"]
    with open(p, "ab") as f:
        f.write(b"ial\nthree\n")
    assert t.read_new_lines() == ["partial", "three"]
    assert t.read_new_lines() == []


def test_sweep_builds_attributed_records(tmp_path):
    (tmp_path / "worker-w1.out").write_bytes(b"hello\n")
    (tmp_path / "worker-w1.err").write_bytes(b"oops\n")
    (tmp_path / "ignored.txt").write_bytes(b"nope\n")
    mon = LogMonitor(str(tmp_path), "node1",
                     lambda wid: {"actor_id": "a" * 16, "job_id": "j1",
                                  "pid": 42})
    recs = {(r["worker_id"], r["stream"]): r for r in mon.sweep()}
    assert set(recs) == {("w1", "stdout"), ("w1", "stderr")}
    assert recs[("w1", "stdout")]["lines"] == ["hello"]
    assert recs[("w1", "stdout")]["job_id"] == "j1"
    assert recs[("w1", "stderr")]["lines"] == ["oops"]
    assert mon.sweep() == []  # no new content


_DRIVER_SCRIPT = r"""
import time
import ray_tpu

ray_tpu.init(num_cpus=2)

@ray_tpu.remote
def shout():
    print("HELLO_FROM_WORKER_TASK")
    return 1

@ray_tpu.remote
class Yeller:
    def yell(self):
        print("HELLO_FROM_ACTOR")
        return 2

assert ray_tpu.get(shout.remote()) == 1
a = Yeller.remote()
assert ray_tpu.get(a.yell.remote()) == 2
# Give the tail sweep (0.25s) + pubsub delivery time to land.
time.sleep(2.0)
ray_tpu.shutdown()
print("DRIVER_DONE")
"""


def test_worker_prints_stream_to_driver_stdout(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + ":" + env.get("PYTHONPATH", "")
    script = tmp_path / "driver.py"
    script.write_text(_DRIVER_SCRIPT)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=180)
    assert "DRIVER_DONE" in out.stdout, out.stderr[-2000:]
    assert "HELLO_FROM_WORKER_TASK" in out.stdout
    assert "HELLO_FROM_ACTOR" in out.stdout
    # Reference-style attribution prefix on the streamed line.
    line = next(ln for ln in out.stdout.splitlines()
                if "HELLO_FROM_ACTOR" in ln)
    assert "node=" in line and ("actor=" in line or "worker=" in line)


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    yield ray_tpu.api._global_worker()
    ray_tpu.shutdown()


def test_dead_worker_last_lines_survive_in_gcs(cluster):
    @ray_tpu.remote(max_restarts=0)
    class Doomed:
        def last_words(self):
            print("FAMOUS_LAST_WORDS", flush=True)
            return "ok"

        def die(self):
            import os as _os

            _os._exit(1)

    a = Doomed.remote()
    assert ray_tpu.get(a.last_words.remote(), timeout=60) == "ok"
    time.sleep(1.0)  # let the tailer ship the line before the kill
    try:
        ray_tpu.get(a.die.remote(), timeout=30)
    except Exception:  # noqa: BLE001 — death surfaces as an error
        pass
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        recs = cluster.gcs.call("LogManager", "tail_logs",
                                num_lines=200, timeout=15)
        lines = [ln for r in recs for ln in r["lines"]]
        if any("FAMOUS_LAST_WORDS" in ln for ln in lines):
            return
        time.sleep(0.5)
    raise AssertionError("dead worker's lines never reached the GCS ring")


def test_cli_logs_dead_worker_post_mortem(cluster, capsys):
    """`ray-tpu logs --dead`: the GCS-retained last lines of a worker
    that no longer exists are reachable from the CLI, and live workers
    are filtered out of the post-mortem view."""

    @ray_tpu.remote(max_restarts=0)
    class Doomed2:
        def last_words(self):
            print("POST_MORTEM_LINE", flush=True)
            return "ok"

        def die(self):
            import os as _os

            _os._exit(1)

    @ray_tpu.remote
    class Chatty:
        def say(self):
            print("STILL_ALIVE_LINE", flush=True)
            return 1

    a = Doomed2.remote()
    b = Chatty.remote()
    assert ray_tpu.get(a.last_words.remote(), timeout=60) == "ok"
    assert ray_tpu.get(b.say.remote(), timeout=60) == 1
    time.sleep(1.0)  # let the tailer ship the lines before the kill
    try:
        ray_tpu.get(a.die.remote(), timeout=30)
    except Exception:  # noqa: BLE001 — death surfaces as an error
        pass
    from ray_tpu.scripts.cli import main as cli_main

    def cli_ring_lines(s):
        # Only the CLI's own dump (== headers + indented ring lines):
        # the driver's live log STREAM also prints to stdout and must
        # not satisfy the assertions.
        return [ln for ln in s.splitlines()
                if ln.startswith("== ") or ln.startswith("  ")]

    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        cli_main(["--address", cluster.gcs_address, "logs", "--dead"])
        lines = cli_ring_lines(capsys.readouterr().out)
        if any("POST_MORTEM_LINE" in ln for ln in lines):
            break
        time.sleep(0.5)
    else:
        raise AssertionError("dead worker's lines never reached "
                             "`logs --dead`")
    # The post-mortem view excludes workers that are still alive: the
    # live Chatty actor's line is in the plain dump but not in --dead.
    assert not any("STILL_ALIVE_LINE" in ln for ln in lines), lines
    cli_main(["--address", cluster.gcs_address, "logs"])
    full = cli_ring_lines(capsys.readouterr().out)
    assert any("STILL_ALIVE_LINE" in ln for ln in full), full
    assert any("POST_MORTEM_LINE" in ln for ln in full), full
    ray_tpu.kill(b)


def test_cli_logs_dumps_ring(cluster, capsys):
    @ray_tpu.remote
    def noisy():
        print("CLI_VISIBLE_LINE")
        return 0

    ray_tpu.get(noisy.remote(), timeout=60)
    from ray_tpu.scripts.cli import main as cli_main

    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        cli_main(["--address", cluster.gcs_address, "logs"])
        out = capsys.readouterr().out
        if "CLI_VISIBLE_LINE" in out:
            assert "worker=" in out or "actor=" in out
            return
        time.sleep(0.5)
    raise AssertionError("CLI logs never showed the worker line")
