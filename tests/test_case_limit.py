"""The limit of a case (`tests/case_limit.py`), held to what it promises.

Each case runs pytest in a subprocess on a small module of
`tests/case_limit_fixtures/`, whose conftest sets the limit to seconds.
"""
import functools
import signal
import subprocess
import sys
import time
import xml.etree.ElementTree as ET
from pathlib import Path

import psutil
import pytest

FIXTURES = Path(__file__).resolve().parent / "case_limit_fixtures"
LIMIT_S = 3         # what tests/case_limit_fixtures/conftest.py sets


@functools.lru_cache(maxsize=None)
def _inner(tmp, module, *options):
    """rc, output, {case: (seconds, reports)} of pytest on one module."""
    where = Path(tmp, "".join((module, *options)))
    junit = where.with_suffix(".xml")
    ran = subprocess.run(
        [sys.executable, "-m", "pytest", str(FIXTURES / f"{module}.py"),
         "-q", "-p", "no:cacheprovider", "-p", "no:randomly",
         f"--rootdir={FIXTURES}", f"--confcutdir={FIXTURES}",
         f"--basetemp={where}", f"--junitxml={junit}", *options],
        capture_output=True, text=True, timeout=120)
    cases = {}
    for case in ET.parse(junit).getroot().iter("testcase"):
        said = [child.get("message", "") + "\n" + (child.text or "")
                for child in case if child.tag in ("failure", "error")]
        seconds, reports = cases.get(case.get("name"), (0.0, []))
        cases[case.get("name")] = (seconds + float(case.get("time")),
                                   reports + said)
    return ran.returncode, ran.stdout + ran.stderr, cases


@pytest.fixture(scope="module")
def inner(tmp_path_factory):
    return functools.partial(_inner, str(tmp_path_factory.mktemp("inner")))


def _ran_into_its_limit(report, limit=LIMIT_S):
    return f"ran into its limit of {limit:g} s" in "".join(report)


@pytest.mark.parametrize("options", [(), ("-p", "xdist", "-n", "1")],
                         ids=["alone", "under_xdist"])
def test_a_case_that_sleeps_for_ever_fails_by_name_and_the_next_runs(
        inner, options):
    rc, said, cases = inner("sleeper", *options)
    assert rc == 1, said
    seconds, report = cases["test_sleeps_for_ever"]
    assert _ran_into_its_limit(report) and LIMIT_S <= seconds < 2 * LIMIT_S
    # every thread's stack: the case's own line, and the thread beside it
    (text,) = report
    stacks = dict(stack.split(" ", 1)
                  for stack in text.split("--- thread ")[1:])
    assert "time.sleep(10 ** 6)" in stacks["MainThread"]
    assert "in wait" in stacks["bystander"]
    assert cases["test_the_next_case_runs_on_the_main_thread"][1] == []
    assert "timer left at the end: 0" in said


def test_a_case_that_swallows_what_the_limit_raised_still_fails(inner):
    """PR 65's first whole run: the wait was a lock taken again inside an
    `ObjectRef.__del__`, where an exception is printed and ignored; the wait
    ended, the case went on and passed, 264 s later."""
    rc, said, cases = inner("swallowed")
    assert rc == 1, said
    assert _ran_into_its_limit(cases["test_swallows_what_the_limit_raised"][1])
    assert cases["test_the_next_case_is_not_blamed"][1] == []


@pytest.mark.parametrize("wait", ["event_wait", "thread_join", "popen_wait",
                                  "socket_recv"])
def test_a_wait_with_no_timeout_is_interrupted(inner, wait):
    rc, said, cases = inner("waits")
    assert rc == 1, said
    seconds, report = cases[f"test_{wait}"]
    # the module's first case gets the limit, the later ones a tenth of it
    assert (_ran_into_its_limit(report)
            or _ran_into_its_limit(report, LIMIT_S / 10)), said
    assert seconds < 2 * LIMIT_S


def test_a_finaliser_still_runs_and_reaps_the_child(inner):
    _, said, cases = inner("waits")
    assert cases["test_the_finaliser_reaped_the_child"][1] == [], said


def test_get_with_no_timeout_is_interrupted_and_the_cluster_is_reaped(inner):
    rc, said, cases = inner("cluster")
    assert rc == 1, said
    _, report = cases["test_get_of_a_task_that_never_ends"]
    assert _ran_into_its_limit(report) and "_get_one" in "".join(report)
    pids = Path(inner.args[0], "cluster", "cluster_pids").read_text()
    pids = [int(pid) for pid in pids.split()]
    assert pids
    deadline = time.monotonic() + 30
    while any(map(psutil.pid_exists, pids)) and time.monotonic() < deadline:
        time.sleep(0.1)
    assert not any(map(psutil.pid_exists, pids))


@pytest.mark.parametrize("module", ["dead_fixture", "dead_object"])
def test_ten_cases_behind_a_dead_fixture_end_within_two_limits(inner, module):
    rc, said, cases = inner(module)
    assert rc == 1, said
    assert len(cases) == 10 and all(report for _, report in cases.values())
    assert sum(seconds for seconds, _ in cases.values()) < 2 * LIMIT_S, cases


@pytest.mark.parametrize("options", [(), ("-p", "xdist", "-n", "1")],
                         ids=["alone", "under_xdist"])
def test_a_run_that_is_cut_leaves_its_junit_file(tmp_path, options):
    """SIGTERM, as the driver's `timeout` sends it: the file holds the case
    that had reported, and the run still dies of the signal."""
    run = subprocess.Popen(
        [sys.executable, "-m", "pytest", str(FIXTURES / "cut.py"), "-q",
         "-p", "no:cacheprovider", f"--rootdir={FIXTURES}",
         f"--confcutdir={FIXTURES}", f"--basetemp={tmp_path / 'inner'}",
         f"--junitxml={tmp_path / 'cut.xml'}", *options],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    deadline = time.monotonic() + 60
    while not list(tmp_path.glob("inner/**/out")):
        assert run.poll() is None and time.monotonic() < deadline
        time.sleep(0.05)
    run.send_signal(signal.SIGTERM)     # `timeout` signals its child, and
    run.send_signal(signal.SIGTERM)     # then its child's process group
    assert run.wait(timeout=30) == -signal.SIGTERM
    (suite,) = ET.parse(tmp_path / "cut.xml").getroot().iter("testsuite")
    assert (suite.get("tests"), suite.get("failures"), suite.get("errors"),
            suite.get("skipped")) == ("1", "0", "0", "0")
    # (the case that was out is an element with no name and no outcome)
    assert [case.get("name") for case in suite.iter("testcase")] == [
        "test_ends_before_the_cut", None]


def test_a_case_that_ends_in_time_is_untouched_and_the_timer_is_stopped(inner):
    rc, said, cases = inner("in_time")
    assert rc == 0, said
    assert [report for _, report in cases.values()] == [[], []]
    assert "timer left at the end: 0" in said
