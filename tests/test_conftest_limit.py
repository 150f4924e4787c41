"""The limit every test runs under (``tests/conftest.py``,
``TEST_LIMIT_S``), driven for real: a pytest of its own, in a
subprocess, under this repo's conftest with the constant cut to one
second."""

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

BLOCKED_IN_TERM = '''
import zmq

def test_blocks():
    ctx = zmq.Context()
    sock = ctx.socket(zmq.PUSH)     # open: term() waits for it for ever
    ctx.term()

def test_next():
    pass
'''

BLOCKED_IN_A_FINALIZER = '''
import zmq

class Leaked:
    def __del__(self):      # where tier-1 hung: the collector's __del__
        ctx = zmq.Context()
        sock = ctx.socket(zmq.PUSH)
        ctx.term()

def test_collects():
    Leaked()

def test_next():
    pass
'''

DEAF_TO_THE_SIGNAL = '''
import signal, time

def test_deaf():
    signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
    time.sleep(60)

def test_next():
    pass
'''


def run_limited(tmp_path, source):
    case = tmp_path / "test_case.py"
    case.write_text(source)
    return subprocess.run(
        [sys.executable, "-c",
         "import sys, pytest, tests.conftest as conftest\n"
         "conftest.TEST_LIMIT_S = 1\n"
         "sys.exit(pytest.main(sys.argv[1:]))",
         str(case), "-p", "tests.conftest", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-q", "--rootdir", str(tmp_path)],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=110)


def test_a_blocked_term_costs_one_named_failure_and_the_run_goes_on(
        tmp_path):
    done = run_limited(tmp_path, BLOCKED_IN_TERM)
    out = done.stdout
    assert done.returncode == 1, out + done.stderr
    assert "1 failed, 1 passed" in out
    assert "TIMED OUT: test_case.py::test_blocks ran into the limit " \
           "of 1 s a test" in out
    # every thread's stack, in the failed case's own captured stderr
    stacks = out[out.index("Captured stderr call"):]
    assert " in term\n" in stacks and " in test_blocks\n" in stacks


def test_a_wait_inside_a_finalizer_costs_its_case_too(tmp_path):
    """Python drops an exception that comes out of a ``__del__``, the
    signal handler's too: the wait ends and the case would pass. It
    fails all the same, by name."""
    done = run_limited(tmp_path, BLOCKED_IN_A_FINALIZER)
    assert done.returncode == 1, done.stdout + done.stderr
    assert "1 failed, 1 passed" in done.stdout
    assert "TIMED OUT: test_case.py::test_collects ran into the limit " \
           "of 1 s a test" in done.stdout


def test_a_wait_no_signal_ends_costs_the_process_with_its_stacks_printed(
        tmp_path):
    """The second line: at twice the limit the watchdog thread prints
    the stacks to the process's own stderr and ends it (under xdist:
    one worker, which is replaced)."""
    done = run_limited(tmp_path, DEAF_TO_THE_SIGNAL)
    assert done.returncode == 1, done.stdout + done.stderr
    assert "passed" not in done.stdout
    assert "Timeout (0:00:02)!" in done.stderr
    assert " in test_deaf\n" in done.stderr
