"""The suite's per-test time limit (tests/conftest.py: TEST_LIMIT_S,
arm_test_limit) ends a process whose main thread is blocked inside a C
call, and says where it was blocked."""

import os
import subprocess
import sys
import time

_CHILD = """
import sys, threading, time
sys.path.insert(0, {tests_dir!r})
import conftest
print(time.monotonic(), flush=True)
conftest.arm_test_limit(1)
lock = threading.Lock()
lock.acquire()
lock.acquire()  # BLOCKED HERE
"""


def test_limit_ends_a_process_blocked_in_c_and_names_the_line():
    child = _CHILD.format(tests_dir=os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", child], capture_output=True,
                          text=True, timeout=120)
    # Both clocks are CLOCK_MONOTONIC, which is one clock for the machine.
    seconds = time.monotonic() - float(proc.stdout)
    assert proc.returncode != 0
    assert seconds < 5.0
    assert "Timeout (0:00:01)!" in proc.stderr
    blocked_at = _CHILD.splitlines().index("lock.acquire()  # BLOCKED HERE") + 1
    assert f'File "<string>", line {blocked_at} in <module>' in proc.stderr
