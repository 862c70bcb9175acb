"""The example scripts run end to end (a subprocess each, on the CPU mesh):
user-facing entry points must not rot (the reference smoke-runs its examples
in CI, .buildkite/gen-pipeline.sh). The tests stand by subject in three
files of about equal seconds, `tests/test_examples_training.py`,
`tests/test_examples_tensorflow.py` and `tests/test_examples_jobs.py`: they
share nothing but this helper, each is 20-70 s of a child process, and three
files end in parallel where one file of ten ended alone (`pytest-xdist` hands
a file to one worker, and a file of few tests out last)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_example(name, *args, timeout=240, **env):
    """`examples/<name>`'s output; it must exit with 0. (`timeout` is under
    `TEST_LIMIT_S`, tests/conftest.py: the child is reaped here, not
    orphaned by the limit firing first.)"""
    env = dict(os.environ, **env)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    # (as `tests/conftest.py` compiles the suite's own stand-ins)
    env["JAX_DISABLE_MOST_OPTIMIZATIONS"] = "1"
    env["PYTHONPATH"] = REPO
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", name), *args],
        env=env, capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, \
        f"{name} failed:\nstdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout
