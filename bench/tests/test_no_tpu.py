"""Without a TPU the command exits nonzero and prints no result."""

import os
import subprocess
import sys

import harness


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "stencil7-l512-loop",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout
