"""bench/run.py refuses a device that is not a TPU: exit 1, no result."""

import os
import subprocess
import sys

from bench import spec


def test_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-resnet-d4.refill-n500",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr
