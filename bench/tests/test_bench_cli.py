"""bench/run.py refuses to run, and prints no result, without a TPU."""
import os
import subprocess
import sys

import benchpath


def test_run_without_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join("bench", "run.py"), "--workload", "xl-bl1.steady",
         "--seed", str(2 ** 31 + 11), "--seconds", "5", "--trace", "0"],
        cwd=benchpath.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
