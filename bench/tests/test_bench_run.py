"""``run.py`` has no CPU path: without a TPU it exits nonzero and prints
no result."""
import os
import subprocess
import sys

import bench_testlib as tl


def test_run_without_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(tl.BENCH, "run.py"), "--workload",
         tl.CELL_1, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=tl.ROOT, env=env)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "TPU" in proc.stderr
