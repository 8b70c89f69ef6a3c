"""The command's own look for a chip: without one it exits 1 and prints
no result."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_no_tpu_exits_nonzero_without_a_result():
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "msmarco-bm25-top1000-open", "--seed", "4294967311", "--seconds",
         "1", "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 1
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
