"""chip_smoke.py on the CPU: the script refuses to run without a TPU,
and its whole path — mount, registration, _bulk slice, HTTP queries,
oracle comparison — runs end to end with only the chip checks failing.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# what a CPU backend can never pass: the platform, and the Pallas merge
# compiled by Mosaic instead of interpreted
CPU_ONLY_FAILURES = {"platform is cpu, not tpu",
                     "the Pallas merge runs in interpret mode"}


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py", "--docs", "2000",
                        "--queries", "4"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode != 0, (r.stdout, r.stderr)
    assert "needs 1 TPU chip(s)" in r.stderr
    for line in r.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


@pytest.mark.parametrize("argv", [
    ["--docs", "8000", "--queries", "8"],
    ["--chips", "4", "--docs", "8000", "--queries", "8"],
], ids=["one_chip", "four_shards"])
def test_runs_end_to_end_and_fails_only_on_the_platform(argv):
    smoke = chip_smoke.run(chip_smoke.parse_args(argv))
    assert "platform is cpu, not tpu" in smoke.failures
    assert set(smoke.failures) <= CPU_ONLY_FAILURES, smoke.failures
