"""The control (the reference in bfloat16, in the program's place) fails
every cell's comparison, and the reference in its own place passes it."""

import pytest

from benchmark import control
from benchmark.tests.tiny import SHARDED, TINY


@pytest.mark.parametrize("cell,overrides", [(c, TINY[c]) for c in sorted(
    TINY)] + [SHARDED], ids=sorted(TINY) + ["sharded"])
def test_control_fails_and_reference_passes(cell, overrides):
    r = control.readings(cell, 424242424242, 2.0, dict(overrides))
    assert r["answers"] > 0
    assert any(r["control"][k] > r["limits"][k]
               for k in ("score_gap", "rank_gap")), r
    assert all(r["reference"][k] == 0.0
               for k in ("score_gap", "rank_gap")), r
