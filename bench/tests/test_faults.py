"""The check catches the control and every planted fault a cell can
have: a run driven past the look for a chip, with its timed path broken
underneath, comes out not correct.  At one chip there is no exchange
between chips to leave out, and a read-only round has no state to leave
unchanged."""
from __future__ import annotations

import pytest

CASES = [
    ("paper-dht.read.uniform", "control"),
    ("paper-dht.read.uniform", "half_batch"),
    ("paper-dht.read.uniform", "alter_answer"),
    ("ycsb-a.zipf", "control"),
    ("ycsb-a.zipf", "stale_state"),
    ("ycsb-a.zipf", "half_batch"),
    ("ycsb-a.zipf", "alter_answer"),
    ("paper-dht.write.b8192", "control"),
    ("paper-dht.write.b8192", "stale_state"),
    ("paper-dht.write.b8192", "half_batch"),
    ("paper-dht.write.b8192", "alter_answer"),
]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_is_caught(run_small, name, fault):
    res = run_small(name, seed=41, fault=fault, seconds=0.3, records=1500)
    assert not res["correct"], (fault, res["checks"])
    broken = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    assert broken, res["checks"]
