"""The plain reference's semantics, alone and against the program on a
small CPU table."""
from __future__ import annotations

import numpy as np
import pytest

from harness.spec import load_module

ref_mod = load_module("references", "cache_semantics")
W_INSERT, W_UPDATE, W_EVICT = ref_mod.W_INSERT, ref_mod.W_UPDATE, \
    ref_mod.W_EVICT


def _ref(n=10):
    return ref_mod.Reference(n)


def _ok(n):
    return np.ones(n, bool)


def test_highest_batch_index_wins_and_reads_see_the_snapshot():
    r = _ref()
    r.write(np.array([1, 2]), np.array([10, 20]), np.array([W_INSERT] * 2))
    # one round: read 1, then two writes of 1; the read sees the old value
    r.round(np.array([1, 1, 1]), np.array([False, True, True]),
            np.array([0, 11, 12]), found=np.array([True, False, False]),
            code=np.array([0, W_UPDATE, W_UPDATE]),
            stamp=np.array([10, 0, 0]), whole=_ok(3))
    assert r.stamp[1] == 12
    r.read(np.array([1]), np.array([True]), np.array([12]), _ok(1))
    assert all(c["value"] == 0 for c in r.checks().values())
    r.read(np.array([1]), np.array([True]), np.array([11]), _ok(1))
    assert r.checks()["wrong_values"]["value"] == 1
    # the right stamp on a value whose other words are not its own
    r.read(np.array([1]), np.array([True]), np.array([12]), ~_ok(1))
    assert r.checks()["wrong_values"]["value"] == 2


def test_losses_within_reported_evictions():
    r = _ref()
    r.write(np.array([1, 2, 3]), np.array([1, 2, 3]),
            np.array([W_INSERT, W_INSERT, W_EVICT]))
    r.read(np.array([1, 2, 3]), np.array([True, False, True]),
           np.array([1, 0, 3]), _ok(3))
    assert r.checks()["unexplained_losses"]["value"] == 0
    r.read(np.array([1]), np.array([False]))
    assert r.checks()["unexplained_losses"]["value"] == 1


def test_codes():
    r = _ref()
    r.write(np.array([1]), np.array([1]), np.array([W_UPDATE]))  # not held
    assert r.checks()["wrong_codes"]["value"] == 1
    r.write(np.array([2]), np.array([1]), np.array([0]))         # dropped
    assert r.checks()["dropped_codes"]["value"] == 1
    r.read(np.array([5]), np.array([True]))                      # never written
    assert r.checks()["found_absent"]["value"] == 1


CELLS = ["paper-dht.read.uniform", "ycsb-a.zipf", "paper-dht.write.b8192"]


@pytest.mark.parametrize("name", CELLS)
def test_program_agrees_with_the_reference(run_small, name):
    res = run_small(name, seed=2**31 + 11)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"ops_per_s", "round_p95_ms", "setup_s"}
