"""Test helpers: import paths and a small cell that runs on the CPU.

Run from the root of the checkout:  ``python -m pytest bench/tests``.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

import pytest

# the tests run at small sizes on the host's CPU, never on a chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def small_cell(name: str, *, records: int = 3000, buckets: int = 1 << 12,
               batch: int = 512, pool_rounds: int = 3):
    """The cell ``bench/workloads/<name>.json`` at a size the CPU runs in
    seconds: same entry, mix, key distribution and row widths, fewer
    records and buckets and a smaller batch.  Built from the files, so a
    workload file is tested whether or not ``BENCHMARK.json`` lists it."""
    from harness.spec import Cell

    workload = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    config = json.loads(
        (BENCH / "configs" / f"{workload['config']}.json").read_text())
    config["records"] = records
    config["table"]["buckets_per_shard"] = buckets
    workload["batch"] = batch
    workload["pool_rounds"] = pool_rounds
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return Cell(name=name, chips=1, config=config, workload=workload,
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])


@pytest.fixture
def run_small():
    from harness import cell as cell_mod

    def go(name: str, seed: int = 7, fault: str | None = None,
           seconds: float = 0.5, trace: bool = False, **size):
        return cell_mod.run(small_cell(name, **size), seed, seconds, trace,
                            t_start=time.perf_counter(), fault=fault,
                            peaks={"hbm_bytes_per_s": 819e9})
    return go
