"""The trace reduction: busy time, idle gaps and their host spans."""
from __future__ import annotations

import pytest

from harness import devtrace


def _trace():
    ops = [("fusion.1", 10.0, 30.0), ("sort.2", 25.0, 40.0),
           ("_pack_kernel", 60.0, 80.0), ("fusion.1", 90.0, 95.0)]
    spans = [("call", 0.0, 50.0), ("block", 50.0, 85.0),
             ("check", 85.0, 100.0)]
    return devtrace.Trace({"/device:TPU:0": ops}, spans, (0.0, 100.0))


def test_busy_is_the_union_of_op_intervals():
    tr = _trace()
    assert devtrace.busy_ns(tr, "/device:TPU:0") == 30 + 20 + 5
    assert devtrace.busy_s(tr) == pytest.approx(55e-9)


def test_idle_gaps_are_named_by_host_span():
    gaps = devtrace.idle_gaps(_trace())
    # 0-10 call, 40-60 split call/block (10 each: first wins), 80-90 block/check, 95-100 check
    assert [g[0] for g in gaps] == ["call", "call", "block", "check"]
    assert sum(g[1] for g in gaps) == pytest.approx(45e-9)
    b = devtrace.breakdown(_trace())
    assert b["device_ops"][0][0] == "fusion.1"
    assert b["idle_gaps"][0][0] == "call (2 gaps)"


def test_ops_outside_the_window_are_clipped():
    tr = _trace()
    tr.device_ops["/device:TPU:0"].append(("late", 100.0, 200.0))
    assert devtrace.busy_ns(tr, "/device:TPU:0") == 55


def _recorded():
    """Three rounds of ``paper-dht.read.uniform`` traced on one v5e chip,
    reduced by :func:`devtrace.load` and kept as JSON."""
    import gzip
    import json
    from pathlib import Path

    p = Path(__file__).parent / "data" / "read_uniform_3rounds.trace.json.gz"
    with gzip.open(p) as f:
        return devtrace.Trace.from_json(json.load(f))


def test_recorded_trace_reduces_to_its_numbers():
    from harness.cell import MetricContext
    from harness.spec import load_module

    tr = _recorded()
    assert {s[0] for s in tr.host_spans} == set(devtrace.HOST_SPANS)
    busy = devtrace.busy_s(tr)
    gaps = devtrace.idle_gaps(tr)
    assert 0 < busy < tr.window_s
    assert busy + sum(g[1] for g in gaps) == pytest.approx(tr.window_s)
    assert busy == pytest.approx(0.304085656, rel=1e-6)
    b = devtrace.breakdown(tr)
    # the probe-window gathers of keys and values lead, then the unpack kernel
    assert b["device_ops"][0][0] == "fusion fusion u32[786432,20]"
    assert any(k.startswith("route_unpack_pallas") for k, _ in b["device_ops"])

    ctx = MetricContext(trace=tr, rounds=3, batch=65536, config={},
                        workload={"mix": {"read": 1.0, "write": 0.0}},
                        table={"key_words": 20, "val_words": 26,
                               "n_shards": 1},
                        peaks={"hbm_bytes_per_s": 819e9})
    got = {m: load_module("metrics", m).read(ctx) for m in (
        "device_idle_share", "device_ms_per_round", "route_kernel_roofline",
        "sort_ms_per_round", "hbm_peak_share")}
    assert got["device_idle_share"] == pytest.approx(
        100 * (1 - busy / tr.window_s))
    assert got["device_ms_per_round"] == pytest.approx(busy * 1e3 / 3)
    # a read round at one shard routes none of its ops: nothing to read
    assert got["route_kernel_roofline"] is None
    assert 0 < got["sort_ms_per_round"] < got["device_ms_per_round"]
    # the allocator's events of those rounds: buffers + reserved scratch
    assert got["hbm_peak_share"] == pytest.approx(
        100 * 2828976640 / 16909336064)


def test_op_label_parses_hlo_text():
    assert devtrace.op_label(
        "%fusion.1 = u32[786432,26]{0,1:T(8,128)S(1)} fusion(u32[4] %a), "
        "kind=kCustom") == "fusion.1 fusion u32[786432,26]"
    assert devtrace.op_label(
        "%sort.6 = (u32[8]{0}, s32[8]{0}) sort(u32[8]{0} %f, s32[8]{0} %g)"
    ) == "sort.6 sort tuple"
    assert devtrace.opcode("sort.6 sort tuple") == "sort"
    assert devtrace.op_label("jit_fn(123)") == "jit_fn(123)"


def test_memory_peak_counts_only_the_window():
    tr = _trace()
    gib = float(1 << 30)
    # (time, allocated, reserved, available); the set-up's peak at t=-5
    # lies before the window and must not count
    tr.memory = {0: [(-5.0, 9 * gib, 1 * gib, 6 * gib),
                     (20.0, 3 * gib, 1 * gib, 12 * gib),
                     (60.0, 5 * gib, 1 * gib, 10 * gib),
                     (99.0, 2 * gib, 1 * gib, 13 * gib)],
                 1: [(50.0, 4 * gib, 1 * gib, 11 * gib)]}
    assert devtrace.memory_peak(tr) == (6 * gib, 16 * gib)
    tr.memory = {0: [(-5.0, 9 * gib, 1 * gib, 6 * gib)]}
    assert devtrace.memory_peak(tr) is None
