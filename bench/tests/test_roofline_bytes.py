"""The route kernels' byte count is the ops' payload, not padded shapes."""
from __future__ import annotations

from harness.spec import load_module

m = load_module("metrics", "route_kernel_roofline")
PAPER = {"key_words": 20, "val_words": 26}
YCSB = {"key_words": 6, "val_words": 250}


def test_payload_words():
    assert m.payload_words(PAPER, {"read": 1.0, "write": 0.0}) == (20, 27)
    assert m.payload_words(PAPER, {"read": 0.0, "write": 1.0}) == (46, 1)
    # mixed: each op carries a tag; a read sends key+tag and gets value+found,
    # a write sends key+value+tag and gets its code
    s, r = m.payload_words(YCSB, {"read": 0.5, "write": 0.5})
    assert s == 0.5 * 7 + 0.5 * 257 and r == 0.5 * 251 + 0.5 * 1


def test_round_bytes_read_and_written_once():
    assert m.round_bytes(PAPER, {"read": 0.0, "write": 1.0}, 65536) == \
        2 * 4 * 65536 * (46 + 1)
    mixed = {"read": 0.5, "write": 0.5}
    assert m.round_bytes(dict(YCSB, n_shards=1), mixed, 8) == \
        2 * 4 * 8 * (0.5 * 7 + 0.5 * 257 + 0.5 * 251 + 0.5 * 1)


def test_elided_reads_are_not_counted():
    """A read-only round routes only the rows another shard owns: none at
    one shard, an expected three quarters at four."""
    reads = {"read": 1.0, "write": 0.0}
    assert m.round_bytes(dict(PAPER, n_shards=1), reads, 65536) == 0
    assert m.round_bytes(dict(PAPER, n_shards=4), reads, 65536) == \
        2 * 4 * 65536 * 0.75 * (20 + 27)


def test_share_from_a_trace():
    from harness import devtrace
    from harness.cell import MetricContext

    # two rounds; each kernel call 1 ms; 8 MB/round at 1 GB/s = 8 ms/round
    pack = "route_pack_pallas.1 custom-call u32[8,1,3]"
    unpack = "route_unpack_pallas.1 custom-call u32[8,1,1]"
    ev = [(pack, 0.0, 1e6), (unpack, 2e6, 3e6), (pack, 4e6, 5e6),
          (unpack, 6e6, 7e6), ("sort.3 sort tuple", 1e6, 2e6),
          ("fusion.2 fusion u32[8]", 7e6, 8e6)]
    tr = devtrace.Trace({"/device:TPU:0": ev}, [("call", 0.0, 8e6)],
                        (0.0, 8e6))
    table = {"key_words": 1, "val_words": 1}
    ctx = MetricContext(trace=tr, rounds=2, batch=125_000, config={},
                        workload={"mix": {"read": 0.0, "write": 1.0}},
                        table=table, peaks={"hbm_bytes_per_s": 1e9})
    # payload: send 2 words, reply 1 word -> 2*4*125000*3 = 3 MB per round
    assert abs(m.read(ctx) - 100 * (2 * 3e6 / 1e9) / 4e-3) < 1e-9
    assert load_module("metrics", "sort_ms_per_round").read(ctx) == 0.5
