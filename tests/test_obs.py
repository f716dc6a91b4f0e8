"""Telemetry substrate tests (DESIGN.md §10): histogram-merge algebra,
wire-stats merging, registry/stats bit-for-bit parity on every backend,
trace ring bounds and export formats, and the trace-cache-proof round
counter that replaced the PR 3 global."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import obs
from repro.core import DHTConfig, dht_create, dht_read, dht_write
from repro.obs.metrics import (FRACTION_EDGES, Histogram, MetricRegistry,
                               histogram_quantile, merge_snapshots,
                               merge_wire_stats, set_registry)
from repro.obs.trace import RoundEvent, TraceRecorder


@pytest.fixture()
def fresh_registry():
    """Swap in an empty registry for the test, restore the global one."""
    reg = MetricRegistry()
    prev = set_registry(reg)
    try:
        yield reg
    finally:
        set_registry(prev)


def _hist_from_seed(seed: int, edges=FRACTION_EDGES) -> Histogram:
    h = Histogram(edges)
    rng = np.random.default_rng(seed)
    for v in rng.uniform(-0.2, 1.4, size=rng.integers(0, 40)):
        h.observe(float(v))
    return h


# ---------------------------------------------------------------- merge
@settings(max_examples=20, deadline=None)
@given(st.integers(0, 97), st.integers(0, 97), st.integers(0, 97))
def test_histogram_merge_associative_commutative(sa, sb, sc):
    """Fixed edges make merge elementwise count addition: any merge
    order of per-shard histograms must give identical dicts."""
    a, b, c = (_hist_from_seed(s) for s in (sa, sb, sc))
    ab = a.merge(b)
    assert ab.to_dict() == b.merge(a).to_dict()
    assert ab.merge(c).to_dict() == a.merge(b.merge(c)).to_dict()
    # identity: merging an empty histogram changes nothing
    assert a.merge(Histogram(a.edges)).to_dict() == a.to_dict()
    # merge is pure — operands untouched
    assert a.count + b.count == ab.count


def test_histogram_merge_rejects_mismatched_edges():
    with pytest.raises(ValueError):
        Histogram((1.0, 2.0)).merge(Histogram((1.0, 3.0)))


def test_histogram_quantile_and_roundtrip():
    h = Histogram((1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 5.0, 50.0):
        h.observe(v)
    assert histogram_quantile(h, 0.5) == 10.0
    assert histogram_quantile(h, 1.0) == 100.0
    rt = Histogram.from_dict(json.loads(json.dumps(h.to_dict())))
    assert rt.to_dict() == h.to_dict()


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 31), st.integers(0, 31), st.booleans())
def test_snapshot_merge_matches_pairwise(sa, sb, swap):
    """merge_snapshots == fold of merge_snapshot, in any order, and the
    merged counters/histograms are the elementwise sums."""
    ra, rb = MetricRegistry(), MetricRegistry()
    rng = np.random.default_rng(sa * 64 + sb)
    for reg, seed in ((ra, sa), (rb, sb)):
        for _ in range(int(rng.integers(1, 8))):
            reg.inc("c.x", int(rng.integers(0, 9)))
        reg.observe("h.y", float(seed % 5) / 5, edges=FRACTION_EDGES)
        reg.set_gauge("g.z", float(seed))
    order = [rb, ra] if swap else [ra, rb]
    merged = merge_snapshots([r.snapshot() for r in order])
    assert merged["counters"]["c.x"] == (ra.counter("c.x")
                                         + rb.counter("c.x"))
    assert merged["histograms"]["h.y"]["count"] == 2
    # gauges are point-in-time: last write wins
    assert merged["gauges"]["g.z"] == float((sa if swap else sb))
    # deterministic serialization: equal histories -> equal JSON
    again = merge_snapshots([r.snapshot() for r in order])
    assert json.dumps(merged, sort_keys=True) == json.dumps(
        again, sort_keys=True)


# ------------------------------------------------------ merge_wire_stats
def test_merge_wire_stats_single_passthrough_bit_for_bit():
    s = {"wire_words": jnp.int32(12345), "fill_frac": jnp.float32(0.321),
         "hits": jnp.int32(7)}
    out = merge_wire_stats(s)
    assert out["wire_words"] is s["wire_words"]
    assert out["fill_frac"] is s["fill_frac"]


@settings(max_examples=16, deadline=None)
@given(st.integers(0, 200000), st.integers(0, 200000),
       st.sampled_from([0.0, 0.125, 0.5, 0.93, 1.0]))
def test_merge_wire_stats_weighted_fill_regression(w1, w2, f1):
    """The shared helper must reproduce the hand-rolled dual-epoch merge
    it replaced (PR 3 ``_dht_read_dual_seq``): words add, fill combines
    weighted by wire words, all in float32 — bit for bit."""
    f2 = 1.0 - f1
    a = {"wire_words": jnp.int32(w1), "fill_frac": jnp.float32(f1)}
    b = {"wire_words": jnp.int32(w2), "fill_frac": jnp.float32(f2)}
    out = merge_wire_stats(a, b)
    ww1, ww2 = np.float32(w1), np.float32(w2)
    expect_fill = ((np.float32(f1) * ww1 + np.float32(f2) * ww2)
                   / np.maximum(ww1 + ww2, np.float32(1.0)))
    assert int(out["wire_words"]) == w1 + w2
    assert np.asarray(out["fill_frac"], np.float32) == expect_fill
    # associativity across three rounds (weighted mean of weighted mean)
    c = {"wire_words": jnp.int32(64), "fill_frac": jnp.float32(0.25)}
    abc = merge_wire_stats(a, b, c)
    two_step = merge_wire_stats(merge_wire_stats(a, b), c)
    assert int(abc["wire_words"]) == int(two_step["wire_words"])
    assert float(abc["fill_frac"]) == pytest.approx(
        float(two_step["fill_frac"]), rel=1e-6)


# ------------------------------------------------- registry/stats parity
def _small_table():
    cfg = DHTConfig(n_shards=4, buckets_per_shard=256)
    st_ = dht_create(cfg)
    rng = np.random.default_rng(2)
    keys = jnp.asarray(rng.integers(0, 2**31, size=(128, 20)), jnp.uint32)
    vals = jnp.asarray(rng.integers(0, 2**31, size=(128, 26)), jnp.uint32)
    return st_, keys, vals


def test_eager_registry_matches_stats_bit_for_bit(fresh_registry):
    """Every eager round flushes its stat lanes into the registry; the
    counters must equal the sums of the per-call stats the caller saw."""
    st_, keys, vals = _small_table()
    st_, ws = dht_write(st_, keys, vals)
    st_, _, found, rs = dht_read(st_, keys)
    assert bool(found.all())
    snap = fresh_registry.snapshot()
    c = snap["counters"]
    assert c["engine.rounds"] == 2
    assert c["routing.dispatches"] == 2
    assert c["engine.wire_words"] == int(ws["wire_words"]) + int(
        rs["wire_words"])
    assert c["engine.dropped"] == int(ws["dropped"])
    assert c["engine.ops.write"] == 128 and c["engine.ops.read"] == 128
    # both wire legs are accounted and they partition the total
    assert (c["engine.wire_send_words"] + c["engine.wire_reply_words"]
            == c["engine.wire_words"])
    h = snap["histograms"]["engine.fill_frac"]
    assert h["count"] == 2
    assert snap["histograms"]["engine.round_latency_us"]["count"] == 2


def test_jit_host_flush_matches_stats_bit_for_bit(fresh_registry):
    """Under jit the engine stays silent (no host flush inside traced
    code); the caller flushes the returned stat lanes — the registry
    must then match those lanes exactly, like the ShardedDHT wrappers."""
    st_, keys, vals = _small_table()
    st_, _ = dht_write(st_, keys, vals)
    rounds0 = fresh_registry.counter("engine.rounds")
    wire0 = fresh_registry.counter("engine.wire_words")

    jitted = jax.jit(lambda s, k: dht_read(s, k))
    st2, out, found, rs = jitted(st_, keys)
    # traced internals must not have advanced the executed-round counter
    assert fresh_registry.counter("engine.rounds") == rounds0
    obs.record_round("jit.read", rs, ops={"read": int(keys.shape[0])})
    assert fresh_registry.counter("engine.rounds") == rounds0 + 1
    assert (fresh_registry.counter("engine.wire_words") - wire0
            == int(rs["wire_words"]))
    assert fresh_registry.counter("dht.hits") == int(rs["hits"])


def test_eager_rounds_survive_repeat_calls(fresh_registry):
    """The PR 3 global froze once jit's trace cache warmed; the
    registry counter advances on every *executed* round."""
    st_, keys, vals = _small_table()
    st_, _ = dht_write(st_, keys, vals)
    for _ in range(3):
        st_, _, _, _ = dht_read(st_, keys)
    assert fresh_registry.counter("engine.rounds") == 4
    assert fresh_registry.counter("routing.dispatches") == 4


def test_count_traced_rounds_defeats_trace_cache():
    st_, keys, vals = _small_table()
    st_, _ = dht_write(st_, keys, vals)

    def read_fn(s, k):
        return dht_read(s, k)

    assert obs.count_traced_rounds(read_fn, st_, keys) == 1
    # a second count is identical — the fresh-lambda wrapper re-traces
    assert obs.count_traced_rounds(read_fn, st_, keys) == 1


def test_disabled_is_a_no_op(fresh_registry):
    st_, keys, vals = _small_table()
    with obs.metrics.disabled():
        st_, ws = dht_write(st_, keys, vals)
    assert int(ws["inserted"]) == 128          # results unaffected
    assert fresh_registry.snapshot() == {
        "counters": {}, "gauges": {}, "histograms": {}}


# ------------------------------------------------------------ trace ring
def _dummy_event(i: int) -> RoundEvent:
    return RoundEvent(source=f"e{i}", ts=float(i), dur=0.5,
                      spans={"bin": (float(i), 0.1),
                             "dispatch": (float(i) + 0.1, 0.4)},
                      ops={"read": 8}, stats={"wire_words": 99 + i})


def test_trace_ring_is_bounded():
    tr = TraceRecorder(maxlen=4)
    for i in range(10):
        tr.record(_dummy_event(i))
    evs = tr.events()
    assert len(evs) == 4 and tr.n_recorded == 10
    assert [e.source for e in evs] == ["e6", "e7", "e8", "e9"]


def test_trace_exports_jsonl_and_chrome(tmp_path):
    tr = TraceRecorder(maxlen=16)
    for i in range(3):
        tr.record(_dummy_event(i))
    jl = tmp_path / "t.jsonl"
    assert tr.to_jsonl(str(jl)) == 3
    lines = [json.loads(ln) for ln in jl.read_text().splitlines()]
    assert [ln["source"] for ln in lines] == ["e0", "e1", "e2"]
    assert lines[0]["stats"]["wire_words"] == 99
    assert set(lines[0]["spans"]) == {"bin", "dispatch"}

    ct = tmp_path / "t_chrome.json"
    # 3 rounds x (1 round event + 2 phase spans)
    assert tr.to_chrome_trace(str(ct)) == 9
    doc = json.loads(ct.read_text())
    assert doc["displayTimeUnit"] == "ms"
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X"
        assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(ev)
    rounds = [e for e in doc["traceEvents"] if e["cat"] == "round"]
    assert [r["name"] for r in rounds] == ["e0", "e1", "e2"]
    assert rounds[0]["args"]["ops"] == {"read": 8}


def test_record_round_flushes_lanes_and_spans(fresh_registry):
    tracer = obs.get_tracer()
    n0 = tracer.n_recorded
    stats = {"wire_words": jnp.int32(640), "fill_frac": jnp.float32(0.25),
             "dropped": jnp.int32(3), "dispatch_rounds": jnp.int32(2),
             "wmarks": jnp.zeros((4,), jnp.uint32)}   # non-scalar: skipped
    obs.record_round("unit.round", stats, ops={"read": 10, "write": 6},
                     t_start=0.0, phase_marks=[("bin", 0.0),
                                               ("apply", 1.0)])
    assert fresh_registry.counter("engine.rounds") == 2   # dispatch_rounds
    assert fresh_registry.counter("engine.wire_words") == 640
    assert fresh_registry.counter("engine.dropped") == 3
    assert fresh_registry.counter("engine.ops.read") == 10
    ev = tracer.events()[-1]
    assert tracer.n_recorded == n0 + 1
    assert ev.stats["wire_words"] == 640 and "wmarks" not in ev.stats
    assert ev.spans["bin"] == (0.0, 1.0)        # ends at next mark
    assert ev.spans["apply"][0] == 1.0          # last span ends at record


def test_record_round_dur_override(fresh_registry):
    # external timing (a bench's median-of-k) lands as the event's dur
    # and in the latency histogram, even with no t_start
    obs.record_round("unit.timed", {"wire_words": jnp.int32(8)},
                     ops={"read": 4}, dur=2e-3)
    ev = obs.get_tracer().events()[-1]
    assert ev.dur == 2e-3
    lat = fresh_registry.snapshot()["histograms"]["engine.round_latency_us"]
    assert lat["count"] >= 1


def test_eager_round_records_issue_hidden_commit_marks(fresh_registry):
    """An eager round's event carries the issue/commit split's host marks
    (DESIGN.md §12); its device phases are scopes in the profiler's trace,
    not host marks."""
    cfg = DHTConfig(n_shards=2, buckets_per_shard=16, key_words=4,
                    val_words=3)
    state = dht_create(cfg)
    keys = jnp.arange(32, dtype=jnp.uint32).reshape(8, 4)
    dht_write(state, keys, jnp.ones((8, 3), jnp.uint32))
    ev = obs.get_tracer().events()[-1]
    assert list(ev.spans) == ["issue", "hidden", "commit"]
    assert ev.spans["issue"][0] == ev.ts


# -- one batched device-to-host fetch per round ---------------------------

class _VectorLane:
    """A vector lane that must never be transferred: reading it fails."""

    shape = (4,)
    ndim = 1
    dtype = np.dtype(np.int32)

    def __array__(self, *a, **k):
        raise AssertionError("a vector lane was fetched")


@pytest.fixture()
def device_gets(monkeypatch):
    """Every ``jax.device_get`` call, as the ``ndim`` of each leaf."""
    calls = []
    real = jax.device_get

    def counted(x):
        calls.append([np.ndim(leaf) for leaf in jax.tree.leaves(x)])
        return real(x)

    monkeypatch.setattr(jax, "device_get", counted)
    return calls


def _sharded_table():
    from repro.core.distributed import ShardedDHT, make_mesh_1d

    d = ShardedDHT.create(make_mesh_1d(1), DHTConfig(
        n_shards=1, buckets_per_shard=256, key_words=4, val_words=3,
        capacity=64))
    rng = np.random.default_rng(3)
    keys = jnp.asarray(rng.integers(0, 2**31, size=(64, 4)), jnp.uint32)
    vals = jnp.asarray(rng.integers(0, 2**31, size=(64, 3)), jnp.uint32)
    return d, keys, vals


def _one_round(d, call, keys, vals):
    if call == "write":
        return d.write(keys, vals)
    if call == "read":
        return d.read(keys)[2]
    if call == "read_many":
        return d.read_many(keys.reshape(16, 4, 4))[2]
    if call == "write_async":
        return d.write_commit(d.write_async(keys, vals))
    return d.read_commit(d.read_async(keys))[2]


@pytest.mark.parametrize("call", ["write", "read", "read_many",
                                  "write_async", "read_async"])
def test_sharded_round_makes_one_scalar_fetch(fresh_registry, device_gets,
                                              call):
    """A ``ShardedDHT`` round moves its stat lanes to the host in exactly
    one ``jax.device_get``, and only 0-d lanes ride it; the caller's
    ``code``/``dropped`` lanes stay device arrays."""
    d, keys, vals = _sharded_table()
    _one_round(d, call, keys, vals)            # compile outside the count
    device_gets.clear()
    stats = _one_round(d, call, keys, vals)
    assert len(device_gets) == 1, device_gets
    assert device_gets[0] and set(device_gets[0]) == {0}, device_gets
    assert isinstance(stats["dropped"], jax.Array)
    if call == "write":
        assert isinstance(stats["code"], jax.Array)
        assert stats["code"].shape == (64,)
        assert int(stats["write_retries"]) == 0


def test_record_round_never_fetches_a_vector_lane(fresh_registry,
                                                  device_gets):
    """The lane's shape is read before any transfer: a vector lane whose
    ``__array__`` raises is skipped, the scalar lanes still flush in one
    fetch, and Python numbers pass through unchanged."""
    stats = {"wire_words": jnp.int32(96), "dropped": jnp.int32(2),
             "fill_frac": jnp.float32(0.5), "bin_counts": _VectorLane(),
             "code": jnp.zeros((8,), jnp.int32), "issue_us": 12.5,
             "forwarded": 3}
    obs.record_round("unit.vector", stats, ops={"write": 8})
    assert len(device_gets) == 1 and device_gets[0] == [0, 0, 0]
    assert fresh_registry.counter("engine.wire_words") == 96
    assert fresh_registry.counter("engine.dropped") == 2
    assert fresh_registry.counter("engine.host_fetches") == 1
    ev = obs.get_tracer().events()[-1]
    assert ev.stats == {"wire_words": 96, "dropped": 2, "fill_frac": 0.5,
                        "issue_us": 12.5, "forwarded": 3}


def test_record_round_of_host_numbers_makes_no_fetch(fresh_registry,
                                                     device_gets):
    obs.record_round("unit.host", {"wire_words": np.int32(5), "hits": 2})
    assert device_gets == []
    assert fresh_registry.counter("engine.host_fetches") == 0
    assert fresh_registry.counter("engine.rounds") == 1
    assert fresh_registry.counter("engine.wire_words") == 5


def test_host_fetches_equal_rounds_on_the_served_path(fresh_registry):
    d, keys, vals = _sharded_table()
    for _ in range(3):
        d.write(keys, vals)
        d.read(keys)
    d.read_many(keys.reshape(16, 4, 4))
    c = fresh_registry.snapshot()["counters"]
    assert c["engine.rounds"] == 7, c
    assert c["engine.host_fetches"] == c["engine.rounds"], c
