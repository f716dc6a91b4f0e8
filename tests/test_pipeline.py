"""Issue/commit pipelined engine (DESIGN.md §12): the host-side hazard
machinery, split-half parity with the synchronous engine, the
read-after-promised-write hazard, random issue/commit interleavings
against the flat-dict oracle, and the sharded backend's closure-cache
keying.  Multi-device cases run in subprocesses (conftest.py note)."""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import DHTConfig, dht_create
from repro.core.async_sim import IssueCommitOracle
from repro.core.dht import (
    dht_read_async,
    dht_read_commit,
    dht_write_async,
    dht_write_commit,
)
from repro.core.layout import MODES
from repro.core.op_engine import (
    OP_READ,
    OP_WRITE,
    dht_commit,
    dht_execute,
    dht_issue,
    mixed_ops,
    read_ops,
    write_ops,
)
from repro.core.pipeline import PendingWrites, RoundQueue
from repro.core.surrogate import (
    SurrogateConfig,
    lookup_or_compute,
    lookup_or_compute_pipelined,
    surrogate_create,
)

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
KW, VW = 20, 26


def _kv(n, seed=0):
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(rng.integers(0, 2**31, size=(n, KW)), jnp.uint32)
    vals = jnp.asarray(rng.integers(0, 2**31, size=(n, VW)), jnp.uint32)
    return keys, vals


@pytest.fixture(params=MODES)
def mode(request):
    return request.param


# -- PendingWrites: the host-side store buffer -------------------------


def test_pending_writes_store_buffer_protocol():
    pw = PendingWrites(VW)
    keys = np.arange(4 * KW, dtype=np.uint32).reshape(4, KW)
    vals = np.arange(4 * VW, dtype=np.uint32).reshape(4, VW)
    promised = np.array([True, True, False, True])
    pw.promise(keys, promised)
    assert len(pw) == 3
    conf = pw.conflicts(keys)
    assert (conf == promised).all(), "only promised keys conflict"
    # publish two of the three, resolve them
    pub = np.array([True, False, False, True])
    pw.publish(keys, vals, pub)
    out = pw.resolve(keys, pub)
    assert (out[pub] == vals[pub]).all()
    assert (out[~pub] == 0).all(), "unmasked rows return zeros"
    # retire drops the keys: no conflicts afterwards
    pw.retire(keys, promised)
    assert len(pw) == 0 and not pw.conflicts(keys).any()


def test_pending_writes_unpublished_resolve_raises():
    """A conflicted row committed before its producer published is a
    driver ordering bug — the table must fail loudly, not serve zeros."""
    pw = PendingWrites(VW)
    keys = np.ones((2, KW), np.uint32)
    pw.promise(keys, np.array([True, False]))
    with pytest.raises(RuntimeError, match="never .*published|published"):
        pw.resolve(keys, np.array([True, False]))


def test_pending_writes_conflicts_respect_valid_mask():
    pw = PendingWrites(VW)
    keys = np.arange(2 * KW, dtype=np.uint32).reshape(2, KW)
    pw.promise(keys)
    conf = pw.conflicts(keys, valid=np.array([True, False]))
    assert conf.tolist() == [True, False], "invalid rows never conflict"


def test_round_queue_fifo_depth_semantics():
    log = []
    q = RoundQueue(2, commit=lambda r: (log.append(r), r)[1])
    assert q.push("a") is None, "depth 2: first push leaves a free slot"
    assert q.push("b") == "a", "second push commits the OLDEST round"
    assert q.push("c") == "b"
    assert q.drain() == ["c"] and log == ["a", "b", "c"], "FIFO order"
    q1 = RoundQueue(1, commit=lambda r: r)
    assert q1.push("x") == "x", "depth 1 commits immediately (synchronous)"
    with pytest.raises(ValueError):
        RoundQueue(0)


# -- split halves vs the one-call engine -------------------------------


def test_issue_commit_matches_execute_all_mixes(mode):
    """dht_issue + dht_commit must be bit-for-bit dht_execute for every
    op mix — the split is a scheduling change, not a semantic one."""
    cfg = DHTConfig(n_shards=4, buckets_per_shard=512, mode=mode)
    keys, vals = _kv(96, seed=3)
    rng = np.random.default_rng(4)
    op = jnp.asarray(
        np.where(rng.random(96) < 0.5, OP_READ, OP_WRITE), jnp.int32)
    batches = [
        (("write",), write_ops(keys, vals)),
        (("read",), read_ops(keys)),
        (("read", "write"), mixed_ops(op, keys, vals + 7)),
        (("read",), read_ops(keys)),
    ]
    st_a = st_b = dht_create(cfg)
    for kinds, ops in batches:
        st_a, _, va, fa, ca, ea = dht_execute(st_a, ops, kinds=kinds)
        st_b, _, vb, fb, cb, eb = dht_commit(
            dht_issue(st_b, ops, kinds=kinds))
        assert bool((va == vb).all()) and bool((fa == fb).all())
        assert bool((ca == cb).all())
        for k in ("hits", "misses", "dropped"):
            if k in ea:
                assert int(ea[k]) == int(eb[k]), (kinds, k)
    assert bool((st_a.keys == st_b.keys).all())
    assert bool((st_a.vals == st_b.vals).all())
    assert bool((st_a.meta == st_b.meta).all())


def test_write_effects_land_at_issue_time():
    """A read issued against an uncommitted write's output state must
    observe the write, and commit order must not matter: effects chain
    through dataflow at ISSUE time, commit only materializes."""
    cfg = DHTConfig(n_shards=4, buckets_per_shard=512)
    keys, vals = _kv(32, seed=5)
    w = dht_write_async(dht_create(cfg), keys, vals)
    r = dht_read_async(w.state, keys)
    # commit the READ first — out of issue order
    _, out, found, _ = dht_read_commit(r)
    assert bool(found.all()) and bool((out == vals).all())
    dht_write_commit(w)


def test_read_snapshot_semantics():
    """The dual rule: a read issued BEFORE a write was issued snapshots
    the pre-write table, no matter how late its commit runs."""
    cfg = DHTConfig(n_shards=4, buckets_per_shard=512)
    keys, vals = _kv(32, seed=6)
    st0 = dht_create(cfg)
    r = dht_read_async(st0, keys)          # issued against the empty table
    w = dht_write_async(st0, keys, vals)
    dht_write_commit(w)                    # write completes first
    _, _, found, _ = dht_read_commit(r)
    assert not bool(found.any()), "late commit must not see a later write"


def test_read_after_promised_write_forwards():
    """The one true hazard: a read issued while a write is PROMISED but
    not yet issued (values still computing).  Conflicted rows are masked
    out of the probe and served by store-to-load forwarding at commit —
    and committing before the producer published fails loudly."""
    cfg = DHTConfig(n_shards=4, buckets_per_shard=512)
    keys, vals = _kv(48, seed=7)
    keys_np = np.asarray(keys)
    st0 = dht_create(cfg)
    pending = PendingWrites(VW)
    promised = np.zeros(48, bool)
    promised[::3] = True
    pending.promise(keys_np, promised)

    early = dht_read_async(st0, keys, pending=pending)
    assert early.conflict is not None and (early.conflict == promised).all()
    with pytest.raises(RuntimeError, match="published"):
        dht_read_commit(early)

    rnd = dht_read_async(st0, keys, pending=pending)
    pending.publish(keys_np, np.asarray(vals), promised)
    _, out, found, stats = dht_read_commit(rnd)
    assert bool(np.asarray(found)[promised].all()), "forwarded rows hit"
    assert (np.asarray(out)[promised] == np.asarray(vals)[promised]).all()
    assert not bool(np.asarray(found)[~promised].any())
    assert int(stats["hits"]) == int(promised.sum())


# -- random interleavings vs the flat-dict oracle ----------------------


@settings(deadline=None, max_examples=8)
@given(st.integers(min_value=0, max_value=10_000))
def test_interleavings_match_oracle(seed):
    """Drive dht_issue/dht_commit through a random schedule — reads and
    writes issued in random mixes over a small key universe, commits
    delayed and reordered at random — and demand every read materialize
    exactly what IssueCommitOracle (issue-time effects, issue-time
    snapshots, commit-order-independent) says it should."""
    rng = np.random.default_rng(seed)
    cfg = DHTConfig(n_shards=4, buckets_per_shard=512)
    state = dht_create(cfg)
    oracle = IssueCommitOracle()

    universe = np.asarray(
        np.random.default_rng(0).integers(0, 2**31, size=(12, KW)), np.uint32)
    in_flight = []  # (engine InFlightRound, oracle handle, kind)

    def commit_one(idx):
        rnd, h, kind = in_flight.pop(idx)
        if kind == "read":
            _, out, found, _ = dht_read_commit(rnd)
            ovals, ofound = oracle.commit(h)
            assert np.asarray(found).tolist() == ofound
            out_np = np.asarray(out)
            for i, v in enumerate(ovals):
                if v is not None:
                    assert (out_np[i] == v).all()
        else:
            dht_write_commit(rnd)
            oracle.commit(h)

    for _ in range(24):
        ids = rng.integers(0, len(universe), size=8)
        keys_np = universe[ids]
        keys = jnp.asarray(keys_np)
        if rng.random() < 0.45:
            vals_np = rng.integers(0, 2**31, size=(8, VW)).astype(np.uint32)
            rnd = dht_write_async(state, keys, jnp.asarray(vals_np))
            state = rnd.state
            in_flight.append((rnd, oracle.issue_write(keys_np, vals_np),
                              "write"))
        else:
            rnd = dht_read_async(state, keys)
            state = rnd.state
            in_flight.append((rnd, oracle.issue_read(keys_np), "read"))
        while in_flight and rng.random() < 0.5:
            commit_one(int(rng.integers(0, len(in_flight))))
    while in_flight:
        commit_one(int(rng.integers(0, len(in_flight))))


# -- pipelined surrogate driver vs the sequential one ------------------


def _surrogate_batches(n_batches=5, n=48, n_inputs=10, seed=11):
    """Consecutive batches share rows, so batch N+1 re-reads keys batch N
    is still computing — the store-to-load forwarding path MUST fire."""
    rng = np.random.default_rng(seed)
    batches = []
    prev = None
    for _ in range(n_batches):
        x = np.round(rng.uniform(0.1, 10.0, size=(n, n_inputs)), 2)
        if prev is not None:
            take = rng.integers(0, n, size=n // 3)
            x[: n // 3] = prev[take]
        prev = x
        batches.append(jnp.asarray(x, jnp.float32))
    return batches


def test_surrogate_pipelined_matches_sequential(mode):
    cfg = SurrogateConfig(
        n_inputs=10, n_outputs=13, sig_digits=4,
        dht=DHTConfig(n_shards=4, buckets_per_shard=512, mode=mode))

    def compute(x):
        return jnp.tanh(x[:, :13] if x.shape[1] >= 13 else
                        jnp.pad(x, ((0, 0), (0, 13 - x.shape[1])))) * 3.0

    batches = _surrogate_batches()
    st_seq = surrogate_create(cfg)
    outs_seq, found_seq, tot = [], [], {"hits": 0, "misses": 0, "stored": 0}
    for x in batches:
        st_seq, out, found, s = lookup_or_compute(cfg, st_seq, x, compute)
        outs_seq.append(out)
        found_seq.append(found)
        for k in tot:
            tot[k] += int(s[k])

    st_pipe, outs_p, found_p, sp = lookup_or_compute_pipelined(
        cfg, surrogate_create(cfg), batches, compute, depth=2)
    assert int(sp["forwarded"]) > 0, "crafted overlap must forward"
    for k in tot:
        assert int(sp[k]) == tot[k], k
    for a, b in zip(outs_seq, outs_p):
        assert bool((a == b).all()), "bit-for-bit output parity"
    for a, b in zip(found_seq, found_p):
        assert bool((a == b).all())
    assert bool((st_seq.keys == st_pipe.keys).all())
    assert bool((st_seq.vals == st_pipe.vals).all())


def test_surrogate_pipelined_depth1_is_sequential():
    cfg = SurrogateConfig(dht=DHTConfig(n_shards=4, buckets_per_shard=512))

    def compute(x):
        return jnp.tanh(jnp.pad(x, ((0, 0), (0, 3)))) * 2.0

    batches = _surrogate_batches(n_batches=3)
    _, outs1, _, s1 = lookup_or_compute_pipelined(
        cfg, surrogate_create(cfg), batches, compute, depth=1)
    _, outs2, _, s2 = lookup_or_compute_pipelined(
        cfg, surrogate_create(cfg), batches, compute, depth=2)
    assert int(s1["forwarded"]) == 0, "depth 1 falls back to synchronous"
    assert int(s1["hits"]) == int(s2["hits"])
    for a, b in zip(outs1, outs2):
        assert bool((a == b).all())


# -- sharded backend: subprocess tests ---------------------------------


def _run(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_sharded_async_closures_never_alias_sync():
    """Regression for the keyed-closure cache: the async wrappers' cache
    key must include the ("async", pipeline_depth) tag, so flipping the
    depth (or mixing sync and pipelined calls) can never serve a stale
    closure — and results stay identical across the flip."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import DHTConfig
        from repro.core.distributed import ShardedDHT, make_mesh_1d

        mesh = make_mesh_1d(4, "d")
        d = ShardedDHT.create(mesh, DHTConfig(
            n_shards=4, buckets_per_shard=512))
        rng = np.random.default_rng(0)
        keys = jnp.asarray(rng.integers(0, 2**31, size=(64, 20)), jnp.uint32)
        vals = jnp.asarray(rng.integers(0, 2**31, size=(64, 26)), jnp.uint32)
        d.write(keys, vals)
        out_s, found_s, _ = d.read(keys)
        n0 = len(d._fn_cache)
        out_a, found_a, _ = d.read_commit(d.read_async(keys))
        assert len(d._fn_cache) == n0 + 1, "async read got its own slot"
        d.pipeline_depth = 3
        out_b, found_b, _ = d.read_commit(d.read_async(keys))
        assert len(d._fn_cache) == n0 + 2, "depth flip got its own slot"
        for out, found in ((out_a, found_a), (out_b, found_b)):
            assert bool(found.all()) and bool((out == out_s).all())
        st = d.write_commit(d.write_async(keys, vals))
        assert int(st["updated"]) == 64
        print("cache keying OK:", len(d._fn_cache), "closures")
    """))


def test_sharded_pipelined_parity_l1_on_and_off():
    """The bench's schedule in miniature: a pipelined lookup-or-compute
    over the jitted sharded wrappers must be bit-for-bit the synchronous
    one — with and without the locality tier attached."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import DHTConfig, L1Config
        from repro.core.distributed import (
            ShardedDHT, _state_shardings, make_mesh_1d)
        from repro.core.layout import dht_create
        from repro.core.pipeline import PendingWrites, RoundQueue

        KW, VW = 20, 26
        mesh = make_mesh_1d(4, "d")
        cfg = DHTConfig(n_shards=4, buckets_per_shard=1024)
        rng = np.random.default_rng(1)

        def compute(keys_np):
            x = keys_np[:, :4].astype(np.float64)
            return ((x * 2654435761.0) % 2**31).astype(np.uint32).repeat(
                VW // 4 + 1, axis=1)[:, :VW]

        batches = []
        prev = None
        for _ in range(4):
            ids = rng.integers(0, 4000, size=96)
            if prev is not None:
                ids[:32] = prev[rng.integers(0, 96, size=32)]
            prev = ids
            kb = np.zeros((96, KW), np.uint32)
            kb[:, 0] = ids
            kb[:, 1] = ids * 7 + 1
            batches.append((jnp.asarray(kb), kb))

        for l1cfg in (None, L1Config(n_sets=64, n_ways=4)):
            def fresh():
                return ShardedDHT.create(mesh, cfg, l1cfg=l1cfg)

            d = fresh()
            outs_s = []
            for kb, kb_np in batches:
                vals, found, _ = d.read(kb)
                fn = np.asarray(found); vn = np.asarray(vals)
                miss = ~fn
                cv = compute(kb_np)
                out = np.where(miss[:, None], cv, vn)
                if miss.any():
                    d.write(kb, jnp.asarray(cv), jnp.asarray(miss))
                outs_s.append((out, fn))

            d = fresh()
            pending = PendingWrites(VW)
            wq = RoundQueue(2, d.write_commit)
            outs_p = []
            conf = pending.conflicts(batches[0][1])
            rd = d.read_async(batches[0][0], jnp.asarray(~conf))
            to_retire = None
            for i, (kb, kb_np) in enumerate(batches):
                vals, found, _ = d.read_commit(rd)
                fn = np.asarray(found); vn = np.asarray(vals)
                if conf.any():
                    fv = pending.resolve(kb_np, conf)
                    vn = np.where(conf[:, None], fv, vn)
                    fn = fn | conf
                if to_retire is not None:
                    pending.retire(*to_retire)
                    to_retire = None
                miss = ~fn
                if miss.any():
                    pending.promise(kb_np, miss)
                if i + 1 < len(batches):
                    nconf = pending.conflicts(batches[i + 1][1])
                    nrd = d.read_async(
                        batches[i + 1][0], jnp.asarray(~nconf))
                cv = compute(kb_np)
                out = np.where(miss[:, None], cv, vn)
                if miss.any():
                    pending.publish(kb_np, cv, miss)
                    w = d.write_async(kb, jnp.asarray(cv), jnp.asarray(miss))
                    to_retire = (kb_np, miss)
                    wq.push(w)
                outs_p.append((out, fn))
                if i + 1 < len(batches):
                    rd, conf = nrd, nconf
            wq.drain()

            for (o_s, f_s), (o_p, f_p) in zip(outs_s, outs_p):
                assert np.array_equal(f_s, f_p), "found parity"
                assert np.array_equal(o_s, o_p), "value parity"
            print("parity OK, l1 =", l1cfg is not None)
    """))
