"""Crash tolerance (DESIGN.md §13): k-successor replication,
crash-tolerant reads, fault injection, anti-entropy repair, and the
bounded write-retry loop, on both backends.

Covers the acceptance criteria: with ``n_replicas=2``, killing one shard
mid-workload loses ZERO acked writes; reads fail over to the first live
successor in the same collective-round schedule; anti-entropy repair
converges the recovered shard (empty watermark diff) and is idempotent;
the ``n_replicas=1`` path stays bit-for-bit today's engine; and the
``IssueCommitOracle`` replicated model agrees with the real engine under
random crash/recover/repair interleavings.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from _hypothesis_compat import given, settings, st
from repro.core import (
    DHTConfig,
    W_DROPPED,
    crash_shard,
    dht_create,
    dht_read,
    dht_write,
    dht_write_replicated,
    migrate,
    recover_shard,
    ring_create,
)
from repro.core import faults
from repro.core.async_sim import IssueCommitOracle
from repro.core.hashing import hash64
from repro.core.membership import (
    MAX_REPLICAS,
    ring_crash,
    ring_join,
    ring_leave,
    ring_owner_np,
    ring_successors_np,
)

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
KW, VW = 20, 26


def _kv(n, seed=0):
    """Keys with DETERMINISTIC values (a pure function of the key), so
    duplicate writes are idempotent and read-back is bit-checkable."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 2**31, size=(n, KW), dtype=np.int64)
    vals = np.zeros((n, VW), np.uint32)
    for w in range(VW):
        vals[:, w] = (keys[:, 0] * (2 * w + 1) * 2654435761 + w) & 0xFFFFFFFF
    return jnp.asarray(keys, jnp.uint32), jnp.asarray(vals)


def _mk(s=8, k=2, cap=None, n=None):
    cfg = DHTConfig(n_shards=s, n_replicas=k, buckets_per_shard=(1 << 12),
                    capacity=cap if cap is not None else (n or 512))
    return dht_create(cfg, ring_create(s))


# ---------------------------------------------------------------------------
# ring successor properties
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=2, max_value=16),
       st.integers(min_value=0, max_value=999))
def test_ring_successors_properties(s, seed):
    """k distinct shards per key, owner is successor 0, crash preserves
    placement, leave is a minimal set change, join-back restores."""
    k = min(MAX_REPLICAS, s)
    rng = np.random.default_rng(seed)
    ring = ring_create(s)
    h = rng.integers(0, 2**32, size=128, dtype=np.uint64).astype(np.uint32)
    succ = ring_successors_np(ring, h, k)
    assert succ.shape == (128, k)
    assert (succ >= 0).all() and (succ < s).all()
    for row in succ:
        assert len(set(row.tolist())) == k, row
    assert (succ[:, 0] == ring_owner_np(ring, h)).all()

    # crash flips liveness WITHOUT rebuilding placement: same table
    victim = int(rng.integers(s))
    r_crash = ring_crash(ring, victim)
    assert (ring_successors_np(r_crash, h, k) == succ).all()
    assert not bool(r_crash.alive[victim])
    assert int(r_crash.epoch) == int(ring.epoch) + 1

    if s <= k:
        return
    # graceful leave rebuilds: keys whose successor set never met the
    # victim keep EXACTLY their old set (minimal churn) ...
    r_left = ring_leave(ring, victim)
    s_left = ring_successors_np(r_left, h, k)
    untouched = ~(succ == victim).any(axis=1)
    assert (s_left[untouched] == succ[untouched]).all()
    # ... touched keys keep every surviving member of their old set
    for old, new in zip(succ[~untouched], s_left[~untouched]):
        assert set(old.tolist()) - {victim} <= set(new.tolist()), (old, new)
    assert not (s_left == victim).any()
    # join-back restores the original table bit-for-bit
    assert (ring_successors_np(ring_join(r_left, victim), h, k)
            == succ).all()


# ---------------------------------------------------------------------------
# replicated writes
# ---------------------------------------------------------------------------

def test_replicated_k1_bit_identical():
    """n_replicas=1 must BE dht_write: same table arrays, same codes."""
    keys, vals = _kv(128, seed=1)
    st_a = _mk(s=4, k=1, n=128)
    st_b = _mk(s=4, k=1, n=128)
    st_a, ws_a = dht_write(st_a, keys, vals)
    st_b, ws_b = dht_write_replicated(st_b, keys, vals)
    for name in ("keys", "vals", "meta", "csum"):
        assert bool((getattr(st_a, name) == getattr(st_b, name)).all()), name
    assert bool((ws_a["code"] == ws_b["code"]).all())
    assert int(ws_b["replica_writes"]) == 0
    assert int(ws_b["acked"]) == 128


def test_replicated_write_acks_and_fans_out():
    keys, vals = _kv(256, seed=2)
    st = _mk(s=8, k=2, n=256)
    st, ws = dht_write_replicated(st, keys, vals)
    assert int(ws["acked"]) == 256
    assert int(ws["replica_writes"]) == 256      # one secondary per row
    assert int(ws["dropped"]) == 0
    # both copies live in the same probe window of their own slabs:
    # every key is readable and bit-identical
    st, out, found, rs = dht_read(st, keys)
    assert bool(found.all()) and bool((out == vals).all())
    assert int(rs["fallback_reads"]) == 0        # healthy ring: owner serves


def test_all_replicas_down_rows_drop_not_ack():
    """A row whose WHOLE replica set is dead reports W_DROPPED/unacked —
    indistinguishable from overflow, which is what retry loops expect."""
    st = _mk(s=4, k=2, n=512)
    keys, vals = _kv(512, seed=3)
    succ = ring_successors_np(st.ring, np.asarray(hash64(keys)[0]), 2)
    doomed = np.isin(succ, (0, 1)).all(axis=1)
    if not doomed.any():                          # ring-layout dependent
        return
    st = crash_shard(st, 0)
    st = crash_shard(st, 1)
    st, ws = dht_write_replicated(st, keys, vals)
    code = np.asarray(ws["code"])
    assert (code[doomed] == W_DROPPED).all()
    assert (code[~doomed] != W_DROPPED).all()
    assert int(ws["acked"]) == int((~doomed).sum())
    st, _, found, _ = dht_read(st, keys)
    found = np.asarray(found)
    assert not found[doomed].any() and found[~doomed].all()


# ---------------------------------------------------------------------------
# crash -> failover -> recover -> repair
# ---------------------------------------------------------------------------

def test_crash_failover_reads_bit_identical():
    victim = 3
    keys, vals = _kv(300, seed=4)
    st = _mk(s=8, k=2, n=300)
    st, _ = dht_write_replicated(st, keys, vals)
    owners = ring_successors_np(st.ring, np.asarray(hash64(keys)[0]), 1)[:, 0]
    st = crash_shard(st, victim)
    st, out, found, rs = dht_read(st, keys)
    assert bool(found.all())
    assert bool((out == vals).all())
    # failover is a routing decision: exactly the victim-owned keys
    # report as fallback-served
    assert int(rs["fallback_reads"]) == int((owners == victim).sum())


def test_availability_gap_closed_by_repair():
    victim = 5
    keys, vals = _kv(300, seed=6)
    st = _mk(s=8, k=2, n=300)
    st, _ = dht_write_replicated(st, keys, vals)
    owners = ring_successors_np(st.ring, np.asarray(hash64(keys)[0]), 1)[:, 0]
    st = crash_shard(st, victim)
    st = recover_shard(st, victim)
    # recovered-but-unrepaired: the live-again owner serves its keys from
    # an empty slab — the documented availability gap (a miss, never a
    # wrong value; write-once recompute would republish bit-identically)
    st, _, found, _ = dht_read(st, keys)
    assert (np.asarray(~found) == (owners == victim)).all()
    # anti-entropy converges: empty diff, everything readable again
    st, rep = migrate.repair_run(st, victim, batch=128)
    assert rep["healed"] > 0
    assert migrate.repair_diff(st, victim) == 0
    st, out, found, rs = dht_read(st, keys)
    assert bool(found.all()) and bool((out == vals).all())
    assert int(rs["fallback_reads"]) == 0
    # idempotent: a second pass finds nothing to heal
    st, rep2 = migrate.repair_run(st, victim, batch=128)
    assert rep2["healed"] == 0 and rep2["rounds"] == 0


def test_repair_plan_watermark_diff():
    """plan_repair enumerates exactly the copies the shard lost, and the
    generation-watermark fast path skips keys already present."""
    victim = 2
    keys, vals = _kv(200, seed=7)
    st = _mk(s=8, k=2, n=200)
    st, _ = dht_write_replicated(st, keys, vals)
    plan_healthy = migrate.plan_repair(st, victim)
    assert plan_healthy.n_missing == 0            # nothing lost yet
    assert plan_healthy.n_candidates == plan_healthy.n_present
    st = crash_shard(st, victim)
    st = recover_shard(st, victim)
    plan = migrate.plan_repair(st, victim)
    assert plan.n_present == 0                    # slab was wiped
    assert plan.n_missing == plan.n_candidates > 0
    # partial heal, then re-plan: healed keys move missing -> present
    rep = migrate.repair_begin(st, victim, batch=32)
    rep, step = migrate.repair_step(rep)
    assert step["healed"] == min(32, plan.n_missing)
    plan2 = migrate.plan_repair(rep.state, victim)
    assert plan2.n_missing == plan.n_missing - step["healed"]


# ---------------------------------------------------------------------------
# deterministic fault injection
# ---------------------------------------------------------------------------

def test_fault_injection_deterministic_drops():
    keys, vals = _kv(256, seed=8)

    def run():
        st = _mk(s=4, k=1, n=256)
        with faults.injected(drop_frac=0.4, seed=13) as plan:
            st, ws = dht_write(st, keys, vals)
        return np.asarray(ws["code"]), plan.injected

    code_a, n_a = run()
    code_b, n_b = run()
    assert 0 < n_a < 256
    assert (code_a == W_DROPPED).sum() == n_a
    # same plan + same call sequence = same injected faults, bit-for-bit
    assert n_a == n_b and (code_a == code_b).all()
    # reads are ineligible by default ("write","migrate"): no perturbation
    st = _mk(s=4, k=1, n=256)
    st, _ = dht_write(st, keys, vals)
    with faults.injected(drop_frac=1.0, seed=13) as plan:
        st, _, found, _ = dht_read(st, keys)
    assert bool(found.all()) and plan.injected == 0


# ---------------------------------------------------------------------------
# IssueCommitOracle: crash/recover/repair transitions + interleavings
# ---------------------------------------------------------------------------

def _static_placement(pool_keys, ring, k):
    succ = ring_successors_np(ring, np.asarray(hash64(pool_keys)[0]), k)
    index = {np.asarray(pool_keys)[i].tobytes(): i
             for i in range(pool_keys.shape[0])}

    def place(key):
        row = np.ascontiguousarray(np.asarray(key, np.uint32)).tobytes()
        return tuple(int(x) for x in succ[index[row]])

    return place


def test_oracle_transitions():
    keys, vals = _kv(64, seed=9)
    ring = ring_create(4)
    orc = IssueCommitOracle(n_shards=4,
                            placement=_static_placement(keys, ring, 2))
    orc.commit(orc.issue_write(keys, vals))
    _, found = orc.commit(orc.issue_read(keys))
    assert all(found)
    owners = ring_successors_np(ring, np.asarray(hash64(keys)[0]), 1)[:, 0]
    victim = int(np.bincount(owners, minlength=4).argmax())
    orc.crash(victim)
    _, found = orc.commit(orc.issue_read(keys))
    assert all(found)                              # failover serves all
    orc.recover(victim)
    _, found = orc.commit(orc.issue_read(keys))
    gap = [not f for f in found]
    assert gap == (owners == victim).tolist()      # the availability gap
    healed = orc.repair(victim, keys)
    assert healed > 0 and orc.repair(victim, keys) == 0
    _, found = orc.commit(orc.issue_read(keys))
    assert all(found)


def test_oracle_interleaving_matches_engine():
    """Random crash/recover+repair/write schedules: the replicated
    engine's visible reads must match the oracle's, value-for-value."""
    s, k, n_pool = 4, 2, 96
    pool_keys, pool_vals = _kv(n_pool, seed=10)
    st = _mk(s=s, k=k, n=n_pool)
    orc = IssueCommitOracle(
        n_shards=s, placement=_static_placement(pool_keys, st.ring, k))
    rng = np.random.default_rng(42)
    alive = [True] * s
    for step in range(30):
        op = rng.choice(["write", "crash", "recover"], p=[0.5, 0.25, 0.25])
        if op == "write":
            idx = rng.choice(n_pool, size=8, replace=False)
            st, _ = dht_write_replicated(
                st, pool_keys[idx], pool_vals[idx])
            orc.commit(orc.issue_write(np.asarray(pool_keys)[idx],
                                       np.asarray(pool_vals)[idx]))
        elif op == "crash" and sum(alive) > 1:
            v = int(rng.choice([i for i in range(s) if alive[i]]))
            st = crash_shard(st, v)
            orc.crash(v)
            alive[v] = False
        elif op == "recover" and not all(alive):
            d = int(rng.choice([i for i in range(s) if not alive[i]]))
            st = recover_shard(st, d)
            st, _ = migrate.repair_run(st, d, batch=64)
            orc.recover(d)
            orc.repair(d, pool_keys)
            alive[d] = True
        st, out, found, _ = dht_read(st, pool_keys)
        ovals, ofound = orc.commit(orc.issue_read(pool_keys))
        found = np.asarray(found)
        assert found.tolist() == ofound, f"step {step}: found diverged"
        for i in np.nonzero(found)[0]:
            assert (np.asarray(out)[i] == ovals[i]).all(), (step, i)


# ---------------------------------------------------------------------------
# sharded backend (subprocess, 8 virtual devices)
# ---------------------------------------------------------------------------

def _run_sharded(code: str, timeout=420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    print(out.stdout)


def test_sharded_crash_failover_repair():
    _run_sharded("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import DHTConfig, ring_create
        from repro.core.distributed import ShardedDHT, make_mesh_1d
        from repro.obs import metrics as obs_metrics

        mesh = make_mesh_1d(8)
        rng = np.random.default_rng(0)
        keys = jnp.asarray(rng.integers(0, 2**31, size=(1024, 20)),
                           jnp.uint32)
        vals = jnp.asarray(rng.integers(0, 2**31, size=(1024, 26)),
                           jnp.uint32)
        d = ShardedDHT.create(
            mesh, DHTConfig(n_shards=8, n_replicas=2,
                            buckets_per_shard=4096, capacity=256),
            ring=ring_create(8))
        ws = d.write(keys, vals)
        assert int(ws["acked"]) == 1024, ws
        assert int(ws["replica_writes"]) == 1024, ws

        d.crash(2)
        out, found, rs = d.read(keys)
        assert bool(found.all()), int(found.sum())
        assert bool((out == vals).all())
        assert int(rs["fallback_reads"]) > 0, rs

        d.recover(2)
        rep = d.repair(2)
        assert rep["healed"] > 0 and rep["diff_after"] == 0, rep
        out, found, rs = d.read(keys)
        assert bool(found.all()) and bool((out == vals).all())
        assert int(rs["fallback_reads"]) == 0, rs
        snap = obs_metrics.get_registry().snapshot()["counters"]
        assert snap["faults.crashes"] == 1, snap
        assert snap["repair.keys_healed"] == rep["healed"], snap
        print("sharded crash/failover/repair OK", rep)
    """)


def test_sharded_l1_crash_fence():
    _run_sharded("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import DHTConfig, L1Config, ring_create
        from repro.core.distributed import ShardedDHT, make_mesh_1d

        mesh = make_mesh_1d(8)
        rng = np.random.default_rng(1)
        keys = jnp.asarray(rng.integers(0, 2**31, size=(512, 20)),
                           jnp.uint32)
        vals = jnp.asarray(rng.integers(0, 2**31, size=(512, 26)),
                           jnp.uint32)
        d = ShardedDHT.create(
            mesh, DHTConfig(n_shards=8, n_replicas=2,
                            buckets_per_shard=4096, capacity=256),
            ring=ring_create(8),
            l1cfg=L1Config(n_sets=256, n_ways=4))
        d.write(keys, vals)
        out, found, rs = d.read(keys)              # fill
        out, found, rs = d.read(keys)              # hot
        warm = int(rs["l1_hits"])
        assert warm > 0, rs

        d.crash(3)
        # the crash's epoch bump fences EVERY pre-crash line: first
        # post-crash round serves zero L1 hits but stays bit-identical
        out, found, rs = d.read(keys)
        assert int(rs["l1_hits"]) == 0, rs
        assert bool(found.all()) and bool((out == vals).all())
        out, found, rs = d.read(keys)              # refilled at new epoch
        assert int(rs["l1_hits"]) > 0, rs
        print("sharded L1 crash fence OK", warm, int(rs["l1_hits"]))
    """)


def test_sharded_write_retry_on_overflow():
    _run_sharded("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import DHTConfig, ring_create
        from repro.core.distributed import ShardedDHT, make_mesh_1d
        from repro.obs import metrics as obs_metrics

        mesh = make_mesh_1d(8)
        rng = np.random.default_rng(2)
        n = 2048
        keys = jnp.asarray(rng.integers(0, 2**31, size=(n, 20)), jnp.uint32)
        vals = jnp.asarray(rng.integers(0, 2**31, size=(n, 26)), jnp.uint32)
        # deliberately tiny static per-bin capacity: the first round MUST
        # overflow and the bounded retry loop must recover every row
        d = ShardedDHT.create(
            mesh, DHTConfig(n_shards=8, buckets_per_shard=4096,
                            capacity=24),
            ring=ring_create(8))
        ws = d.write(keys, vals)
        applied = (int(ws["inserted"]) + int(ws["updated"])
                   + int(ws["evicted"]))
        assert applied == n, (applied, n)
        assert int(ws["write_retries"]) >= 1, ws
        assert int(ws["dropped"]) == 0, ws
        snap = obs_metrics.get_registry().snapshot()["counters"]
        # recovered rows are requeued, never silently dropped
        assert snap.get("engine.requeued", 0) > 0, snap
        assert snap.get("engine.dropped", 0) == 0, snap
        print("sharded retry-on-overflow OK",
              int(ws["write_retries"]), int(snap["engine.requeued"]))
    """)


def test_eager_write_retry_on_overflow():
    """Eager ``dht_write(max_retries=)``: a fixed routing capacity sized
    below the skewed bin load drops rows in round 1; the bounded retry
    re-issues them (a thin batch fits the same window) and the registry
    relabels the recovered drops ``dropped -> requeued``."""
    from repro.obs import metrics as obs_metrics

    rng = np.random.default_rng(5)
    n, s = 2048, 32
    keys = jnp.asarray(rng.integers(0, 2**31, size=(n, 20)), jnp.uint32)
    vals = jnp.asarray(rng.integers(0, 2**31, size=(n, 26)), jnp.uint32)
    cfg = DHTConfig(n_shards=s, buckets_per_shard=1 << 13, capacity=72)

    obs_metrics.get_registry().reset()
    st = dht_create(cfg)
    st, ws0 = dht_write(st, keys, vals)
    assert int(ws0["dropped"]) > 0, "capacity must overflow for this test"

    obs_metrics.get_registry().reset()
    st = dht_create(cfg)
    st, ws = dht_write(st, keys, vals, max_retries=2)
    assert int(ws["dropped"]) == 0, ws
    assert int(ws["rounds"]) > 1, ws
    snap = obs_metrics.get_registry().snapshot()["counters"]
    assert snap.get("engine.dropped", 0) == 0, snap
    assert snap.get("engine.requeued", 0) == int(ws0["dropped"]), (
        snap, int(ws0["dropped"]))
    # read back in thin chunks (a full-batch read would overflow the
    # same fixed routing window and report spurious misses)
    for lo in range(0, n, 256):
        st, got, found, _ = dht_read(st, keys[lo:lo + 256])
        assert bool(np.asarray(found).all()), lo
        assert np.array_equal(np.asarray(got), np.asarray(vals[lo:lo + 256]))

    # default (max_retries=0) stays bit-for-bit the single-round write
    st1 = dht_create(cfg)
    st1, _ = dht_write(st1, keys, vals)
    st2 = dht_create(cfg)
    st2, _ = dht_write(st2, keys, vals, max_retries=0)
    for a, b in zip(jax.tree_util.tree_leaves(st1),
                    jax.tree_util.tree_leaves(st2)):
        assert jnp.array_equal(a, b)
