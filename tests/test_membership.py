"""Elastic membership & online resharding (core/membership.py +
core/migrate.py, DESIGN.md §4-5).

Covers the acceptance criteria: resize S -> 2S -> S preserves 100% of
live entries; reads between plan and retire hit in-flight entries
(dual-epoch path); shard leave/join rebalance in place; and the
shard_map backend reshards through the all_to_all write path (run in a
subprocess with forced virtual devices, like tests/test_distributed.py).
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np

from repro.core import (
    DHTConfig,
    adopt_ring,
    dht_create,
    dht_read,
    dht_resize,
    dht_write,
    migration_begin,
    migration_finish,
    migration_read,
    migration_step,
    plan_migration,
    ring_create,
    ring_join,
    ring_leave,
    ring_resize,
    shard_join,
    shard_leave,
)
from repro.core.hashing import hash64
from repro.core.layout import INVALID, OCCUPIED, occupancy
from repro.core.membership import ring_owner_np, ring_owner_of

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
KW, VW = 20, 26


def _kv(n, seed=0):
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(rng.integers(0, 2**31, size=(n, KW)), jnp.uint32)
    vals = jnp.asarray(rng.integers(0, 2**31, size=(n, VW)), jnp.uint32)
    return keys, vals


def _hashes(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2**32, size=(n,), dtype=np.uint64).astype(np.uint32)


# ---------------------------------------------------------------------------
# ring properties
# ---------------------------------------------------------------------------

def test_ring_covers_all_live_shards_roughly_evenly():
    ring = ring_create(8, n_virtual=64)
    owners = ring_owner_np(ring, _hashes(20_000))
    counts = np.bincount(owners, minlength=8)
    assert (counts > 0).all(), "every live shard must own keys"
    # virtual nodes keep the imbalance bounded (loose: max < 3x mean)
    assert counts.max() < 3 * counts.mean()


def test_ring_lookup_jnp_matches_np():
    ring = ring_create(5, n_virtual=32)
    h = _hashes(1000)
    np.testing.assert_array_equal(
        np.asarray(ring_owner_of(ring, jnp.asarray(h))),
        ring_owner_np(ring, h),
    )


def test_ring_minimal_disruption_on_leave_and_join():
    ring = ring_create(8, n_virtual=64)
    h = _hashes(20_000)
    before = ring_owner_np(ring, h)
    left = ring_leave(ring, 3)
    after = ring_owner_np(left, h)
    moved = before != after
    # only keys owned by the leaver move, and they all move off shard 3
    assert (before[moved] == 3).all()
    assert not (after == 3).any()
    assert int(left.epoch) == 1
    # join restores the exact previous ownership (vnode positions are
    # deterministic in (shard, replica))
    back = ring_join(left, 3)
    np.testing.assert_array_equal(ring_owner_np(back, h), before)
    assert int(back.epoch) == 2


def test_ring_resize_moves_only_captured_keys():
    ring = ring_create(4, n_virtual=64)
    h = _hashes(20_000)
    before = ring_owner_np(ring, h)
    grown = ring_resize(ring, 8)
    after = ring_owner_np(grown, h)
    moved = before != after
    # keys only move TO the new shards, never between the old ones
    assert (after[moved] >= 4).all()
    assert 0.2 < moved.mean() < 0.8, "roughly half the keyspace moves on 2x"


# ---------------------------------------------------------------------------
# online resharding (local backend)
# ---------------------------------------------------------------------------

def _live_count(state):
    m = np.asarray(state.meta)
    return int((((m & OCCUPIED) != 0) & ((m & INVALID) == 0)).sum())


def test_resize_up_and_down_preserves_all_live_entries():
    cfg = DHTConfig(n_shards=4, buckets_per_shard=1024)
    st = dht_create(cfg, ring_create(4))
    keys, vals = _kv(300)
    st, ws = dht_write(st, keys, vals)
    assert int(ws["inserted"]) == 300
    n_live = _live_count(st)

    st, ms = dht_resize(st, 8)
    assert st.cfg.n_shards == 8 and st.keys.shape[0] == 8
    assert _live_count(st) == n_live
    assert ms["evicted_at_dest"] == 0, "lossless at this occupancy"
    assert ms["inplace"] and 0 < ms["moved"] < n_live, \
        "consistent hashing must move only part of the table"
    st, out, found, rs = dht_read(st, keys)
    assert bool(found.all()), f"lost {300 - int(rs['hits'])} entries on grow"
    assert bool((out == vals).all())

    st, ms = dht_resize(st, 4)
    assert st.cfg.n_shards == 4 and st.keys.shape[0] == 4
    assert _live_count(st) == n_live
    st, out, found, rs = dht_read(st, keys)
    assert bool(found.all()), f"lost {300 - int(rs['hits'])} entries on shrink"
    assert bool((out == vals).all())


def test_mid_migration_dual_read_never_loses_hits():
    cfg = DHTConfig(n_shards=4, buckets_per_shard=1024)
    st = dht_create(cfg, ring_create(4))
    keys, vals = _kv(256)
    st, _ = dht_write(st, keys, vals)

    mig = migration_begin(st, ring_resize(st.ring, 8), batch=32)
    assert mig.plan.n_moved > 64, "need several batches in flight"
    steps = 0
    while not mig.done:
        mig, _ = migration_step(mig)
        steps += 1
        # between plan and retire: every entry stays readable
        mig, out, found, ds = migration_read(mig, keys)
        assert bool(found.all()), f"lost entries at step {steps}"
        assert bool((out == vals).all())
    assert steps >= 3
    # early steps must have served part of the reads from the old epoch
    st2, ms = migration_finish(mig)
    assert ms["moved"] == ms["n_planned"]
    st2, out, found, _ = dht_read(st2, keys)
    assert bool(found.all()) and bool((out == vals).all())


def test_mid_migration_write_survives_stale_copy():
    """A key re-written mid-migration must not be clobbered when its stale
    old-epoch copy streams over afterwards."""
    cfg = DHTConfig(n_shards=4, buckets_per_shard=1024)
    st = dht_create(cfg, ring_create(4))
    keys, vals = _kv(128)
    st, _ = dht_write(st, keys, vals)

    mig = migration_begin(st, ring_resize(st.ring, 8), batch=16)
    # before any batch moves: overwrite every key in the NEW epoch
    mig.new, _ = dht_write(mig.new, keys, vals + 7)
    while not mig.done:
        mig, _ = migration_step(mig)
    st2, ms = migration_finish(mig)
    assert ms["skipped"] > 0, "guard read must skip superseded stale copies"
    st2, out, found, _ = dht_read(st2, keys)
    assert bool(found.all())
    assert bool((out == vals + 7).all()), "stale migration copy clobbered a write"


def test_shard_leave_then_join_rebalances_in_place():
    cfg = DHTConfig(n_shards=8, buckets_per_shard=512)
    st = dht_create(cfg, ring_create(8))
    keys, vals = _kv(400)
    st, _ = dht_write(st, keys, vals)
    n_live = _live_count(st)

    st, ms = shard_leave(st, 2)
    assert ms["inplace"] and ms["moved"] < n_live // 2
    assert float(occupancy(st)[2]) == 0.0, "leaver's slab must drain"
    assert _live_count(st) == n_live
    st, out, found, _ = dht_read(st, keys)
    assert bool(found.all()) and bool((out == vals).all())

    st, ms = shard_join(st, 2)
    assert _live_count(st) == n_live
    assert float(occupancy(st)[2]) > 0.0, "joiner must recapture entries"
    st, out, found, _ = dht_read(st, keys)
    assert bool(found.all()) and bool((out == vals).all())


def test_shrink_into_full_table_reports_destination_evictions():
    """Shrinking below capacity cannot be lossless; the loss must be
    *reported* (evicted_at_dest), never silent."""
    cfg = DHTConfig(n_shards=4, buckets_per_shard=16, n_probe=4)
    st = dht_create(cfg, ring_create(4))
    keys, vals = _kv(48)                      # 48 entries into 64 buckets
    st, _ = dht_write(st, keys, vals)
    n_live = _live_count(st)
    assert n_live > 16, "need more live entries than the shrunk capacity"
    st, ms = dht_resize(st, 1)                # -> only 16 buckets remain
    assert ms["evicted_at_dest"] > 0, \
        "lossy migration must surface destination evictions"
    assert _live_count(st) <= cfg.buckets_per_shard


def test_adopt_ring_migrates_modulo_placement():
    cfg = DHTConfig(n_shards=4, buckets_per_shard=1024)
    st = dht_create(cfg)                       # legacy static placement
    keys, vals = _kv(200)
    st, _ = dht_write(st, keys, vals)
    st, ms = adopt_ring(st)
    assert st.ring is not None and ms["moved"] > 0
    st, out, found, _ = dht_read(st, keys)
    assert bool(found.all()) and bool((out == vals).all())


def test_plan_matches_owner_delta():
    """The plan enumerates exactly the occupied buckets whose ring owner
    differs from the row they sit in."""
    cfg = DHTConfig(n_shards=4, buckets_per_shard=512)
    st = dht_create(cfg, ring_create(4))
    keys, vals = _kv(200)
    st, _ = dht_write(st, keys, vals)
    new_ring = ring_leave(st.ring, 1)
    plan = plan_migration(st, new_ring, st.cfg)
    # independent recomputation from the stored keys
    s, b, kw = st.keys.shape
    h_hi, _ = hash64(jnp.reshape(st.keys, (s * b, kw)))
    owner = ring_owner_np(new_ring, np.asarray(h_hi)).reshape(s, b)
    m = np.asarray(st.meta)
    live = ((m & OCCUPIED) != 0) & ((m & INVALID) == 0)
    expect = np.nonzero(
        (live & (owner != np.arange(s)[:, None])).reshape(-1))[0]
    np.testing.assert_array_equal(plan.src, expect)
    # under consistent hashing, leaving shard 1 moves exactly its entries
    rows = plan.src // b
    assert (rows == 1).all()


def test_invalid_entries_are_not_migrated():
    cfg = DHTConfig(n_shards=4, buckets_per_shard=512)
    st = dht_create(cfg, ring_create(4))
    keys, vals = _kv(64)
    st, _ = dht_write(st, keys, vals)
    st.csum = st.csum ^ jnp.uint32(0xDEADBEEF)     # corrupt everything
    st, _, found, _ = dht_read(st, keys)           # flags INVALID
    assert not bool(found.any())
    st, ms = dht_resize(st, 8)
    assert ms["n_live"] == 0 and ms["moved"] == 0


# ---------------------------------------------------------------------------
# shard_map backend (subprocess, >= 2 virtual devices)
# ---------------------------------------------------------------------------

def test_sharded_leave_join_all_to_all():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import DHTConfig, ring_create
        from repro.core.distributed import ShardedDHT, make_mesh_1d
        from repro.core.layout import occupancy

        assert len(jax.devices()) >= 2
        mesh = make_mesh_1d(8)
        rng = np.random.default_rng(0)
        keys = jnp.asarray(rng.integers(0, 2**31, size=(256, 20)), jnp.uint32)
        vals = jnp.asarray(rng.integers(0, 2**31, size=(256, 26)), jnp.uint32)
        d = ShardedDHT.create(
            mesh, DHTConfig(n_shards=8, buckets_per_shard=512, capacity=64),
            ring=ring_create(8))
        d.write(keys, vals)

        ms = d.leave(3)
        assert 0 < ms["moved"] < 256, ms
        out, found, rs = d.read(keys)
        assert bool(found.all()) and bool((out == vals).all())
        assert int(rs["epoch"]) == 1, rs
        assert float(occupancy(d.state)[3]) == 0.0

        ms = d.join(3)
        out, found, rs = d.read(keys)
        assert bool(found.all()) and bool((out == vals).all())
        assert float(occupancy(d.state)[3]) > 0.0
        print("sharded elastic membership OK", ms)
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=420)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    print(out.stdout)
