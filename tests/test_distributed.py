"""Distributed execution tests — run in subprocesses so the main pytest
process keeps the single real CPU device (see conftest.py note)."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_sharded_dht_all_modes():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import DHTConfig
        from repro.core.compat import make_mesh
        from repro.core.distributed import ShardedDHT

        mesh = make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(0)
        keys = jnp.asarray(rng.integers(0, 2**31, size=(256, 20)), jnp.uint32)
        vals = jnp.asarray(rng.integers(0, 2**31, size=(256, 26)), jnp.uint32)
        for mode in ("lockfree", "fine", "coarse"):
            d = ShardedDHT.create(mesh, DHTConfig(
                n_shards=8, buckets_per_shard=512, mode=mode, capacity=64))
            ws = d.write(keys, vals)
            out, found, rs = d.read(keys)
            assert bool(found.all()), (mode, int(rs["hits"]))
            assert bool((out == vals).all()), mode
            if mode != "lockfree":
                assert int(ws["lock_tokens"]) > 0
        print("all modes OK")
    """))


def test_sharded_dht_read_many_one_round():
    """The multi-key (stencil) read path on the shard_map/all_to_all
    backend: every candidate key resolves in one routing round."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import DHTConfig
        from repro.core.distributed import ShardedDHT, make_mesh_1d

        mesh = make_mesh_1d(8)
        rng = np.random.default_rng(1)
        keys = jnp.asarray(rng.integers(0, 2**31, size=(256, 20)), jnp.uint32)
        vals = jnp.asarray(rng.integers(0, 2**31, size=(256, 26)), jnp.uint32)
        d = ShardedDHT.create(mesh, DHTConfig(
            n_shards=8, buckets_per_shard=1024, capacity=256))
        d.write(keys, vals)
        many = keys.reshape(64, 4, 20)
        out, found, rs = d.read_many(many)
        assert found.shape == (64, 4) and bool(found.all()), int(rs["hits"])
        assert bool((out.reshape(256, 26) == vals).all())
        # valid mask: only the first candidate of each row is probed
        valid = jnp.zeros((64, 4), bool).at[:, 0].set(True)
        out, found, rs = d.read_many(many, valid)
        f = np.asarray(found)
        assert f[:, 0].all() and not f[:, 1:].any()
        print("read_many OK")
    """))


def test_sharded_execute_fn_matches_wrappers_all_modes():
    """The op-engine closure on the shard_map/all_to_all backend must be
    bitwise-identical to the read/write wrapper closures (which are thin
    shims over the same engine), and its get-or-put must equal the old
    guard-read + masked-write sequence — per consistency mode."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import DHTConfig
        from repro.core.dht import W_SKIP, W_INSERT
        from repro.core.distributed import ShardedDHT, make_mesh_1d

        mesh = make_mesh_1d(8)
        rng = np.random.default_rng(5)
        keys = jnp.asarray(rng.integers(0, 2**31, size=(256, 20)), jnp.uint32)
        vals = jnp.asarray(rng.integers(0, 2**31, size=(256, 26)), jnp.uint32)
        k2 = jnp.asarray(rng.integers(0, 2**31, size=(256, 20)), jnp.uint32)
        v2 = jnp.asarray(rng.integers(0, 2**31, size=(256, 26)), jnp.uint32)
        ones = jnp.ones((256,), bool)
        for mode in ("lockfree", "fine", "coarse"):
            cfg = DHTConfig(n_shards=8, buckets_per_shard=512, mode=mode,
                            capacity=64)
            a = ShardedDHT.create(mesh, cfg)
            b = ShardedDHT.create(mesh, cfg)
            # wrappers on a
            ws = a.write(keys, vals)
            out_a, found_a, _ = a.read(keys)
            # engine closures on b
            ew = b.execute_fn(("write",))
            er = b.execute_fn(("read",))
            b.state, _, _, code_w, es = ew(b.state, keys, vals, ones)
            b.state, out_b, found_b, _, _ = er(b.state, keys, vals, ones)
            np.testing.assert_array_equal(np.asarray(ws["code"]),
                                          np.asarray(code_w))
            np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))
            np.testing.assert_array_equal(np.asarray(found_a),
                                          np.asarray(found_b))
            for n in ("keys", "vals", "meta", "csum"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(a.state, n)),
                    np.asarray(getattr(b.state, n)), (mode, n))
            # get-or-put == guard-read + write-if-absent (in one round)
            mk = jnp.concatenate([keys[:128], k2[:128]])
            mv = jnp.concatenate([vals[:128] + 3, v2[:128]])
            em = b.execute_fn(("migrate",))
            b.state, gval, gfound, gcode, ges = em(b.state, mk, mv, ones)
            out_r, found_r, _ = a.read(mk)
            a.write(mk, mv, ones & ~found_r)
            np.testing.assert_array_equal(np.asarray(gfound),
                                          np.asarray(found_r))
            np.testing.assert_array_equal(np.asarray(gval),
                                          np.asarray(out_r))
            assert int(jnp.sum(gcode == W_SKIP)) == 128
            assert int(jnp.sum(gcode == W_INSERT)) == 128
            for n in ("keys", "vals", "meta", "csum"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(a.state, n)),
                    np.asarray(getattr(b.state, n)), (mode, n))
        print("execute_fn parity OK")
    """))


def test_sharded_l1_locality_tier_parity_and_elision():
    """Locality tier on the shard_map/all_to_all backend (DESIGN.md §9):
    the L1-fronted ShardedDHT must be bitwise-identical to the cacheless
    one on a mixed read/write stream, serve real L1 hits on repeats,
    invalidate across remote writes, and the self-traffic elision must
    show up in the wire accounting (the local shard's block never crosses
    the fabric)."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import DHTConfig, L1Config
        from repro.core.distributed import ShardedDHT, make_mesh_1d

        mesh = make_mesh_1d(8)
        rng = np.random.default_rng(7)
        keys = jnp.asarray(rng.integers(0, 2**31, size=(256, 20)), jnp.uint32)
        vals = jnp.asarray(rng.integers(0, 2**31, size=(256, 26)), jnp.uint32)
        cfg = DHTConfig(n_shards=8, buckets_per_shard=512, capacity=64)
        a = ShardedDHT.create(mesh, cfg)
        b = ShardedDHT.create(mesh, cfg, l1cfg=L1Config(n_sets=128, n_ways=4))
        a.write(keys, vals); b.write(keys, vals)

        # elision wire accounting: a read round ships (S-1) blocks per
        # device, both legs (the self block is elided padding)
        o1, f1, s1 = a.read(keys)
        send, reply = (20 + 1 + 1), (26 + 1 + 1)
        assert int(s1["wire_words"]) == 8 * (7 * 64) * (send + reply), \\
            int(s1["wire_words"])

        o2, f2, s2 = b.read(keys)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
        assert bool(f1.all())
        # cached round adds the 3 coherence reply lanes, nothing else
        assert int(s2["wire_words"]) == 8 * (7 * 64) * (send + reply + 3)
        assert int(s2["l1_hits"]) == 0

        o3, f3, s3 = b.read(keys)
        assert int(s3["l1_hits"]) > 128, int(s3["l1_hits"])
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o3))

        # a write through the sharded engine invalidates remotely cached
        # lines via the watermark piggyback
        b.write(keys[:64], vals[:64] + 9); a.write(keys[:64], vals[:64] + 9)
        o4, f4, s4 = b.read(keys)
        o5, f5, s5 = a.read(keys)
        np.testing.assert_array_equal(np.asarray(o4), np.asarray(o5))
        assert bool((np.asarray(o4[:64]) == np.asarray(vals[:64] + 9)).all())
        for n in ("keys", "vals", "meta", "csum"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a.state, n)),
                np.asarray(getattr(b.state, n)), n)

        # satellite: the all-true valid mask is cached per batch shape
        assert a._ones(256) is a._ones(256)
        assert a._ones((64, 4)) is a._ones((64, 4))
        # read_many refreshes the coherence table without disturbing parity
        many = keys.reshape(64, 4, 20)
        om, fm, _ = b.read_many(many)
        assert bool(fm.all())
        np.testing.assert_array_equal(
            np.asarray(om.reshape(256, 26)), np.asarray(o4))
        print("sharded locality tier OK")
    """))


def test_sharded_telemetry_parity_and_merge():
    """DESIGN.md §10 on the shard_map backend: the wrapper-side flush
    must agree bit-for-bit with the stats the caller saw, keep counting
    across jit-cache-hit calls (the PR 3 failure mode), and per-process
    snapshots must merge additively."""
    print(_run("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro import obs
        from repro.core import DHTConfig
        from repro.core.distributed import ShardedDHT, make_mesh_1d

        mesh = make_mesh_1d(8)
        rng = np.random.default_rng(5)
        keys = jnp.asarray(rng.integers(0, 2**31, size=(256, 20)), jnp.uint32)
        vals = jnp.asarray(rng.integers(0, 2**31, size=(256, 26)), jnp.uint32)
        d = ShardedDHT.create(mesh, DHTConfig(
            n_shards=8, buckets_per_shard=512, capacity=64))
        ws = d.write(keys, vals)
        out, found, r1 = d.read(keys)
        out, found, r2 = d.read(keys)   # jit cache hit — must still count
        assert bool(found.all())
        snap = d.telemetry_snapshot()
        c = snap["counters"]
        assert c["engine.rounds"] == 3, c
        assert c["engine.wire_words"] == (int(ws["wire_words"])
                                          + int(r1["wire_words"])
                                          + int(r2["wire_words"])), c
        assert c["dht.hits"] == int(r1["hits"]) + int(r2["hits"]), c
        assert c["engine.ops.write"] == 256 and c["engine.ops.read"] == 512
        assert snap["histograms"]["engine.round_latency_us"]["count"] == 3
        # cross-process aggregation: counters/histograms add
        merged = obs.merge_snapshots([snap, snap])
        assert merged["counters"]["engine.rounds"] == 6
        assert merged["histograms"]["engine.fill_frac"]["count"] == (
            2 * snap["histograms"]["engine.fill_frac"]["count"])
        json.dumps(snap)  # snapshot must be plain-JSON serializable
        print("sharded telemetry OK")
    """))


def test_sharded_train_step_matches_single_device():
    """The same train step on a 1-device and a 4-device mesh must produce
    allclose losses — the distribution is semantics-preserving."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced
        from repro.core.compat import make_mesh
        from repro.optim import AdamWConfig
        from repro.train import make_train_state, make_train_step
        from repro.launch.shardings import batch_shardings, params_shardings

        cfg = reduced(get_config("starcoder2-3b"), n_layers=2)
        params, opt = make_train_state(cfg, jax.random.PRNGKey(0))
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, cfg.vocab_size),
        }
        step = make_train_step(cfg, AdamWConfig(), donate=False)
        _, _, m1 = step(params, opt, batch)

        mesh = make_mesh((2, 2), ("data", "model"))
        p_sh = params_shardings(jax.eval_shape(lambda: params), mesh)
        b_sh = batch_shardings(jax.eval_shape(lambda: batch), mesh)
        params_d = jax.device_put(params, p_sh)
        batch_d = jax.device_put(batch, b_sh)
        step_d = make_train_step(cfg, AdamWConfig(), donate=False)
        with mesh:
            _, _, m2 = step_d(params_d, opt, batch_d)
        a, b = float(m1["loss"]), float(m2["loss"])
        assert abs(a - b) < 1e-3, (a, b)
        print("losses", a, b)
    """, devices=4)
    print(out)


def test_elastic_restart_across_meshes():
    """Checkpoint format is shard-count independent: params trained on one
    mesh restore onto a different mesh (elastic scaling, DESIGN.md §7)."""
    out = _run("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint import restore, save
        from repro.configs import get_config, reduced
        from repro.core.compat import make_mesh
        from repro.launch.shardings import params_shardings
        from repro.optim import AdamWConfig
        from repro.train import make_train_state, make_train_step

        cfg = reduced(get_config("mamba2-370m"), n_layers=2)
        params, opt = make_train_state(cfg, jax.random.PRNGKey(0))
        with tempfile.TemporaryDirectory() as d:
            save(d, 3, (params, opt))
            # "restart" onto a 8-device mesh: restore + apply new shardings
            mesh = make_mesh((4, 2), ("data", "model"))
            step, (p2, o2) = restore(d, (params, opt))
            p_sh = params_shardings(jax.eval_shape(lambda: p2), mesh)
            p2 = jax.device_put(p2, p_sh)
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            # and it still trains on the new mesh
            stepf = make_train_step(cfg, AdamWConfig(), donate=False)
            batch = {
                "tokens": jnp.zeros((8, 16), jnp.int32),
                "labels": jnp.zeros((8, 16), jnp.int32),
            }
            with mesh:
                _, _, m = stepf(p2, o2, batch)
            assert bool(jnp.isfinite(m["loss"]))
        print("elastic restart OK")
    """)
    print(out)


def test_dryrun_entry_smallest_cell():
    """End-to-end dry-run driver on the real 512-device production mesh for
    the smallest arch (proves the (16,16) and (2,16,16) meshes build and a
    full cell lowers+compiles through the public entry point)."""
    out = _run("""
        import os
        assert os.environ["XLA_FLAGS"].endswith("512")
        from repro.launch.dryrun import run_cell
        cell = run_cell("mamba2-370m", "decode_32k", multi_pod=True, verbose=False)
        assert cell["ok"], cell.get("error")
        assert cell["chips"] == 512
        print("multi-pod decode cell OK:",
              round(cell["memory"].get("temp_bytes", 0) / 1e9, 2), "GB temp")
    """, devices=512)
    print(out)


def test_sharded_write_retry_rows_lane_decides_the_retry():
    """Forced routing overflow (tiny static capacity): each write round's
    ``retry_rows`` lane equals the host count of its valid rows left
    ``W_DROPPED``, exactly those rows are re-issued, and the final codes
    hold no drop — for the single-copy and the replicated closures."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import obs
        from repro.core import DHTConfig, ring_create
        from repro.core.dht import W_DROPPED
        from repro.core.distributed import ShardedDHT, make_mesh_1d

        mesh = make_mesh_1d(8)
        rng = np.random.default_rng(7)
        n = 2048
        keys = jnp.asarray(rng.integers(0, 2**31, size=(n, 20)), jnp.uint32)
        vals = jnp.asarray(rng.integers(0, 2**31, size=(n, 26)), jnp.uint32)
        for replicas in (1, 2):
            d = ShardedDHT.create(
                mesh, DHTConfig(n_shards=8, n_replicas=replicas,
                                buckets_per_shard=4096, capacity=24),
                ring=ring_create(8))
            rounds = []
            dispatch = d._write_dispatch

            def recorded(k, v, valid, extra=()):
                stats = dispatch(k, v, valid, extra)
                rounds.append((np.asarray(valid), np.asarray(stats["code"]),
                               int(stats["retry_rows"])))
                return stats

            d._write_dispatch = recorded
            c0 = obs.get_registry().snapshot()["counters"]
            ws = d.write(keys, vals)
            c1 = obs.get_registry().snapshot()["counters"]
            assert len(rounds) >= 2, (replicas, len(rounds))
            for i, (valid, code, retry_rows) in enumerate(rounds):
                left = valid & (code == W_DROPPED)
                assert retry_rows == int(left.sum()), (replicas, i)
                if i + 1 < len(rounds):
                    assert retry_rows > 0
                    assert (rounds[i + 1][0] == left).all(), (replicas, i)
            assert rounds[-1][2] == 0, replicas
            assert not (np.asarray(ws["code"]) == W_DROPPED).any(), replicas
            assert int(ws["retry_rows"]) == 0 and int(ws["dropped"]) == 0
            assert int(ws["write_retries"]) == len(rounds) - 1
            delta = {k: c1.get(k, 0) - c0.get(k, 0)
                     for k in ("engine.rounds", "engine.host_fetches")}
            assert delta["engine.rounds"] == len(rounds), delta
            assert delta["engine.host_fetches"] == len(rounds), delta
            print("retry_rows OK", replicas, [r[2] for r in rounds])
    """))
