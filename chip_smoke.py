"""Smoke run of the sharded DHT on a TPU, through the entry points a user
calls.

    python chip_smoke.py             # one chip: phases A-D
    python chip_smoke.py --chips 4   # four chips, one shard each: A-C

The table has the paper's geometry: 80 B keys, 104 B values, lock-free
mode, ``buckets_per_shard = 1 << 22`` (768 MiB of slab per shard).

- A  load 1,048,576 distinct keys in batches of 65,536 through
     ``ShardedDHT.write`` and read them all back, plus 65,536 keys that
     were never written, against a plain dict.
- B  one 95 % read / 5 % write batch through the op engine's execute
     closure, on a snapshot of the table, against the dict.
- C  an L1-fronted table, loaded the same way, reads a seeded Zipf stream
     twice: same answers as the uncached table, with L1 hits.
- D  a few steps of the POET example with and without the DHT.

Every check raises on failure, so any failed phase exits non-zero.  The
script refuses to run without a TPU.  The printed seconds are smoke
timings (first call includes compilation), not measurements.  The last
line of stdout is the JSON verdict with the device JAX reports.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent

KEY_WORDS, VAL_WORDS = 20, 26          # 80 B keys, 104 B values
N_KEYS = 1 << 20
BATCH = 1 << 16
BUCKETS_PER_SHARD = 1 << 22
L1_STREAM_ZIPF = 1.2
POET_STEPS = 3
POET_TOL = dict(rtol=2e-2, atol=1e-4)  # tests/test_system.py's bound


def log(msg: str) -> None:
    print(msg, flush=True)


def smoke_timing(phase: str, first_s: float, steady_s: list[float]) -> None:
    msg = (f"[{phase}] smoke timing, not a measurement: first call "
           f"{first_s!r} s (compile included)")
    if steady_s:
        msg += (f", steady {sum(steady_s) / len(steady_s)!r} s/call over "
                f"{len(steady_s)} calls")
    log(msg)


class DictRef:
    """The plain reference: a Python dict from key bytes to value bytes."""

    def __init__(self):
        self.d: dict[bytes, bytes] = {}

    @staticmethod
    def _rows(a):
        a = np.ascontiguousarray(a, np.uint32)
        return a.view(np.dtype((np.void, 4 * a.shape[1]))).ravel().tolist()

    def put(self, keys, vals) -> None:
        self.d.update(zip(self._rows(keys), self._rows(vals)))

    def drop(self, keys) -> None:
        for k in self._rows(keys):
            self.d.pop(k, None)

    def get(self, keys):
        """-> (found (n,) bool, vals (n, VW) uint32, 0 where absent)."""
        zero = bytes(4 * VAL_WORDS)
        got = [self.d.get(k) for k in self._rows(keys)]
        found = np.array([g is not None for g in got], bool)
        vals = np.frombuffer(b"".join(g or zero for g in got), np.uint32)
        return found, vals.reshape(len(got), VAL_WORDS)


def make_keys(rng, ids):
    """Distinct keys: word 0 is the id, the other words are random."""
    k = rng.integers(0, 2**32, size=(len(ids), KEY_WORDS), dtype=np.uint32)
    k[:, 0] = ids
    return k


def make_vals(rng, n):
    return rng.integers(0, 2**32, size=(n, VAL_WORDS), dtype=np.uint32)


def load(table, keys, vals, batch):
    """Write every batch; -> summed write stats and per-call seconds."""
    totals = {"inserted": 0, "updated": 0, "evicted": 0, "dropped": 0}
    secs = []
    for lo in range(0, keys.shape[0], batch):
        t = time.perf_counter()
        st = table.write(jnp.asarray(keys[lo:lo + batch]),
                         jnp.asarray(vals[lo:lo + batch]))
        for k in totals:
            totals[k] += int(st[k])
        secs.append(time.perf_counter() - t)
    return totals, secs


def read_all(table, keys, batch):
    """-> (vals, found, summed stats, per-call seconds) as numpy."""
    vals, found, secs = [], [], []
    totals = {"hits": 0, "misses": 0, "mismatches": 0, "dropped": 0,
              "l1_hits": 0}
    for lo in range(0, keys.shape[0], batch):
        t = time.perf_counter()
        v, f, st = table.read(jnp.asarray(keys[lo:lo + batch]))
        vals.append(np.asarray(v))
        found.append(np.asarray(f))
        for k in totals:
            if k in st:
                totals[k] += int(st[k])
        secs.append(time.perf_counter() - t)
    return np.concatenate(vals), np.concatenate(found), totals, secs


def compiled_text(fn, *args) -> str:
    return fn.lower(*args).compile().as_text()


def phase_a(table, rng, ref, n_keys, batch):
    """Load and read back; -> (keys, vals, which keys the table holds)."""
    keys = make_keys(rng, np.arange(n_keys, dtype=np.uint32))
    vals = make_vals(rng, n_keys)
    w, wsecs = load(table, keys, vals, batch)
    ref.put(keys, vals)
    smoke_timing("A write", wsecs[0], wsecs[1:])
    assert w["dropped"] == 0, w
    assert w["updated"] == 0, w                  # every key is new
    assert w["inserted"] + w["evicted"] == n_keys, w

    got, found, r, rsecs = read_all(table, keys, batch)
    smoke_timing("A read", rsecs[0], rsecs[1:])
    exp_found, exp_vals = ref.get(keys)
    assert exp_found.all()
    assert (got[found] == exp_vals[found]).all(), "found value != written"
    lost = keys[~found]
    assert lost.shape[0] == w["evicted"], (lost.shape[0], w)
    assert r["hits"] == int(found.sum()) and r["dropped"] == 0, r

    absent = make_keys(rng, np.arange(n_keys, n_keys + batch,
                                      dtype=np.uint32))
    _, afound, ar, _ = read_all(table, absent, batch)
    assert not afound.any() and ar["hits"] == 0, ar
    ref.drop(lost)
    log(f"[A] ok: wrote {n_keys} keys (inserted {w['inserted']}, evicted "
        f"{w['evicted']}, dropped {w['dropped']}); read back "
        f"{int(found.sum())} equal to the dict, {lost.shape[0]} lost = "
        f"evicted; {batch} never-written keys all missed")
    return keys, vals, found


def phase_b(table, rng, ref, keys, held, batch):
    """One mixed 95 % read / 5 % write round on a snapshot."""
    from repro.core import OP_READ, OP_WRITE
    from repro.core.dht import W_DROPPED, W_EVICT, W_INSERT, W_UPDATE

    n_keys = keys.shape[0]
    n_w = batch * 5 // 100
    is_w = np.zeros(batch, bool)
    is_w[rng.choice(batch, n_w, replace=False)] = True
    # reads: held keys plus 1 in 10 never written; writes: half updates of
    # held keys, half new keys; no key is both read and written
    pick = rng.permutation(np.nonzero(held)[0])
    n_r = batch - n_w
    n_r_abs = n_r // 10
    n_upd = n_w // 2
    read_keys = np.concatenate([
        keys[pick[:n_r - n_r_abs]],
        make_keys(rng, np.arange(2 * n_keys, 2 * n_keys + n_r_abs,
                                 dtype=np.uint32))])
    write_keys = np.concatenate([
        keys[pick[n_r:n_r + n_upd]],
        make_keys(rng, np.arange(3 * n_keys, 3 * n_keys + n_w - n_upd,
                                 dtype=np.uint32))])
    mk = np.empty((batch, KEY_WORDS), np.uint32)
    mk[~is_w] = read_keys[rng.permutation(n_r)]
    mk[is_w] = write_keys
    mv = make_vals(rng, batch)
    op = np.where(is_w, OP_WRITE, OP_READ).astype(np.int32)

    exp_found, exp_vals = ref.get(mk)
    snap = jax.tree.map(jnp.copy, table.state)
    execute = table.execute_fn(("read", "write"))
    args = (jnp.asarray(mk), jnp.asarray(mv), jnp.ones((batch,), bool),
            jnp.asarray(op))
    t = time.perf_counter()
    snap, out, found, code, es = execute(snap, *args)
    found, out, code = np.asarray(found), np.asarray(out), np.asarray(code)
    smoke_timing("B mixed", time.perf_counter() - t, [])

    r = ~is_w
    assert (found[r] == exp_found[r]).all(), "mixed read presence != dict"
    assert (out[r & found] == exp_vals[r & found]).all(), "mixed read value"
    assert int(es["dropped"]) == 0 and not (code[is_w] == W_DROPPED).any()
    upd = is_w & exp_found
    assert (code[upd] == W_UPDATE).all(), "update of a held key"
    assert np.isin(code[is_w & ~exp_found], (W_INSERT, W_EVICT)).all()
    n_evict = int((code[is_w] == W_EVICT).sum())

    # read the whole batch back from the post-round snapshot
    rfn = table.read_fn(snap)
    _, back, bfound, _ = rfn(snap, args[0], args[2])
    back, bfound = np.asarray(back), np.asarray(bfound)
    want = np.where(is_w[:, None], mv, exp_vals)
    want_found = is_w | exp_found
    lost = want_found & ~bfound
    assert not (bfound & ~want_found).any(), "a key appeared from nowhere"
    assert (back[bfound] == want[bfound]).all(), "read-back != dict"
    assert int(lost.sum()) <= n_evict, (int(lost.sum()), n_evict)
    log(f"[B] ok: {n_r} reads ({int(found[r].sum())} hits) and {n_w} "
        f"writes ({int(upd.sum())} updates, {n_evict} evictions) in one "
        f"round equal the dict; read-back lost {int(lost.sum())} "
        f"<= evictions")
    del snap


def phase_c(mesh, cfg, uncached, rng, ref, keys, vals, batch):
    """L1-fronted table vs the uncached one on a skewed stream."""
    from repro.core import L1Config
    from repro.core.distributed import ShardedDHT

    table = ShardedDHT.create(mesh, cfg, l1cfg=L1Config())
    w, _ = load(table, keys, vals, batch)
    assert w["dropped"] == 0, w
    ranks = (rng.zipf(L1_STREAM_ZIPF, size=batch) - 1) % keys.shape[0]
    stream = keys[rng.permutation(keys.shape[0])[ranks]]
    want, want_found, _, _ = read_all(uncached, stream, batch)
    ref_found, ref_vals = ref.get(stream)
    assert (want_found == ref_found).all()
    assert (want[want_found] == ref_vals[want_found]).all()
    hits, secs = [], []
    for _ in range(2):
        got, found, st, s = read_all(table, stream, batch)
        assert (found == want_found).all(), "L1 presence != uncached"
        assert (got == want).all(), "L1 value != uncached"
        hits.append(st["l1_hits"])
        secs += s
    smoke_timing("C cached read", secs[0], secs[1:])
    assert hits[1] > 0, hits
    log(f"[C] ok: Zipf({L1_STREAM_ZIPF}) stream of {batch} keys "
        f"({len(np.unique(ranks))} distinct) read twice through the L1 "
        f"table equals the uncached table; l1_hits per pass {hits}")
    return table


def phase_d():
    """A few POET steps with and without the DHT."""
    sys.path.insert(0, str(ROOT))
    from examples.poet_reactive_transport import PoetConfig, run_simulation

    cfg = PoetConfig(n_steps=POET_STEPS)
    t = time.perf_counter()
    ref = run_simulation(cfg, use_dht=False)
    t_ref = time.perf_counter() - t
    t = time.perf_counter()
    dht = run_simulation(cfg, use_dht=True)
    t_dht = time.perf_counter() - t
    a, b = np.asarray(dht["conc"]), np.asarray(ref["conc"])
    err = float(np.abs(a - b).max())
    assert np.isfinite(a).all()
    np.testing.assert_allclose(a, b, **POET_TOL)
    assert dht["hit_rate"] > 0, dht["hit_rate"]
    assert dht["mismatches"] == 0, dht["mismatches"]
    log(f"[D] smoke timing, not a measurement: POET {cfg.nx}x{cfg.ny} "
        f"{cfg.n_steps} steps, without DHT {t_ref!r} s, with DHT "
        f"{t_dht!r} s (compile included)")
    log(f"[D] ok: hit rate {dht['hit_rate']!r}, chemistry calls "
        f"{dht['chem_calls']} vs {ref['chem_calls']}, max |dconc| {err!r}")


def check_compiled(table, keys, vals, batch, n_chips, cached):
    """The compiled rounds hold the Pallas kernels (and, across chips,
    the all_to_all exchange)."""
    k = jnp.asarray(keys[:batch])
    v = jnp.asarray(vals[:batch])
    ones = jnp.ones((batch,), bool)
    texts = {"write": compiled_text(table.write_fn(), table.state, k, v,
                                    ones),
             "read": compiled_text(table.read_fn(), table.state, k, ones),
             "cached read": compiled_text(cached.read_cached_fn(),
                                          cached.state, cached.l1, k, ones)}
    for name, txt in texts.items():
        assert "tpu_custom_call" in txt, f"no Pallas kernel in {name}"
        if n_chips > 1:
            assert "all-to-all" in txt, f"no all-to-all in {name}"
    log("[HLO] ok: compiled write, read and cached-read rounds contain "
        "tpu_custom_call" + (" and all-to-all" if n_chips > 1 else ""))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
                 "this script runs only on the chip")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} devices")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import DHTConfig
    from repro.core.distributed import ShardedDHT, make_mesh_1d
    from repro.launch.compile_cache import configure_compile_cache

    log(f"compile cache: {configure_compile_cache()}")
    mesh = make_mesh_1d(args.chips)
    cfg = DHTConfig(key_words=KEY_WORDS, val_words=VAL_WORDS,
                    n_shards=args.chips, buckets_per_shard=BUCKETS_PER_SHARD,
                    mode="lockfree")
    log(f"device {devices[0].device_kind} x{args.chips}; {cfg}; "
        f"slab {cfg.shard_bytes} B per shard")
    rng = np.random.default_rng(args.seed)
    ref = DictRef()

    table = ShardedDHT.create(mesh, cfg)
    placed = table.state.keys.sharding.device_set
    assert len(placed) == args.chips, placed
    log(f"slabs on {len(placed)} distinct devices")
    keys, vals, held = phase_a(table, rng, ref, N_KEYS, BATCH)
    phase_b(table, rng, ref, keys, held, BATCH)
    cached = phase_c(mesh, cfg, table, rng, ref, keys, vals, BATCH)
    check_compiled(table, keys, vals, BATCH, args.chips, cached)
    del cached
    if args.chips == 1:
        phase_d()

    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
