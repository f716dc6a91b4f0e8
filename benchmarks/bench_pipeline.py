"""Pipelined-engine bench (DESIGN.md §12): issue/commit overlap of the
sharded backend measured against the synchronous schedule.

Two miss-heavy surrogate workloads — Zipf(1.1) ids (hot head: keys
repeat across consecutive batches, exercising the store-to-load
forwarding hazard) and uniform ids over a range large enough that
nearly every probe misses — run a lookup-or-compute loop over the
jitted ``ShardedDHT`` wrappers at pipeline depth 1 (synchronous
read -> compute -> write per batch) and depth 2 (batch N+1's read round
issued before batch N's miss compute, writes lazily committed through a
double-buffered ``RoundQueue``).

The measured section runs in a fresh subprocess with 8 forced host
devices (the sharded tests' pattern — the parent's jax backend is
already initialized single-device) and single-threaded BLAS.  The
jitted closures dispatch asynchronously: ``read_async`` returns in
milliseconds while the round executes on the XLA threadpool, which is
the latency the depth-2 schedule hides behind the miss compute.

The miss compute models the paper's coupled solver as a cheap
deterministic value function plus a wall-clock stall (a sleep)
calibrated so a full-miss batch costs ~1.5x one read+write round — the
regime the POET coupling sits in.  The stall is a sleep rather than a
CPU spin deliberately: the quantity the pipeline hides is *latency the
solver does not spend on the DHT's cores* (network round-trips in the
paper's MPI setting; an external chemistry process here).  On a
small CI runner a CPU-bound solver and the XLA threadpool contend for
the same cores, total work is conserved, and no schedule can beat the
synchronous wall-clock — a spin-based "demo" would measure contention,
not pipelining.  The sleep keeps the cores free, so the measured
speedup is exactly the async-dispatch overlap the engine provides
(verified: a round issued before the stall shows ~0 residual wait at
commit).  The roofline bound for the calibrated ratio
(:func:`repro.roofline.analysis.overlap_speedup_bound`, the same
max-of-terms rule as ``Roofline.step_time``) is reported next to the
measured speedup.

Gates read by CI from the registry gauges this bench publishes
(``bench.pipeline.*``) and the depth-2 rows' ``derived`` column:
bit-for-bit parity of the pipelined schedule against the sequential
one, mean ``overlap_frac >= 0.3`` over the depth-2 commits, and
wall-clock ``speedup > 1.0``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from repro import obs
from repro.roofline.analysis import overlap_speedup_bound

from .common import Row, refuse_on_tpu

S = 8

_CHILD_CODE = r"""
import json, sys, time
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import DHTConfig
from repro.core.distributed import ShardedDHT, _state_shardings
from repro.core.layout import dht_create
from repro.core.pipeline import PendingWrites, RoundQueue

cfgj = json.loads(sys.argv[1])
N, B, TRIALS, RATIO = (cfgj["n"], cfgj["batches"], cfgj["trials"],
                       cfgj["ratio"])
S, KW, VW, HID = 8, 20, 26, 64
KEY_RANGE = 500_000

mesh = Mesh(np.array(jax.devices()), ("d",))
# sized so the ~B*N miss inserts sit at <20% occupancy: a dropped insert
# (full probe window) would break store-to-load forwarding parity -- the
# sync schedule re-misses the dropped key while the pipelined one
# forwards it as found -- so the child asserts dropped == 0 below
cfg = DHTConfig(n_shards=S, buckets_per_shard=1 << 14)
d = ShardedDHT.create(mesh, cfg)
_shardings = _state_shardings(mesh, d.state)


def reset():
    d.state = jax.device_put(dht_create(cfg), _shardings)


def make_keys(ids):
    # the paper's 80-byte keys, word-filled deterministically from the id
    n = ids.shape[0]
    keys = np.zeros((n, KW), np.uint32)
    keys[:, 0] = ids & 0xFFFFFFFF
    keys[:, 1] = ids >> 32
    for w in range(2, KW):
        keys[:, w] = (ids * (w * 2654435761 + 1)) & 0xFFFFFFFF
    return keys


_r = np.random.default_rng(7)
_w_in = _r.standard_normal((8, HID)).astype(np.float32)
_w_mid = (_r.standard_normal((HID, HID)) / np.sqrt(HID)).astype(np.float32)
_w_out = _r.standard_normal((HID, VW)).astype(np.float32)


def make_compute(stall_per_key_s):
    # host-side stand-in for the coupled solver: a cheap deterministic
    # value function per key row (duplicate rows compute duplicate
    # values, so in-batch duplicate writes carry no ordering ambiguity)
    # plus a wall-clock stall proportional to the miss count.  The stall
    # is a sleep, NOT spin: it models a solver whose latency -- an
    # external chemistry code, a licensed process, an accelerator the
    # DHT does not share -- is what the pipeline hides.  A CPU-bound
    # spin would be dishonest the other way on a small CI runner: with
    # the XLA threadpool and the solver contending for the same cores,
    # total work is conserved and NO schedule can beat sync wall-clock;
    # the sleep keeps the core free so the in-flight round genuinely
    # executes during it (verified: issuing a round then sleeping leaves
    # ~0 residual wait at commit).
    def fn(keys_np, n_miss):
        x = keys_np[:, :8].astype(np.float32) / 2.0 ** 16
        a = np.tanh(np.tanh(x @ _w_in) @ _w_mid)
        y = np.ascontiguousarray((a @ _w_out).astype(np.float32))
        if n_miss > 0:
            time.sleep(stall_per_key_s * n_miss)
        return y.view(np.uint32)
    return fn


# -- warm every closure (sync AND async cache keys), then calibrate ----
rng = np.random.default_rng(99)
wk_np = make_keys(rng.integers(0, KEY_RANGE, size=N).astype(np.int64))
wk = jnp.asarray(wk_np)
wv = jnp.asarray(rng.integers(0, 2 ** 31, size=(N, VW)), jnp.uint32)
wmask = jnp.ones((N,), bool)
d.write(wk, wv)
d.read(wk)
d.read_commit(d.read_async(wk, wmask))
d.write_commit(d.write_async(wk, wv, wmask))

ts = []
for _ in range(3):
    t0 = time.perf_counter()
    rnd = d.read_async(wk, wmask)
    w = d.write_async(wk, wv, wmask)
    jax.block_until_ready((rnd.outs, d.state.keys))
    rnd.committed = w.committed = True
    ts.append(time.perf_counter() - t0)
t_round = min(ts)

def mintime(fn, reps=3):
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


# calibrate the solver stall so a full-miss batch costs ~RATIO rounds
stall_per_key = RATIO * t_round / N
compute = make_compute(stall_per_key)
t_compute = mintime(lambda: compute(wk_np, N))


def sync_pass(kbs):
    reset()
    hits = misses = dropped = 0
    outs = []
    t0 = time.perf_counter()
    for kb, kb_np in kbs:
        vals, found, _ = d.read(kb)
        vals_np, found_np = np.asarray(vals), np.asarray(found)
        miss = ~found_np
        if miss.any():
            cvals = compute(kb_np, int(miss.sum()))
            out = np.where(miss[:, None], cvals, vals_np)
            wst = d.write(kb, jnp.asarray(cvals), jnp.asarray(miss))
            dropped += int(wst.get("dropped", 0))
        else:
            out = vals_np
        hits += int(found_np.sum())
        misses += int(miss.sum())
        outs.append((out, found_np))
    return time.perf_counter() - t0, outs, hits, misses, dropped


def pipe_pass(kbs):
    # the ShardedDHT twin of core.surrogate.lookup_or_compute_pipelined:
    # same promise -> issue-filtered read-ahead -> publish -> write ->
    # retire-after-next-commit schedule, over the jitted wrappers
    reset()
    pending = PendingWrites(VW)
    wq = RoundQueue(2, d.write_commit)
    acc = {"overlap": 0.0, "rounds": 0, "forwarded": 0,
           "hits": 0, "misses": 0, "dropped": 0}

    def note(st):
        acc["overlap"] += float(st["overlap_frac"])
        acc["rounds"] += 1
        acc["dropped"] += int(st.get("dropped", 0))

    def issue(i):
        kb, kb_np = kbs[i]
        conf = pending.conflicts(kb_np)
        return d.read_async(kb, jnp.asarray(~conf)), conf

    outs = []
    t0 = time.perf_counter()
    rd, conf = issue(0)
    to_retire = None
    for i, (kb, kb_np) in enumerate(kbs):
        vals, found, rstats = d.read_commit(rd)
        note(rstats)
        vals_np, found_np = np.asarray(vals), np.asarray(found)
        if conf.any():
            # resolve forwards BEFORE retiring: the conflicted rows'
            # values live in the pending table until this commit
            fvals = pending.resolve(kb_np, conf)
            vals_np = np.where(conf[:, None], fvals, vals_np)
            found_np = found_np | conf
            acc["forwarded"] += int(conf.sum())
        if to_retire is not None:
            # previous batch's write is issued AND the one read-ahead
            # round that could forward from it has now committed
            pending.retire(*to_retire)
            to_retire = None
        miss = ~found_np
        if miss.any():
            # promise BEFORE issuing the next read: its conflict filter
            # must know the keys this batch is about to write
            pending.promise(kb_np, miss)
        nxt = issue(i + 1) if i + 1 < len(kbs) else None
        if miss.any():
            # solver stall overlaps the in-flight read + queued write
            cvals = compute(kb_np, int(miss.sum()))
            out = np.where(miss[:, None], cvals, vals_np)
            pending.publish(kb_np, cvals, miss)
            w = d.write_async(kb, jnp.asarray(cvals), jnp.asarray(miss))
            to_retire = (kb_np, miss)
            done = wq.push(w)
            if done is not None:
                note(done)
        else:
            out = vals_np
        acc["hits"] += int(found_np.sum())
        acc["misses"] += int(miss.sum())
        outs.append((out, found_np))
        if nxt is not None:
            rd, conf = nxt
    for st in wq.drain():
        note(st)
    return time.perf_counter() - t0, outs, acc


results = {"t_round_s": t_round, "t_compute_s": t_compute,
           "stall_per_key_us": stall_per_key * 1e6}
for dist in ("zipf", "uniform"):
    rng_d = np.random.default_rng(23 if dist == "zipf" else 29)
    kbs = []
    for _ in range(B):
        if dist == "zipf":
            ids = rng_d.zipf(1.1, size=N) % KEY_RANGE
        else:
            ids = rng_d.integers(0, KEY_RANGE, size=N)
        kb_np = make_keys(ids.astype(np.int64))
        kbs.append((jnp.asarray(kb_np), kb_np))
    sync_pass(kbs)
    pipe_pass(kbs)                          # warm off the clock
    t_seq = outs_s = hits = misses = dropped = None
    for _ in range(TRIALS):
        t, outs_s, hits, misses, dropped = sync_pass(kbs)
        t_seq = t if t_seq is None else min(t_seq, t)
    t_pipe = outs_p = acc = None
    for _ in range(TRIALS):
        t, outs_p, acc = pipe_pass(kbs)
        t_pipe = t if t_pipe is None else min(t_pipe, t)
    # forwarding parity is only meaningful drop-free (a dropped insert
    # re-misses in the sync schedule but forwards in the pipelined one);
    # the table is sized for zero drops, so any drop is a loud failure
    assert dropped == 0 and acc["dropped"] == 0, \
        f"{dist}: table overflow (sync={dropped} pipe={acc['dropped']})"
    parity = (acc["hits"] == hits and acc["misses"] == misses)
    for (o_s, f_s), (o_p, f_p) in zip(outs_s, outs_p):
        parity &= bool(np.array_equal(o_s, o_p))
        parity &= bool(np.array_equal(f_s, f_p))
    results[dist] = {
        "t_seq_s": t_seq, "t_pipe_s": t_pipe,
        "speedup": t_seq / t_pipe if t_pipe > 0 else 0.0,
        "overlap_frac": acc["overlap"] / max(acc["rounds"], 1),
        "rounds": acc["rounds"], "forwarded": acc["forwarded"],
        "hits": hits, "misses": misses, "parity": bool(parity),
    }
print("RESULT " + json.dumps(results))
"""


def _run_child(child_cfg: dict, devices: int = S) -> dict:
    """Run the measured section in a fresh process with forced host
    devices and single-threaded BLAS (the parent's backend is already
    initialized single-device)."""
    refuse_on_tpu("bench_pipeline")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={devices}")
    env.setdefault("JAX_PLATFORMS", "cpu")
    for v in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
        env[v] = "1"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get(
        "PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD_CODE, json.dumps(child_cfg)],
        capture_output=True, text=True, env=env, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(
            f"pipeline subprocess failed:\n{proc.stderr[-2000:]}")
    for line in reversed(proc.stdout.strip().splitlines()):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT line in child output:\n{proc.stdout}")


def run(quick: bool = True):
    n = 4096 if quick else 8192
    n_batches = 6 if quick else 8
    res = _run_child({"n": n, "batches": n_batches, "trials": 3,
                      "ratio": 1.5})
    bound = overlap_speedup_bound(res["t_compute_s"], res["t_round_s"])
    rows = []
    for dist in ("zipf", "uniform"):
        r = res[dist]
        overlap, speedup = r["overlap_frac"], r["speedup"]
        obs.set_gauge(f"bench.pipeline.overlap_frac.{dist}", overlap)
        obs.set_gauge(f"bench.pipeline.speedup.{dist}", speedup)
        obs.set_gauge(f"bench.pipeline.speedup_bound.{dist}",
                      bound["speedup_bound"])
        rows.append(Row(
            f"pipeline/{dist}/S{S}/depth1",
            r["t_seq_s"] / (n * n_batches) * 1e6,
            f"wall_ms={r['t_seq_s'] * 1e3:.1f};hits={r['hits']};"
            f"misses={r['misses']}"))
        rows.append(Row(
            f"pipeline/{dist}/S{S}/depth2",
            r["t_pipe_s"] / (n * n_batches) * 1e6,
            f"speedup={speedup:.2f};overlap_frac={overlap:.3f};"
            f"rounds={r['rounds']};forwarded={r['forwarded']};"
            f"speedup_bound={bound['speedup_bound']:.2f};"
            f"hideable_frac={bound['hideable_frac']:.2f};"
            f"parity={'ok' if r['parity'] else 'MISMATCH'}"))
    return rows


def main(quick: bool = True):
    for r in run(quick):
        print(r.csv())


if __name__ == "__main__":
    main(False)
