"""One run of one cell: build, preload, warm, measure, check, report.

:func:`run` does everything after the look for a chip, so that the tests
can drive it on the CPU at a small size.  Phases, in order:

1. table: ``ShardedDHT.create`` on a one-axis mesh of the cell's chips;
2. preload: every id of the configuration written once through the
   cell's entry at the cell's batch, then read back (presence only) to
   learn which keys the preload's evictions removed;
3. pool: the cell's distinct rounds of keys built on the device from the
   seed;
4. warm-up: ``warm_rounds`` rounds through the entry (checked like the
   others, not timed);
5. window: a closed loop of rounds for ``seconds``; each round is timed
   from the call into the entry to its outputs and the table state being
   ready.  Around the timed call the harness makes the round's values
   (``batch`` span) and reduces the values it read to a stamp and a
   whole-row flag per row (``check`` span), each waited for before the
   next call;
6. read-back: every id read through the entry once the window is over;
7. check: the reference replays every round from the seed and compares
   what the table answered, every found flag, code and value
   (``checks``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import shutil
import time

import numpy as np

from . import devtrace, traffic
from .spec import ROOT, Cell, load_module

FAULTS = ("control", "stale_state", "half_batch", "alter_answer")


class CompileEvents:
    """Counts JAX's compile-cache hits, cache writes (one per compile that
    missed) and backend compile events, which JAX also records for a
    program loaded from the cache (one listener pair per process; read
    deltas with :meth:`snapshot`)."""

    _instance = None

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    @classmethod
    def get(cls) -> "CompileEvents":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "cache_writes": self.cache_writes}


@dataclasses.dataclass
class RoundLog:
    """What the window keeps per round: device refs, fetched after."""

    pool_slot: list = dataclasses.field(default_factory=list)
    found: list = dataclasses.field(default_factory=list)
    code: list = dataclasses.field(default_factory=list)
    dropped: list = dataclasses.field(default_factory=list)
    stamp: list = dataclasses.field(default_factory=list)   # value_check
    whole: list = dataclasses.field(default_factory=list)
    seconds: list = dataclasses.field(default_factory=list)


def _log(msg: str) -> None:
    print(msg, flush=True)


def _faulty(entry, fault: str | None, pool: traffic.HostPool, batch: int):
    """Wrap ``entry.round`` with a planted fault (tests and the chip's
    fault runs only; the benchmark's own runs never plant one)."""
    if fault in (None, "control"):
        return entry
    import jax
    import jax.numpy as jnp

    inner = entry.round

    def round_(b):
        if fault == "stale_state":            # the step returns its state unchanged
            before = entry.dht.state
            out = inner(b)
            entry.dht.state = before
            return out
        if fault == "half_batch":             # the second half is left out
            half = jnp.arange(batch) < batch // 2
            b = dict(b, valid=jax.device_put(half & b["valid"],
                                             b["valid"].sharding))
            return inner(b)
        out = dict(inner(b))                  # alter_answer
        is_w = pool.ops[b["slot"]] == traffic.OP_WRITE
        if "vals" in out and (~is_w).any():
            row = int(np.argmax(~is_w))
            out["vals"] = out["vals"].at[row, 1].add(jnp.uint32(1))
        else:
            out["code"] = out["code"].at[0].set(7)
        return out

    entry.round = round_
    return entry


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, fault: str | None = None, peaks: dict | None = None,
        keep_trace: str | None = None) -> dict:
    """One run; returns the result dict (the contract's last line)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from repro.core import DHTConfig
    from repro.core.distributed import ShardedDHT, make_mesh_1d, shard_spec

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    wl = cell.workload
    batch = int(wl["batch"])
    n_ids = cell.n_ids
    events = CompileEvents.get()

    # -- 1. table ------------------------------------------------------------
    table = dict(cell.table, n_shards=cell.chips)
    cfg = DHTConfig(**table)
    mesh = make_mesh_1d(cell.chips)
    bsh = NamedSharding(mesh, shard_spec(mesh))
    dht = ShardedDHT.create(mesh, cfg)
    entry = load_module("entries", wl["entry"]).Entry(dht, wl)
    _log(f"table: {cfg}; slab {cfg.shard_bytes} B per shard; "
         f"entry {wl['entry']}")

    pool = traffic.draw_pool(seed, wl, n_ids,
                             load_module("traffic", wl["keys"]["dist"]))
    salts = pool.salts
    salt_k = jnp.uint32(salts.key)
    salt_v = jnp.uint32(salts.value)
    kw, vw = cfg.key_words, cfg.val_words
    gen_keys = jax.jit(lambda i, s: traffic.key_words(jnp, i, kw, s),
                       out_shardings=bsh)
    gen_vals = jax.jit(lambda i, s: traffic.value_words(jnp, i, vw, s),
                       out_shardings=bsh)
    gen_round_vals = jax.jit(
        lambda r, s: traffic.value_words(
            jnp, traffic.round_stamps(jnp, r, batch), vw, s),
        out_shardings=bsh)
    check_vals = jax.jit(lambda v, s: traffic.value_check(jnp, v, s),
                         out_shardings=(bsh, bsh))
    ones = jax.device_put(jnp.ones((batch,), bool), bsh)
    zeros_vals = (jax.device_put(jnp.zeros((batch, vw), jnp.uint32), bsh)
                  if "vals" in entry.fields else None)

    def put(a):
        return jax.device_put(a, bsh)

    ref = load_module("references", cell.config["reference"]).Reference(
        n_ids)

    # -- 2. preload and presence read-back -----------------------------------
    t = time.perf_counter()
    chunks = list(traffic.preload_chunks(n_ids, batch))
    codes = []
    for i, (ids, valid) in enumerate(chunks):
        before = dht.state
        codes.append(entry.write_rows(
            gen_keys(put(ids.astype(np.uint32)), salt_k),
            gen_vals(put(traffic.preload_stamps(ids)), salt_v), put(valid)))
        if fault == "control" and i == len(chunks) - 1:
            # the control: the last preload round is acked, then lost (an
            # ack sent before the write lands, as a pipeline without its
            # store buffer would); breaks "a read returns the last write"
            jax.block_until_ready(codes[-1])
            dht.state = before
        del before
    for (ids, valid), code in zip(chunks, jax.device_get(codes)):
        ref.write(ids[valid], traffic.preload_stamps(ids)[valid], code[valid])
    found = [entry.read_rows(gen_keys(put(ids.astype(np.uint32)), salt_k),
                             put(valid), zeros_vals)[0]
             for ids, valid in chunks]
    for (ids, valid), f in zip(chunks, jax.device_get(found)):
        ref.read(ids[valid], f[valid])
    del codes, found
    _log(f"preload: {n_ids} ids in {len(chunks)} rounds of {batch}, "
         f"{ref.evictions} evictions, {ref.losses} keys lost, "
         f"{time.perf_counter() - t!r} s")

    # -- 3. pool of keys on the device ---------------------------------------
    dev_pool = []
    for p in range(pool.rounds):
        b = {"slot": p, "valid": ones,
             "keys": gen_keys(put(pool.ids[p].astype(np.uint32)), salt_k)}
        if "op" in entry.fields:
            b["op"] = put(entry.op_tags(pool.ops[p] == traffic.OP_WRITE))
        dev_pool.append(b)
    jax.block_until_ready(dev_pool)
    entry = _faulty(entry, fault, pool, batch)

    log = RoundLog()
    ann = jax.profiler.TraceAnnotation

    def one_round(r: int) -> None:
        with ann("batch"):
            p = r % pool.rounds
            b = dev_pool[p]
            if "vals" in entry.fields:
                b = dict(b, vals=gen_round_vals(np.uint32(r), salt_v))
                jax.block_until_ready(b["vals"])
        with ann("call"):
            t_a = time.perf_counter()
            out = entry.round(b)
        with ann("block"):
            jax.block_until_ready((out, entry.live()))
            t_b = time.perf_counter()
        with ann("check"):
            log.pool_slot.append(p)
            log.seconds.append(t_b - t_a)
            log.found.append(out.get("found"))
            log.code.append(out.get("code"))
            log.dropped.append(out.get("dropped"))
            if "vals" in out:
                chk = check_vals(out["vals"], salt_v)
                jax.block_until_ready(chk)
            else:
                chk = (None, None)
            log.stamp.append(chk[0])
            log.whole.append(chk[1])

    # -- 4. warm-up ----------------------------------------------------------
    n_warm = int(wl.get("warm_rounds", 2))
    for r in range(n_warm):
        one_round(r)
    warm = events.snapshot()

    # -- 5. window -----------------------------------------------------------
    trace_dir = ROOT / ".bench_out" / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        prof = jax.profiler.trace(str(trace_dir))
    else:
        prof = contextlib.nullcontext()
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    r = n_warm
    with prof:
        while True:
            one_round(r)
            r += 1
            window_s = time.perf_counter() - t_window
            if window_s >= seconds:
                break
    n_rounds = r - n_warm
    during = events.snapshot()
    in_window = during["compiles"] - warm["compiles"]

    # device memory, read before anything else is allocated
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in mesh.devices.flat)

    # -- 6. read-back of every id --------------------------------------------
    t_rb = time.perf_counter()
    back = []
    for ids, valid in chunks:
        f, v = entry.read_rows(gen_keys(put(ids.astype(np.uint32)), salt_k),
                               put(valid), zeros_vals)
        back.append(jax.device_get((f,) + check_vals(v, salt_v)))
    found_h, code_h, stamp_h, whole_h = jax.device_get(
        (log.found, log.code, log.stamp, log.whole))
    dropped_h = [0 if d is None else int(d) for d in jax.device_get(log.dropped)]
    del dev_pool, entry, dht
    readback_s = time.perf_counter() - t_rb

    # -- 7. check --------------------------------------------------------------
    t_chk = time.perf_counter()
    wrong_before = None
    for i, p in enumerate(log.pool_slot):
        if i == n_warm:
            wrong_before = _wrong(ref)
        ref.round(pool.ids[p], pool.ops[p] == traffic.OP_WRITE,
                  traffic.round_stamps(np, i, batch), found=found_h[i],
                  code=code_h[i], stamp=stamp_h[i], whole=whole_h[i])
    window_wrong = _wrong(ref) - (wrong_before or 0)
    for (ids, valid), (f, st, wh) in zip(chunks, back):
        ref.read(ids[valid], f[valid], st[valid], wh[valid])
    checks = ref.checks(dropped_reported=sum(dropped_h))
    checks["compiles_in_window"] = {"value": in_window, "limit": 0}
    check_s = time.perf_counter() - t_chk
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    attempted = n_rounds * batch
    failed = min(attempted, sum(dropped_h[n_warm:]) + window_wrong)
    lat = np.asarray(log.seconds[n_warm:])
    slow = np.argsort(lat)[::-1][:5]
    _log(f"window: {n_rounds} rounds of {batch} ops in {window_s!r} s; "
         f"compiles inside the window: {in_window}")
    _log(f"window: round median {float(np.median(lat)) * 1e3!r} ms; "
         f"slowest (round, ms): "
         f"{[(int(n_warm + i), float(lat[i]) * 1e3) for i in slow]}")
    _log(f"check: {ref.summary()}; read-back {readback_s!r} s, "
         f"reference {check_s!r} s")
    _log(f"compile cache: hits {during['cache_hits']}, entries written "
         f"{during['cache_writes']}; programs compiled or loaded "
         f"{during['compiles']} ({during['compile_s']!r} s)")

    device = jax.devices()[0]
    result = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": {},
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
    }
    if not trace:
        m = {"ops_per_s": ((attempted - failed) / window_s, "ops/s"),
             "round_p95_ms": (float(np.percentile(lat, 95)) * 1e3, "ms"),
             "setup_s": (setup_s, "s")}
        for spec_m in cell.end_to_end:
            v, unit = m[spec_m["name"]]
            result["metrics"][spec_m["name"]] = {"value": v, "unit": unit}
    else:
        path = devtrace.find_xplane(str(trace_dir))
        if keep_trace:
            shutil.copy(path, keep_trace)
        tr = devtrace.load(path)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = MetricContext(trace=tr, rounds=n_rounds, batch=batch,
                            config=cell.config, workload=wl, table=table,
                            peaks=peaks or {})
        for spec_m in cell.per_layer:
            v = load_module("metrics", spec_m["name"]).read(ctx)
            if v is not None:
                result["metrics"][spec_m["name"]] = {"value": v,
                                                     "unit": spec_m["unit"]}
        result["device"]["busy_s"] = devtrace.busy_s(tr)
        result["device"]["window_s"] = tr.window_s
        result["breakdown"] = devtrace.breakdown(tr)
    result["checks"] = checks
    return result


def _wrong(ref) -> int:
    return ref.wrong_values + ref.found_absent + ref.wrong_codes


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reader may read."""

    trace: devtrace.Trace
    rounds: int           # rounds in the traced window
    batch: int
    config: dict
    workload: dict
    table: dict           # the DHTConfig fields the table was built with
    peaks: dict
