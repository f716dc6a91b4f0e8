"""Calibrate the α-β round-cost model and predict at scale (DESIGN.md §11).

Sweep → fit → validate → predict:

1. **Sweep** measured rounds over (n_items, n_shards, op kind, key skew)
   on the eager-layout engine with count-driven capacity baked per point
   (the jitted program matches what the prologue would size).  Every
   timed median lands in the trace ring as one RoundEvent via
   ``record_round(..., dur=t_med)``.
2. **Fit** ``obs.costmodel.fit`` over the sweep events (non-negative
   least squares, relative-residual weighting).
3. **Validate** against held-out shard counts the fit never saw: the
   fully analytic prediction (simulated capacity → replayed wire
   accounting → fitted coefficients) must land within 25% of the
   measured median — the CI gate.
4. **Predict** throughput at unreachable scale (S=256 / S=4096) and
   cross-check the engine's wire-word accounting against the compiled
   HLO of ``dht_execute`` (``roofline.collective_bytes``) in a
   forced-multi-device subprocess: two independent estimates of the
   same traffic, expected to agree exactly.

Gauges (CI gates read these from the BENCH json telemetry):
  bench.costmodel.heldout_rel_err   median relative error at held-out S
  bench.costmodel.wire_hlo_ratio    engine wire words / HLO words
  bench.costmodel.analytic_hlo_ratio   analytic replay / HLO words
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import numpy as np

from repro import obs
from repro.core import dht as d
from repro.core import hashing, routing
from repro.core.layout import DHTConfig, dht_create
from repro.obs import costmodel
from repro.obs import trace as obs_trace

from .common import Row, make_keys_vals, refuse_on_tpu, time_fn

KW, VW = 8, 8          # compact lanes: the model is lane-width-aware
BPS = 512              # buckets per shard in the sweep tables

_SOURCE = "bench.scale"


def _measure_round(state, kind: str, keys, vals, cap: int):
    """Median wall time + stat lanes of one jitted n-item round with the
    count-driven capacity baked in (what the prologue would size)."""
    kinds = ("write",) if kind == "write" else ("read",)

    def fn(st, op_keys, op_vals):
        o = (d.write_ops(op_keys, op_vals, None) if kind == "write"
             else d.read_ops(op_keys, None))
        st, _, _v, _f, _c, es = d.dht_execute(st, o, kinds=kinds,
                                              capacity=cap)
        return es

    jf = jax.jit(fn)
    t_med, es = time_fn(jf, state, keys, vals)
    return t_med, es


def _sweep_point(S: int, n: int, kind: str, dist: str, seed: int):
    cfg = DHTConfig(n_shards=S, buckets_per_shard=BPS,
                    key_words=KW, val_words=VW)
    state = dht_create(cfg)
    keys, vals = make_keys_vals(n, kw=KW, vw=VW, dist=dist, seed=seed)
    # preload so reads hit (write kind measures the update path)
    state, _ = d.dht_write(state, keys, vals)
    # host-side count-driven capacity, as the eager prologue would size it
    dest = np.asarray(hashing.owner_shard(hashing.hash64(keys)[0], S))
    cap = routing.plan_capacity(dest, S)
    t_med, es = _measure_round(state, kind, keys, vals, cap)
    obs_trace.record_round(_SOURCE, es, ops={kind: n}, dur=t_med)
    ev = {"stats": {k: np.asarray(v).item()
                    for k, v in es.items() if np.asarray(v).ndim == 0},
          "ops": {kind: n}, "dur": t_med}
    return ev, cap


def _heldout_error(model, events):
    """Median relative error of the FULLY analytic prediction (simulated
    capacity, replayed wire accounting) against held-out measured time."""
    errs = []
    for ev in events:
        (kind, n), = ev["ops"].items()
        pred = costmodel.predict_round(
            model, n, int(ev["stats"]["n_shards"]), key_words=KW,
            val_words=VW, kind=kind, prologue=False)
        errs.append(abs(pred["t_pred_s"] - ev["dur"]) / ev["dur"])
    return float(np.median(errs)), errs


_XCHECK_CODE = r"""
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import dht as d
from repro.core.layout import DHTConfig, dht_create
from repro.core.compat import shard_map
from repro.core.distributed import shard_spec, _psum_stats
from repro.obs import costmodel

S = len(jax.devices())
CAP = 64
KW, VW = 8, 8
cfg = DHTConfig(n_shards=S, buckets_per_shard=128, key_words=KW, val_words=VW)
st = dht_create(cfg)
mesh = Mesh(np.array(jax.devices()), ("d",))
sspec = shard_spec(mesh)
state_spec = jax.tree.map(lambda _: sspec, st)
bspec = P("d")

def fn(state, keys, valid):
    # elide_self=False: the compiled all_to_all still carries the self
    # block, so the cross-check must count it on the engine side too;
    # capacity baked so no prologue words enter the accounting.  vals and
    # found MUST be returned or XLA dead-code-eliminates the reply leg's
    # all-to-all and the HLO side undercounts by one leg
    state, _, vals, found, _c, es = d.dht_execute(
        state, d.read_ops(keys, valid), kinds=("read",), axis_name=("d",),
        elide_self=False, capacity=CAP)
    return state, vals, found, _psum_stats(es, ("d",))

stats_spec = {k: P() for k in
              ("mismatches", "rounds", "lock_tokens", "dropped", "epoch",
               "wire_words", "wire_send_words", "wire_reply_words",
               "fill_frac", "dispatch_rounds", "n_shards", "capacity",
               "bin_counts", "bin_max_load", "bin_imbalance", "hot_frac",
               "fallback_reads")}
sm = shard_map(fn, mesh=mesh, in_specs=(state_spec, bspec, bspec),
               out_specs=(state_spec, bspec, bspec, stats_spec))
jf = jax.jit(sm)
n = CAP * S
keys = jnp.ones((n, KW), jnp.uint32)
valid = jnp.ones((n,), bool)
hlo = jf.lower(st, keys, valid).compile().as_text()
hlo_words = costmodel.hlo_alltoall_words(hlo)
_, _, _, es = jf(st, keys, valid)
engine_words = int(es["wire_words"]) // S      # psum over S devices
analytic = costmodel.predict_wire_words(
    CAP, S, key_words=KW, val_words=VW, capacity=CAP, prologue=False)
print(json.dumps({"hlo_words": hlo_words, "engine_words": engine_words,
                  "analytic_words": analytic["wire_words"], "S": S}))
"""


def _wire_hlo_xcheck(devices: int = 4) -> dict:
    """Run the wire-vs-HLO audit in a fresh subprocess with forced host
    devices (the parent's jax backend is already initialized)."""
    refuse_on_tpu("bench_scale_model")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={devices}")
    env.setdefault("JAX_PLATFORMS", "cpu")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get(
        "PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _XCHECK_CODE],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"xcheck subprocess failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run(quick: bool = True) -> list[Row]:
    if quick:
        fit_S, holdout_S = (2, 4, 8, 32), (16,)
        read_n, write_n = (512, 2048), (2048,)
        pred_S = (256, 4096)
    else:
        fit_S, holdout_S = (2, 4, 8, 32, 64), (16, 48)
        read_n, write_n = (512, 2048, 8192), (2048, 8192)
        pred_S = (256, 1024, 4096)

    rows: list[Row] = []
    fit_events, holdout_events = [], []
    seed = 0
    for S in fit_S:
        for n in read_n:
            ev, cap = _sweep_point(S, n, "read", "uniform", seed)
            fit_events.append(ev)
            rows.append(Row(f"scale_read_S{S}_n{n}", ev["dur"] * 1e6,
                            f"cap={cap} wire={ev['stats']['wire_words']}"))
            seed += 1
    for S in fit_S[::2]:
        for n in write_n:
            ev, cap = _sweep_point(S, n, "write", "uniform", seed)
            fit_events.append(ev)
            rows.append(Row(f"scale_write_S{S}_n{n}", ev["dur"] * 1e6,
                            f"cap={cap} wire={ev['stats']['wire_words']}"))
            seed += 1
    # skewed mix: capacity (max bin) decouples from n/S — pins c_apply
    ev, cap = _sweep_point(8, max(read_n), "read", "zipf", seed)
    fit_events.append(ev)
    rows.append(Row(f"scale_read_S8_zipf", ev["dur"] * 1e6,
                    f"cap={cap} imb={ev['stats']['bin_imbalance']:.2f}"))
    seed += 1
    for S in holdout_S:
        for n in read_n:
            ev, cap = _sweep_point(S, n, "read", "uniform", seed)
            holdout_events.append(ev)
            seed += 1

    model = costmodel.fit(fit_events)
    obs.set_gauge("bench.costmodel.alpha_us", model.alpha * 1e6)
    obs.set_gauge("bench.costmodel.beta_ns_per_word", model.beta * 1e9)
    obs.set_gauge("bench.costmodel.fit_rel_err", model.fit_rel_err)
    rows.append(Row("scale_fit", model.alpha * 1e6,
                    f"beta={model.beta * 1e9:.3g}ns/word "
                    f"c_bin={model.c_bin * 1e9:.3g}ns "
                    f"c_apply={model.c_apply * 1e9:.3g}ns/row "
                    f"fit_err={100 * model.fit_rel_err:.1f}% "
                    f"n={model.n_events}"))

    err, _ = _heldout_error(model, holdout_events)
    obs.set_gauge("bench.costmodel.heldout_rel_err", err)
    for ev in holdout_events:
        (kind, n), = ev["ops"].items()
        S = int(ev["stats"]["n_shards"])
        pred = costmodel.predict_round(model, n, S, key_words=KW,
                                       val_words=VW, kind=kind,
                                       prologue=False)
        rows.append(Row(f"scale_heldout_S{S}_n{n}",
                        pred["t_pred_s"] * 1e6,
                        f"meas={ev['dur'] * 1e6:.1f}us "
                        f"err={100 * abs(pred['t_pred_s'] - ev['dur']) / ev['dur']:.1f}%"))
    rows.append(Row("scale_heldout_err", 100 * err,
                    f"median rel err at held-out S "
                    f"({'PASS' if err <= 0.25 else 'FAIL'}: gate 25%)"))

    # unreachable-scale predictions (the ROADMAP's calibrated simulator)
    n_pred = max(read_n)
    for S in pred_S:
        p = costmodel.predict_round(model, n_pred, S, key_words=KW,
                                    val_words=VW, kind="read")
        obs.set_gauge(f"bench.costmodel.pred_S{S}_mops",
                      p["throughput_pred"] / 1e6)
        rows.append(Row(f"scale_pred_S{S}", p["t_pred_s"] * 1e6,
                        f"{p['throughput_pred'] / 1e6:.2f}Mops/s "
                        f"cap={p['capacity']} wire={p['wire_words']}"))

    # standing audit: engine wire accounting vs compiled-HLO collectives
    try:
        x = _wire_hlo_xcheck()
        r_engine = x["engine_words"] / max(x["hlo_words"], 1)
        r_analytic = x["analytic_words"] / max(x["hlo_words"], 1)
        derived = (f"engine/hlo={r_engine:.4f} analytic/hlo={r_analytic:.4f} "
                   f"(S={x['S']}, hlo={x['hlo_words']}w)")
    except Exception as e:  # pragma: no cover - CI surfaces via gate
        r_engine = r_analytic = 0.0
        derived = f"ERROR:{type(e).__name__}:{e}"
    obs.set_gauge("bench.costmodel.wire_hlo_ratio", r_engine)
    obs.set_gauge("bench.costmodel.analytic_hlo_ratio", r_analytic)
    rows.append(Row("scale_xcheck", 0.0, derived))
    return rows


if __name__ == "__main__":
    for r in run(quick=True):
        print(r.csv())
