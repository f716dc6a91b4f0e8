"""Benchmark runner: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV; ``--json PATH`` additionally
writes the same rows machine-readably (``BENCH_<name>`` -> row dicts) so
the perf trajectory is tracked across PRs.  The JSON payload carries a
``telemetry`` key — the metric-registry snapshot accumulated across the
run (DESIGN.md §10) — and ``--trace``/``--chrome-trace`` dump the
per-round ring buffer as JSONL / perfetto-loadable ``trace_event`` JSON.

    PYTHONPATH=src:. python -m benchmarks.run [--full] [--json PATH] \
        [--trace PATH] [--chrome-trace PATH]
"""
import argparse
import dataclasses
import hashlib
import json
import sys
import time
import traceback

# BENCH json schema (bumped when the payload shape changes): v2 added the
# "schema" header (version, config fingerprint, repeat count) and the
# optional "repeats_raw" block that obs/regress.py's median-of-k uses
SCHEMA_VERSION = 2


def _fingerprint(config: dict) -> str:
    """Short stable hash of the run configuration — trajectory tooling
    refuses to compare BENCH files with different fingerprints (a quick
    run regressing against a --full baseline is noise, not signal)."""
    blob = json.dumps(config, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:12]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-sized sweeps (slow on 1 CPU core)")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (e.g. fig45,kernels)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write results as JSON {BENCH_<name>: [rows]}")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the per-round trace ring as JSONL")
    ap.add_argument("--chrome-trace", default=None, metavar="PATH",
                    help="write the trace as Chrome trace_event JSON")
    ap.add_argument("--repeats", type=int, default=1, metavar="N",
                    help="run each bench N times; repeat 0 keeps the "
                         "BENCH_<name> rows, all repeats land in "
                         "repeats_raw for noise-aware regression gating")
    args = ap.parse_args()
    quick = not args.full
    repeats = max(args.repeats, 1)

    from repro import obs
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()

    from . import (
        bench_crash,
        bench_fig3_server_vs_dht,
        bench_fig45_throughput,
        bench_fig6_mixed,
        bench_fig7_poet,
        bench_interp,
        bench_kernels,
        bench_l1_locality,
        bench_pipeline,
        bench_resharding,
        bench_roofline,
        bench_scale_model,
        bench_table2_mismatch,
        bench_value_sizes,
    )

    benches = {
        "fig3": bench_fig3_server_vs_dht,
        "fig45": bench_fig45_throughput,
        "fig6": bench_fig6_mixed,
        "table2": bench_table2_mismatch,
        "fig7": bench_fig7_poet,
        "valsize": bench_value_sizes,
        "kernels": bench_kernels,
        "l1": bench_l1_locality,
        "pipeline": bench_pipeline,
        "interp": bench_interp,
        "reshard": bench_resharding,
        "crash": bench_crash,
        "roofline": bench_roofline,
        "scale": bench_scale_model,
    }
    selected = (args.only.split(",") if args.only else list(benches))

    print("name,us_per_call,derived")
    results: dict[str, list[dict]] = {}
    repeats_raw: dict[str, list[list[dict]]] = {}
    failures = 0
    for name in [n for n in selected if n not in benches]:
        failures += 1
        print(f"{name},NaN,ERROR:unknown bench (known: {','.join(benches)})")
        results[f"BENCH_{name}"] = [
            {"name": name, "us_per_call": None, "derived": "ERROR:unknown"}]
    for name in [n for n in selected if n in benches]:
        mod = benches[name]
        t0 = time.perf_counter()
        try:
            for rep in range(repeats):
                rows = mod.run(quick)
                if name == "fig45":
                    rows = rows + mod.table1(rows)
                dicts = [dataclasses.asdict(r) for r in rows]
                if rep == 0:
                    for r in rows:
                        print(r.csv())
                    results[f"BENCH_{name}"] = dicts
                repeats_raw.setdefault(name, []).append(dicts)
        except Exception as e:
            failures += 1
            print(f"{name},NaN,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
            results[f"BENCH_{name}"] = [
                {"name": name, "us_per_call": None,
                 "derived": f"ERROR:{type(e).__name__}:{e}"}]
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
    if args.json:
        config = {"quick": quick, "benches": sorted(selected),
                  "repeats": repeats}
        payload = {"schema": {"schema_version": SCHEMA_VERSION,
                              "fingerprint": _fingerprint(config),
                              "config": config,
                              "repeats": repeats},
                   "failures": failures, "quick": quick,
                   "telemetry": obs.get_registry().snapshot(), **results}
        if repeats > 1:
            payload["repeats_raw"] = repeats_raw
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}", file=sys.stderr)
    if args.trace:
        n = obs.get_tracer().to_jsonl(args.trace)
        print(f"# wrote {args.trace} ({n} round events)", file=sys.stderr)
    if args.chrome_trace:
        n = obs.get_tracer().to_chrome_trace(args.chrome_trace)
        print(f"# wrote {args.chrome_trace} ({n} trace events)",
              file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
