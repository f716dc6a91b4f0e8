"""Benchmark of the sharded DHT on TPU: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations and metrics are named in ``BENCHMARK.json`` at the
root of the checkout; each is found by its name under ``bench/`` (see
``bench/harness/spec.py``).  The run refuses to start without a TPU or
with fewer chips than the cell asks for.  It prints the device, set-up and
compile-cache lines, and as its last line of standard output one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``,
with ``--trace 1`` a ``breakdown``, and last ``checks``: each number the
correctness check compared, with its limit.  The same numbers are the last
lines of standard error.

``--fault`` plants one of ``harness.cell.FAULTS`` (the control and the
faults the correctness check must catch); the benchmark's own runs never
pass it.  ``--seeds`` runs several seeds in one process, one result line
each, to prove correctness on many seeds without paying start-up for each.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def configure_jax():
    """Compile cache at ``$JAX_COMPILATION_CACHE_DIR`` if set, else at a
    fixed path inside the checkout; every program is cached, however
    quick its compile, so that a second run compiles nothing."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax, cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seeds", default=None,
                    help="comma-separated seeds run in this one process")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced window's .xplane.pb to this path")
    args = ap.parse_args(argv)

    jax, cache = configure_jax()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    from harness import cell as cell_mod
    from harness.spec import load_cell, load_peaks

    cell = load_cell(args.workload, ROOT)
    devices = jax.devices()
    d = devices[0]
    print(f"device: platform {d.platform}, kind {d.device_kind}, "
          f"count {len(devices)}", flush=True)
    if d.platform != "tpu":
        print(f"bench: no TPU (JAX sees {d.platform}); this benchmark runs "
              "only on the chip", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peaks = load_peaks(d.device_kind)
    print(f"compile cache: {cache}", flush=True)
    cell_mod.CompileEvents.get()

    seeds = ([int(s) for s in args.seeds.split(",")] if args.seeds
             else [args.seed])
    t_start = T_START
    for seed in seeds:
        res = cell_mod.run(cell, seed, args.seconds, bool(args.trace),
                           t_start=t_start, fault=args.fault, peaks=peaks,
                           keep_trace=args.keep_trace)
        for name, c in res["checks"].items():
            print(f"check {name}: {c['value']} (limit {c['limit']})",
                  file=sys.stderr, flush=True)
        print(json.dumps(res), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
