"""Shared benchmark harness.

Measured numbers are CPU wall times of the jitted SPMD programs (relative
ordering across the three consistency modes is the reproducible claim);
the ``derived`` column models absolute throughput at the paper's hardware
constants so the magnitudes are comparable with the paper's figures:

  RT_LAT      one RDMA round-trip on NDR InfiniBand  (~2.2 us)
  SW_OVERHEAD per-op software/client overhead, calibrated so the modeled
              lock-free read throughput at 640 ranks reproduces the
              paper's 16 Mops observation.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

RT_LAT = 2.2e-6
SW_OVERHEAD = 3.8e-5
PAPER_RANKS = 640


def refuse_on_tpu(bench: str) -> None:
    """Stop a CPU rehearsal bench that would start a CPU-forced child on
    a TPU host: the parent would hold the chip while the child measured
    the CPU, under the chip's name."""
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{bench} is a CPU rehearsal (its measured child runs on forced "
            "host devices); run it with JAX_PLATFORMS=cpu, or use "
            "chip_smoke.py on the chip")


@dataclasses.dataclass
class Row:
    name: str
    us_per_call: float
    derived: str

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.3f},{self.derived}"


def time_fn(fn, *args, iters: int = 3, warmup: int = 1):
    """Median wall seconds of fn(*args) with block_until_ready."""
    out = None
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), out


def modeled_ops(ranks: int, rts_per_op: float) -> float:
    """Modeled cluster throughput (ops/s) at paper-like constants."""
    return ranks / (rts_per_op * RT_LAT + SW_OVERHEAD)


def make_keys_vals(n, kw=20, vw=26, dist="uniform", key_range=712_500,
                   zipf_skew=0.99, seed=0):
    """The paper's key generator: random 80-byte keys; zipf(0.99) over a
    712,500-id range for the skewed workload (§5.2)."""
    rng = np.random.default_rng(seed)
    if dist == "zipf":
        ids = rng.zipf(zipf_skew + 1.0, size=n) % key_range
    else:
        ids = rng.integers(0, key_range, size=n)
    keys = np.zeros((n, kw), np.uint32)
    keys[:, 0] = ids & 0xFFFFFFFF
    keys[:, 1] = ids >> 32
    # fill remaining words deterministically from the id (80-byte keys)
    for w in range(2, kw):
        keys[:, w] = (ids * (w * 2654435761 + 1)) & 0xFFFFFFFF
    vals = rng.integers(0, 2**31, size=(n, vw)).astype(np.uint32)
    return jnp.asarray(keys), jnp.asarray(vals)
