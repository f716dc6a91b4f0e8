"""YCSB's scrambled Zipfian key chooser (``ScrambledZipfianGenerator``).

YCSB draws a Zipfian rank over a fixed space of 10^10 items with its
precomputed zeta constant (Gray et al., "Quickly generating billion-record
synthetic databases", SIGMOD 1994), then scatters the rank over the record
ids with the 64-bit FNV-1 hash: ``id = fnvhash64(rank) % n_ids``.  Popular
ids are therefore spread over the key space, and the hottest id takes about
``1 / ZETAN`` (3.8 %) of the draws whatever the record count.  This is the
``requestdistribution=zipfian`` chooser of YCSB's CoreWorkload for
workloads A-C (Cooper et al., SoCC 2010), where no inserts grow the space.
"""
from __future__ import annotations

import numpy as np

ITEM_COUNT = 10_000_000_000
ZIPFIAN_CONSTANT = 0.99
ZETAN = 26.46902820178302          # YCSB: zeta(ITEM_COUNT, 0.99)
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


def zipfian_ranks(u: np.ndarray, theta: float = ZIPFIAN_CONSTANT,
                  items: int = ITEM_COUNT, zetan: float = ZETAN) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextLong`` for uniform draws ``u`` in [0, 1)."""
    alpha = 1.0 / (1.0 - theta)
    zeta2 = 1.0 + 0.5 ** theta
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    ret = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    ret = np.where(uz < 1.0 + 0.5 ** theta, 1, ret)
    return np.where(uz < 1.0, 0, ret)


def fnvhash64(val: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64``: FNV-1 over the 8 low-first octets, then
    ``Math.abs`` of the signed result."""
    val = val.astype(np.uint64)
    h = np.full(val.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (val & np.uint64(0xFF))) * np.uint64(FNV_PRIME_64)
            val = val >> np.uint64(8)
    return np.abs(h.view(np.int64))


def draw(rng: np.random.Generator, n_ids: int, size: int,
         params: dict) -> np.ndarray:
    """-> ``size`` ids as int64; ``params["theta"]`` must be YCSB's 0.99,
    the only exponent its precomputed ``ZETAN`` holds for."""
    theta = float(params.get("theta", ZIPFIAN_CONSTANT))
    if theta != ZIPFIAN_CONSTANT:
        raise ValueError(f"scrambled_zipfian: theta {theta} != YCSB's "
                         f"{ZIPFIAN_CONSTANT}, for which ZETAN is computed")
    ranks = zipfian_ranks(rng.random(size), theta)
    return fnvhash64(ranks) % n_ids
