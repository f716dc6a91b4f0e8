"""The one traffic generator: a workload file's parameters -> rounds.

A workload file (``bench/workloads/<cell>.json``) states the batch per
round, the op mix, the key distribution (a module under ``bench/traffic/``
found by name) and how many distinct rounds of ids to build
(``pool_rounds``).  Ids are drawn on the host with numpy from ``--seed``
(cheap: one int per op); their key words are expanded on the device in
one jitted call per pool round, during set-up.  The window cycles through
the pool of ids.  Values are not pooled: every round writes values of its
own, expanded on the device from the round's index by one jitted call, so
a write lost in any round leaves a value that no later round rewrites.

Words are a pure function of (seed, id) for keys and of (seed, stamp) for
values, written twice from one definition, :func:`key_words` /
:func:`value_words`, for numpy (the reference) and jax.numpy (the device):
the reference recomputes every expected value without reading anything the
program produced.  Word 0 of a key is its id, so distinct ids are distinct
keys; word 0 of a value is its stamp, so a stale value names its write.

Stamps: the preload writes id ``i`` with stamp ``2 i``; round ``r`` (warm-up
and window rounds counted from 0) writes position ``j`` with stamp
``2 (r * batch + j) + 1``, modulo 2**32.  Stamps are distinct while
``r * batch < 2**31``: 32,768 rounds at a batch of 65,536.
"""
from __future__ import annotations

import dataclasses

import numpy as np

OP_READ, OP_WRITE = 0, 1        # the program's tags are mapped by the entry
_GOLD = 0x9E3779B1


def _fmix32(xp, h):
    """murmur3's 32-bit finaliser, in uint32 arithmetic for numpy or jnp."""
    h = h ^ (h >> 16)
    h = h * xp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * xp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _words(xp, lead, n_words: int, salt, mul: int):
    """(n,) uint32 -> (n, n_words) uint32; word 0 is ``lead`` itself.
    ``salt`` is an int or a uint32 scalar array (traced on the device, so
    one compiled program serves every seed)."""
    w = xp.arange(n_words, dtype=xp.uint32)
    salt = xp.asarray(salt, dtype=xp.uint32)
    h = _fmix32(xp, lead[:, None] * xp.uint32(mul)
                ^ (w[None, :] * xp.uint32(0x27D4EB2F) + salt))
    return xp.where(w[None, :] == 0, lead[:, None], h)


def key_words(xp, ids, n_words: int, salt):
    return _words(xp, ids.astype(xp.uint32), n_words, salt, _GOLD)


def value_words(xp, stamps, n_words: int, salt):
    return _words(xp, stamps.astype(xp.uint32), n_words, salt, 0x165667B1)


def value_check(xp, vals, salt):
    """(n, VW) value words -> (stamp, whole): each row's word 0, the stamp
    it claims, and whether every one of its words is that stamp's value.
    The device reduces a round's values to these two lanes, so that the
    reference compares every value the window read at 5 B a row."""
    stamp = vals[:, 0]
    want = value_words(xp, stamp, vals.shape[1], salt)
    return stamp, xp.all(vals == want, axis=1)


def preload_stamps(ids: np.ndarray) -> np.ndarray:
    return (2 * ids).astype(np.uint32)


def round_stamps(xp, r, batch: int):
    """Stamps of round ``r``'s positions, in uint32 arithmetic (it wraps
    alike in numpy and jax.numpy); ``r`` may be a traced uint32 scalar."""
    j = xp.arange(batch, dtype=xp.uint32)
    r = xp.asarray(r, dtype=xp.uint32)
    return (r * xp.uint32(batch) + j) * xp.uint32(2) + xp.uint32(1)


@dataclasses.dataclass(frozen=True)
class Salts:
    key: int
    value: int

    @classmethod
    def from_seed(cls, seed: int) -> "Salts":
        w = np.random.default_rng([seed, 0x5A17]).integers(
            0, 2**32, size=2, dtype=np.uint64)
        return cls(int(w[0]), int(w[1]))


@dataclasses.dataclass
class HostPool:
    """The host's copy of the traffic: ids and op tags of every pool
    round (round ``r`` of a run uses pool round ``r % rounds``)."""

    ids: np.ndarray      # (P, B) int64
    ops: np.ndarray      # (P, B) int8, OP_READ / OP_WRITE
    batch: int
    salts: Salts

    @property
    def rounds(self) -> int:
        return self.ids.shape[0]


def draw_pool(seed: int, traffic: dict, n_ids: int, dist) -> HostPool:
    """Ids and op tags of ``traffic["pool_rounds"]`` rounds from the seed.

    Every seed gets the same number of rounds, ops and reads per round;
    only which ids and which positions carry writes differ.

    A workload file that gives ``traffic_seed`` fixes its traffic instead:
    the ids, the op tags and the key words come from that seed, and the
    run's seed only orders the pool's rounds and makes the values.  Every
    run then does the same work, for a mix whose rounds differ in cost
    with the keys they draw (in a skewed mix, the rare round whose inserts
    collide takes an extra write pass)."""
    fixed = "traffic_seed" in traffic
    t_seed = int(traffic["traffic_seed"]) if fixed else seed
    rng = np.random.default_rng([t_seed, 0x7EAF])
    batch = int(traffic["batch"])
    n_pool = int(traffic["pool_rounds"])
    write_share = float(traffic["mix"]["write"])
    n_write = int(round(batch * write_share))
    ids = np.stack([dist.draw(rng, n_ids, batch, traffic.get("keys", {}))
                    for _ in range(n_pool)]).astype(np.int64)
    ops = np.zeros((n_pool, batch), np.int8)
    for p in range(n_pool):
        ops[p, rng.permutation(batch)[:n_write]] = OP_WRITE
    if fixed:
        order = np.random.default_rng([seed, 0x0DE7]).permutation(n_pool)
        ids, ops = ids[order], ops[order]
    salts = Salts(key=Salts.from_seed(t_seed).key,
                  value=Salts.from_seed(seed).value)
    return HostPool(ids=ids, ops=ops, batch=batch, salts=salts)


def preload_chunks(n_ids: int, batch: int):
    """Ids ``0 .. n_ids-1`` in order, as (ids, valid) chunks of ``batch``
    (the last one padded with invalid rows, so every chunk has one shape)."""
    for lo in range(0, n_ids, batch):
        ids = np.arange(lo, lo + batch, dtype=np.int64)
        yield np.minimum(ids, n_ids - 1), ids < n_ids
