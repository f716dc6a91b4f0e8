"""The key distributions and the word generator."""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from harness import traffic
from harness.spec import load_module


def test_scrambled_zipfian_rank_frequencies():
    """Ranks 0 and 1 (before scrambling) are drawn with probability
    r^-theta / zeta exactly; beyond them YCSB's generator follows Gray et
    al.'s closed-form approximation, whose CDF is checked, at 2 M draws."""
    z = load_module("traffic", "scrambled_zipfian")
    ranks = z.zipfian_ranks(np.random.default_rng(3).random(2_000_000))
    freq = np.bincount(ranks[ranks < 64], minlength=64) / ranks.size
    want = (np.arange(1, 65) ** -z.ZIPFIAN_CONSTANT) / z.ZETAN
    np.testing.assert_allclose(freq[:2], want[:2], rtol=0.03)
    th, n = z.ZIPFIAN_CONSTANT, z.ITEM_COUNT
    eta = (1 - (2 / n) ** (1 - th)) / (1 - (1 + 0.5 ** th) / z.ZETAN)
    k = np.arange(2, 64)
    cdf = ((((k + 1) / n) ** (1 - th)) - 1 + eta) / eta
    np.testing.assert_allclose(np.cumsum(freq)[k], cdf, rtol=0.01)
    # the hottest id of a scrambled draw over 1 M records takes ~1/ZETAN
    ids = z.draw(np.random.default_rng(4), 1_000_000, 500_000,
                 {"theta": 0.99})
    top = np.sort(np.bincount(ids))[::-1]
    assert abs(top[0] / ids.size - 1 / z.ZETAN) < 0.003
    assert ids.min() >= 0 and ids.max() < 1_000_000


def test_fnvhash64_matches_ycsb():
    """YCSB's ``Utils.fnvhash64``: FNV-1 over 8 octets, then Math.abs."""
    z = load_module("traffic", "scrambled_zipfian")

    def java(val: int) -> int:
        h = 0xCBF29CE484222325
        for _ in range(8):
            h ^= val & 0xFF
            val >>= 8
            h = (h * 1099511628211) & (2**64 - 1)
        h = h - 2**64 if h >= 2**63 else h
        return abs(h)

    vals = np.array([0, 1, 255, 256, 12345678901, 2**40 + 7], np.int64)
    assert z.fnvhash64(vals).tolist() == [java(int(v)) for v in vals]


def test_uniform_covers_the_id_space():
    u = load_module("traffic", "uniform")
    ids = u.draw(np.random.default_rng(0), 1000, 200_000, {})
    c = np.bincount(ids, minlength=1000)
    assert c.min() > 120 and c.max() < 290


def test_device_and_host_words_agree():
    ids = np.array([0, 1, 712_499, 2**31 - 1], np.int64)
    for fn, n in ((traffic.key_words, 20), (traffic.value_words, 250)):
        host = fn(np, ids.astype(np.uint32), n, 0xDEADBEEF)
        dev = jax.jit(lambda i, s, fn=fn, n=n: fn(jnp, i, n, s))(
            jnp.asarray(ids.astype(np.uint32)), jnp.uint32(0xDEADBEEF))
        np.testing.assert_array_equal(host, np.asarray(dev))
        assert (host[:, 0] == ids.astype(np.uint32)).all()


def test_pool_is_a_function_of_the_seed():
    wl = {"batch": 256, "pool_rounds": 3, "mix": {"read": 0.5, "write": 0.5},
          "keys": {"dist": "scrambled_zipfian", "theta": 0.99}}
    dist = load_module("traffic", "scrambled_zipfian")
    a = traffic.draw_pool(2**31 + 5, wl, 1000, dist)
    b = traffic.draw_pool(2**31 + 5, wl, 1000, dist)
    c = traffic.draw_pool(2**31 + 6, wl, 1000, dist)
    np.testing.assert_array_equal(a.ids, b.ids)
    assert (a.ids != c.ids).any()
    # every seed: the same number of writes in every round
    assert (a.ops.sum(axis=1) == 128).all() and (c.ops.sum(axis=1) == 128).all()


def test_round_stamps_differ_from_round_to_round():
    """Every round writes values of its own: stamps are odd (the preload's
    are even), distinct across rounds, and alike on host and device."""
    host = np.stack([traffic.round_stamps(np, r, 512) for r in range(4)])
    assert (host % 2 == 1).all() and np.unique(host).size == host.size
    dev = jax.jit(lambda r: traffic.round_stamps(jnp, r, 512))
    for r in (0, 3, 40_000):
        np.testing.assert_array_equal(
            np.asarray(dev(np.uint32(r))), traffic.round_stamps(np, r, 512))


def test_value_check_names_the_stamp_and_flags_a_changed_word():
    st = np.array([7, 9, 11], np.uint32)
    vals = traffic.value_words(np, st, 26, 0xBEEF)
    vals[2, 5] ^= 1
    stamp, whole = traffic.value_check(np, vals, 0xBEEF)
    assert stamp.tolist() == [7, 9, 11] and whole.tolist() == [True, True,
                                                               False]


def test_a_fixed_traffic_seed_leaves_the_seed_only_order_and_values():
    wl = {"batch": 256, "pool_rounds": 4, "mix": {"read": 0.5, "write": 0.5},
          "keys": {"dist": "scrambled_zipfian", "theta": 0.99},
          "traffic_seed": 0}
    dist = load_module("traffic", "scrambled_zipfian")
    a = traffic.draw_pool(2**31 + 5, wl, 1000, dist)
    b = traffic.draw_pool(2**31 + 6, wl, 1000, dist)
    key = lambda pool: sorted(map(bytes, np.concatenate(   # noqa: E731
        [pool.ids, pool.ops], axis=1)))
    assert key(a) == key(b) and (a.ids != b.ids).any()
    assert a.salts.key == b.salts.key and a.salts.value != b.salts.value
