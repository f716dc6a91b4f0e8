"""Compile the main path's Pallas kernels and whole DHT rounds for a TPU
v5e chip that is described, not attached.

Interpret-mode tests check what the kernels compute; these check that
Mosaic and XLA:TPU accept them at the lane widths ``routing._encode``
builds for real rounds and at the batch and table sizes of
``chip_smoke.py``.  Nothing runs: a pass here is a compile, not a chip run.
The topology is described inside a module fixture (never at import), so
only the worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import DHTConfig, L1Config, l1cache, routing
from repro.core.compat import make_mesh
from repro.core.distributed import ShardedDHT, _state_shardings
from repro.core.layout import dht_create
from repro.kernels import ops
from repro.kernels.l1_kernel import l1_probe_pallas
from repro.kernels.route_kernel import route_pack_pallas, route_unpack_pallas

BATCH = 1 << 16                  # chip_smoke.py's batch
BUCKETS = 1 << 22                # chip_smoke.py's buckets per shard
# whole rounds compile at a smaller batch: XLA:TPU takes ~16 s to compile
# one 65,536-element sort, and a read round holds several
ROUND_BATCH = 1 << 12
V5E_HBM = 16 * 1024**3
# lanes per row that routing._encode builds: send leg of a read, write and
# mixed read/write round; reply leg of a plain and of an L1-fronted round
PACK_WIDTHS = (22, 48, 49)
UNPACK_WIDTHS = (28, 31)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("width", PACK_WIDTHS)
def test_route_pack_compiles_for_v5e(one_chip, width):
    c = jax.jit(lambda m, i, f: route_pack_pallas(m, i, f, interpret=False)
                ).lower(_shape(one_chip, (BATCH, width), jnp.uint32),
                        _shape(one_chip, (BATCH,), jnp.int32),
                        _shape(one_chip, (width,), jnp.uint32)).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("width", UNPACK_WIDTHS)
def test_route_unpack_compiles_for_v5e(one_chip, width):
    c = jax.jit(
        lambda b, s, k, f: route_unpack_pallas(b, s, k, f, interpret=False)
    ).lower(_shape(one_chip, (BATCH, width), jnp.uint32),
            _shape(one_chip, (BATCH,), jnp.int32),
            _shape(one_chip, (BATCH,), jnp.int32),
            _shape(one_chip, (width,), jnp.uint32)).compile()
    assert "tpu_custom_call" in c.as_text()


def test_l1_probe_compiles_for_v5e(one_chip):
    g = L1Config()
    c = jax.jit(
        lambda a, b, f, q, s: l1_probe_pallas(a, b, f, q, s, interpret=False)
    ).lower(
        _shape(one_chip, (g.n_sets, g.n_ways, g.key_words), jnp.uint32),
        _shape(one_chip, (g.n_sets, g.n_ways, g.val_words), jnp.uint32),
        _shape(one_chip, (g.n_sets, g.n_ways), jnp.bool_),
        _shape(one_chip, (BATCH, g.key_words), jnp.uint32),
        _shape(one_chip, (BATCH,), jnp.int32)).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.fixture
def chip_kernels(monkeypatch):
    """Steer the traced rounds onto the compiled Pallas kernels: the
    auto switches and ``ops`` ask the (CPU) default backend."""
    monkeypatch.setattr(routing, "USE_PALLAS_ROUTE", True)
    monkeypatch.setattr(l1cache, "USE_PALLAS_L1", True)
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)


def _described_table(topo, l1cfg=None) -> ShardedDHT:
    """A one-shard ShardedDHT on one described chip, built from shapes
    (``ShardedDHT.create`` would place arrays, which a described device
    cannot hold)."""
    mesh = make_mesh((1,), ("dht",), devices=topo.devices[:1])
    cfg = DHTConfig(n_shards=1, buckets_per_shard=BUCKETS)
    shapes = jax.eval_shape(lambda: dht_create(cfg))
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, _state_shardings(mesh, shapes))
    l1 = None
    if l1cfg is not None:
        l1 = jax.eval_shape(lambda: jax.tree.map(
            lambda x: x[None], l1cache.l1_create(l1cfg, 1)))
        l1 = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, P("dht"))), l1)
    return ShardedDHT(mesh=mesh, cfg=cfg, state=state, l1cfg=l1cfg, l1=l1)


def _batch(d: ShardedDHT, width: int | None, dtype=jnp.uint32):
    sh = NamedSharding(d.mesh, P("dht"))
    shape = (ROUND_BATCH,) if width is None else (ROUND_BATCH, width)
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


@pytest.mark.parametrize("round_kind", ["write", "read", "mixed",
                                        "cached_read"])
def test_sharded_round_compiles_for_v5e(topo, chip_kernels, round_kind):
    """A whole ShardedDHT round on chip_smoke.py's table compiles for one
    v5e chip, carries the Pallas kernels, and fits the chip's HBM."""
    d = _described_table(
        topo, L1Config() if round_kind == "cached_read" else None)
    keys, vals = _batch(d, d.cfg.key_words), _batch(d, d.cfg.val_words)
    valid = _batch(d, None, jnp.bool_)
    if round_kind == "write":
        low = d.write_fn().lower(d.state, keys, vals, valid)
    elif round_kind == "read":
        low = d.read_fn().lower(d.state, keys, valid)
    elif round_kind == "mixed":
        low = d.execute_fn(("read", "write")).lower(
            d.state, keys, vals, valid, _batch(d, None, jnp.int32))
    else:
        low = d.read_cached_fn().lower(d.state, d.l1, keys, valid)
    c = low.compile()
    assert "tpu_custom_call" in c.as_text()
    m = c.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert used < V5E_HBM, used
