"""The program's spans and phase scopes on the profiler's clock
(DESIGN.md §10): the ``ShardedDHT`` wrappers' ``dht.*`` host spans, the
``gc`` span of full garbage collections, and the op engine's
``bin``/``dispatch``/``apply``/``collect`` scopes in every round's
``op_name`` metadata."""
import gc
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import DHTConfig, dht_create, read_ops
from repro.core.distributed import ShardedDHT, make_mesh_1d
from repro.core.op_engine import dht_execute
from repro.obs.trace import PHASES

CFG = DHTConfig(n_shards=1, buckets_per_shard=256, key_words=4,
                val_words=3, capacity=64)


def _table():
    d = ShardedDHT.create(make_mesh_1d(1), CFG)
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, 2**31, size=(64, 4)), jnp.uint32)
    vals = jnp.asarray(rng.integers(0, 2**31, size=(64, 3)), jnp.uint32)
    return d, keys, vals


def _host_events(log_dir, names):
    """(name, start, end) of every host event named in ``names``, in
    order of start, from the profiler's trace under ``log_dir``."""
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns) for e in line.events
                        if e.name in names]
    return sorted(out, key=lambda e: e[1])


@pytest.mark.parametrize("call,children", [
    ("write", ("dht.dispatch", "dht.flush", "dht.retry_check")),
    ("read", ("dht.dispatch", "dht.flush")),
    ("read_many", ("dht.dispatch", "dht.flush")),
])
def test_wrapper_span_holds_its_phases_in_order(tmp_path, call, children):
    d, keys, vals = _table()
    args = {"write": (keys, vals), "read": (keys,),
            "read_many": (keys.reshape(16, 4, 4),)}[call]
    getattr(d, call)(*args)                       # compile outside
    parent = f"dht.{call}"
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(getattr(d, call)(*args))
    evs = _host_events(str(tmp_path), {parent} | set(children))
    (_, p_lo, p_hi), = [e for e in evs if e[0] == parent]
    inner = [e for e in evs if e[0] != parent]
    assert tuple(n for n, _, _ in inner) == children
    assert all(p_lo <= s <= e <= p_hi for _, s, e in inner)


@pytest.mark.parametrize("generation,spans", [(0, 0), (1, 0), (2, 1)])
def test_only_full_collections_open_a_gc_span(tmp_path, generation, spans):
    gc.disable()                       # no collection but the forced one
    try:
        with jax.profiler.trace(str(tmp_path)):
            gc.collect(generation)
    finally:
        gc.enable()
    assert len(_host_events(str(tmp_path), {"gc"})) == spans


def _lowered(kind: str) -> str:
    d, keys, vals = _table()
    if kind == "read":
        low = d.read_fn().lower(d.state, keys, d._ones(64))
    elif kind == "write":
        low = d.write_fn().lower(d.state, keys, vals, d._ones(64))
    elif kind == "mixed":
        op = jnp.zeros((64,), jnp.int32)
        low = d.execute_fn(("read", "write")).lower(
            d.state, keys, vals, d._ones(64), op)
    else:                              # the engine outside shard_map
        low = jax.jit(lambda s, k: dht_execute(
            s, read_ops(k), kinds=("read",))[2]).lower(
                dht_create(DHTConfig(n_shards=2, buckets_per_shard=64,
                                     key_words=4, val_words=3)), keys)
    return low.as_text(debug_info=True)


@pytest.mark.parametrize("kind", ["read", "write", "mixed", "local"])
def test_round_ops_carry_every_phase_scope(kind):
    text = _lowered(kind)
    found = set(re.findall(r"/(%s)/" % "|".join(PHASES), text))
    assert found == set(PHASES), found
