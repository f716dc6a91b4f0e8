"""Sharded execution of the DHT over a device mesh.

Every device contributes one table shard (the paper: "the parallel
processes offer a part of their available memory").  Queries are
device-local batches; routing crosses the *entire* mesh (all axes
flattened), so the table behaves as one global key-value space no matter
how the mesh is otherwise partitioned for the model (DP/TP/PP axes).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from . import dht as dht_ops
from . import l1cache
from .compat import make_mesh, shard_map
from .layout import DHTConfig, DHTState, dht_create
from .pipeline import RoundQueue


def mesh_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def shard_spec(mesh: Mesh) -> P:
    """Table shards spread over all mesh axes (flattened)."""
    return P(mesh_axes(mesh))


# Per-round skew lanes (DESIGN.md §11) every wrapper's stats carry; the
# specs below append these so the shard_map out_specs stay in lockstep
# with the dicts the dht.py wrappers return.
SKEW_KEYS = ("bin_counts", "bin_max_load", "bin_imbalance", "hot_frac")


def _psum_stats(stats: dict, axes) -> dict:
    out = {}
    for k, v in stats.items():
        if k == "code":
            out[k] = v  # per-item, stays sharded
        elif k in ("rounds", "epoch", "dispatch_rounds", "n_shards",
                   "capacity", "bin_max_load"):
            out[k] = jax.lax.pmax(v, axes)  # replicated/uniform or max
        elif k in ("fill_frac", "bin_imbalance", "hot_frac"):
            out[k] = jax.lax.pmean(v, axes)  # per-device fraction -> mean
        else:
            out[k] = jax.lax.psum(v, axes)
    return out


def _state_shardings(mesh: Mesh, template: DHTState):
    """NamedShardings for a DHTState: slabs spread over the mesh, the
    membership ring (if any) replicated on every device."""
    spec = shard_spec(mesh)
    sh = jax.tree.map(lambda _: NamedSharding(mesh, spec), template)
    if template.ring is not None:
        sh.ring = jax.tree.map(
            lambda _: NamedSharding(mesh, P()), template.ring)
    return sh


@dataclasses.dataclass
class ShardedRound:
    """An issued-but-uncommitted sharded round (DESIGN.md §12): the
    host-level twin of ``op_engine.InFlightRound`` for the jitted
    wrappers.  The jitted call has already returned — every array here
    is a future under JAX async dispatch — and ``outs`` holds the
    positional results the matching ``*_commit`` will unpack."""

    source: str
    outs: tuple
    stats: dict
    ops: dict
    t_start: float
    t_issued: float
    committed: bool = False


@dataclasses.dataclass
class ShardedDHT:
    """Jitted sharded read/write closures bound to a mesh.

    With ``l1cfg`` set, every device fronts its traffic with the locality
    tier (DESIGN.md §9): reads probe the per-device L1 before routing and
    elide self-owned requests from the ``all_to_all``; every round —
    reads AND writes — refreshes the per-shard coherence watermarks from
    the reply-lane piggyback, which is what invalidates cached lines a
    remote write obsoleted.  All table mutations must therefore go
    through this object's closures while an L1 is attached.

    ``pipeline_depth`` configures the issue/commit wrappers
    (:meth:`read_async` / :meth:`write_async`, DESIGN.md §12): it is the
    depth of the :meth:`round_queue` double buffer AND part of every
    pipelined closure's cache key, so sync and pipelined closures can
    never alias in ``_fn_cache``."""

    mesh: Mesh
    cfg: DHTConfig
    state: DHTState
    l1cfg: l1cache.L1Config | None = None
    l1: l1cache.L1State | None = None
    pipeline_depth: int = 2
    # keyed closure cache: (op name, cfg, ring-presence[, extras]) -> jitted
    # shard_map closure — a fresh wrapper per call would retrace every time
    _fn_cache: dict = dataclasses.field(default_factory=dict, repr=False)
    # valid-mask cache (satellite): one all-true device_put per batch shape
    # instead of a fresh transfer on every read/write/read_many call
    _ones_cache: dict = dataclasses.field(default_factory=dict, repr=False)

    def _cached_fn(self, name: str, maker, state: DHTState | None = None,
                   extra: tuple = ()):
        """Every hot wrapper (read/write/read_many/execute) fetches its
        jitted closure from here; the key captures exactly the structural
        inputs a retrace depends on — the table cfg (capacity included,
        so count-driven capacity buckets each get one trace), whether a
        membership ring is attached, and any wrapper extras (the L1
        config; the ``("async", pipeline_depth)`` tag of the pipelined
        wrappers, so sync and pipelined closures never share a slot)."""
        state = self.state if state is None else state
        key = (name, state.cfg, state.ring is None) + tuple(extra)
        fn = self._fn_cache.get(key)
        if fn is None:
            fn = maker()
            self._fn_cache[key] = fn
        return fn

    def _async_key(self) -> tuple:
        return ("async", int(self.pipeline_depth))

    @classmethod
    def create(cls, mesh: Mesh, cfg: DHTConfig, ring=None,
               l1cfg: l1cache.L1Config | None = None) -> "ShardedDHT":
        if AxisType.Explicit in mesh.axis_types:
            raise ValueError(
                "ShardedDHT needs a mesh with Auto axes (got "
                f"{mesh.axis_types}); build it with make_mesh_1d or "
                "repro.core.compat.make_mesh — the host-side gathers of "
                "membership, repair and the L1 paths fail on Explicit axes")
        n_dev = mesh.devices.size
        assert cfg.n_shards == n_dev, (
            f"one shard per device: n_shards={cfg.n_shards} != mesh size {n_dev}"
        )
        template = dht_create(cfg, ring)
        state = jax.device_put(template, _state_shardings(mesh, template))
        l1 = None
        if l1cfg is not None:
            if l1cfg.key_words != cfg.key_words or \
                    l1cfg.val_words != cfg.val_words:
                l1cfg = dataclasses.replace(
                    l1cfg, key_words=cfg.key_words, val_words=cfg.val_words)
            # one private L1 per device: leading device dim, sharded like
            # the slabs so each device sees exactly its own cache
            l1t = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n_dev,) + x.shape),
                l1cache.l1_create(l1cfg, cfg.n_shards))
            spec = shard_spec(mesh)
            l1 = jax.device_put(
                l1t, jax.tree.map(lambda _: NamedSharding(mesh, spec), l1t))
        return cls(mesh=mesh, cfg=cfg, state=state, l1cfg=l1cfg, l1=l1)

    # -- sharded ops ------------------------------------------------------
    def _specs(self, state: DHTState | None = None):
        state = self.state if state is None else state
        axes = mesh_axes(self.mesh)
        sspec = shard_spec(self.mesh)
        state_spec = jax.tree.map(lambda _: sspec, state)
        if state.ring is not None:
            state_spec.ring = jax.tree.map(lambda _: P(), state.ring)
        batch_spec = P(axes)
        return axes, state_spec, batch_spec

    def write_fn(self, state: DHTState | None = None):
        assert self.l1 is None, (
            "L1 attached: write through write() (write_refresh_fn) so the "
            "coherence watermarks refresh — a raw write round would let "
            "stale cached lines keep serving")
        axes, state_spec, batch_spec = self._specs(state)

        def fn(state, keys, vals, valid):
            state, stats = dht_ops.dht_write(
                state, keys, vals, valid, axis_name=axes)
            return state, _psum_stats(stats, axes)

        stats_spec = {k: (batch_spec if k == "code" else P())
                      for k in ("inserted", "updated", "evicted", "dropped",
                                "rounds", "lock_tokens", "epoch",
                                "wire_words", "fill_frac", "code")
                      + SKEW_KEYS}
        return jax.jit(
            shard_map(
                fn, mesh=self.mesh,
                in_specs=(state_spec, batch_spec, batch_spec, batch_spec),
                out_specs=(state_spec, stats_spec),
            )
        )

    def read_fn(self, state: DHTState | None = None):
        axes, state_spec, batch_spec = self._specs(state)

        def fn(state, keys, valid):
            state, vals, found, stats = dht_ops.dht_read(
                state, keys, valid, axis_name=axes)
            return state, vals, found, _psum_stats(stats, axes)

        stats_spec = {k: P() for k in
                      ("hits", "misses", "mismatches", "dropped",
                       "lock_tokens", "epoch", "wire_words", "fill_frac",
                       "fallback_reads")
                      + SKEW_KEYS}
        return jax.jit(
            shard_map(
                fn, mesh=self.mesh,
                in_specs=(state_spec, batch_spec, batch_spec),
                out_specs=(state_spec, batch_spec, batch_spec, stats_spec),
            )
        )

    def execute_fn(self, kinds: tuple[str, ...], state: DHTState | None = None):
        """Jitted shard_map closure over the one-round op-engine
        (``core/op_engine.dht_execute``, DESIGN.md §8) for uniform-kind
        batches: ``kinds=("migrate",)`` is the resharding get-or-put path;
        ``("read",)``/``("write",)`` mirror :meth:`read_fn`/:meth:`write_fn`.

        The returned closure maps ``(state, keys, vals, valid) ->
        (state', vals, found, code, estats)``.  With more than one kind
        (e.g. ``("read", "write")``) the batch is op-tagged and the
        closure takes the per-item ``OP_*`` tag as a fifth argument,
        ``op``."""
        assert self.l1 is None or "write" not in kinds, (
            "L1 attached: a same-epoch write round without the watermark "
            "refresh would let stale cached lines keep serving — use "
            "write().  (Get-or-put rounds are safe: W_SKIP never "
            "overwrites a present key, and epoch-bumping migrations flush "
            "the cache via the epoch stamp.)")
        axes, state_spec, batch_spec = self._specs(state)
        do_write = ("write" in kinds) or ("migrate" in kinds)
        tagged = len(kinds) > 1

        def fn(state, keys, vals, valid, *op):
            ops = dht_ops.OpBatch(
                keys=keys, valid=valid, vals=vals if do_write else None,
                op=op[0].astype(jnp.int32) if tagged else None)
            state, _, out, found, code, es = dht_ops.dht_execute(
                state, ops, kinds=kinds, axis_name=axes)
            return state, out, found, code, _psum_stats(es, axes)

        stats_spec = {k: P() for k in
                      ("mismatches", "rounds", "lock_tokens", "dropped",
                       "epoch", "wire_words", "wire_send_words",
                       "wire_reply_words", "fill_frac", "dispatch_rounds",
                       "n_shards", "capacity", "fallback_reads")
                      + SKEW_KEYS}
        return jax.jit(
            shard_map(
                fn, mesh=self.mesh,
                in_specs=(state_spec, batch_spec, batch_spec, batch_spec)
                + (batch_spec,) * tagged,
                out_specs=(state_spec, batch_spec, batch_spec, batch_spec,
                           stats_spec),
            )
        )

    def read_many_fn(self, state: DHTState | None = None):
        """Neighborhood (multi-key) read: (n, m, KW) candidate keys per
        batch row, all probed in ONE all_to_all round (DESIGN.md §6)."""
        axes, state_spec, batch_spec = self._specs(state)

        def fn(state, keys, valid):
            state, vals, found, stats = dht_ops.dht_read_many(
                state, keys, valid, axis_name=axes)
            return state, vals, found, _psum_stats(stats, axes)

        stats_spec = {k: P() for k in
                      ("hits", "misses", "mismatches", "dropped",
                       "lock_tokens", "epoch", "wire_words", "fill_frac",
                       "fallback_reads")
                      + SKEW_KEYS}
        return jax.jit(
            shard_map(
                fn, mesh=self.mesh,
                in_specs=(state_spec, batch_spec, batch_spec),
                out_specs=(state_spec, batch_spec, batch_spec, stats_spec),
            )
        )

    # -- locality tier (DESIGN.md §9) -------------------------------------
    def _l1_spec(self):
        sspec = shard_spec(self.mesh)
        return jax.tree.map(lambda _: sspec, self.l1)

    def read_cached_fn(self, state: DHTState | None = None):
        """L1-fronted read: coherent hot keys are served device-locally,
        self-owned residue skips the all_to_all (engine elision), and the
        round's reply lanes refresh the coherence watermarks."""
        axes, state_spec, batch_spec = self._specs(state)
        l1_spec = self._l1_spec()

        def fn(state, l1, keys, valid):
            l1d = jax.tree.map(lambda x: x[0], l1)
            state, l1d, vals, found, stats = dht_ops.dht_read_cached(
                state, l1d, keys, valid, axis_name=axes)
            l1 = jax.tree.map(lambda x: x[None], l1d)
            return state, l1, vals, found, _psum_stats(stats, axes)

        stats_spec = {k: P() for k in
                      ("hits", "misses", "l1_hits", "mismatches", "dropped",
                       "lock_tokens", "epoch", "wire_words", "fill_frac",
                       "fallback_reads")
                      + SKEW_KEYS}
        return jax.jit(
            shard_map(
                fn, mesh=self.mesh,
                in_specs=(state_spec, l1_spec, batch_spec, batch_spec),
                out_specs=(state_spec, l1_spec, batch_spec, batch_spec,
                           stats_spec),
            )
        )

    def write_refresh_fn(self, state: DHTState | None = None):
        """Write round that also refreshes the L1 coherence table: the
        piggybacked post-round watermarks are what invalidate every
        cached line the write obsoleted — on this device and every other
        one (all devices run the same round)."""
        axes, state_spec, batch_spec = self._specs(state)
        l1_spec = self._l1_spec()

        def fn(state, l1, keys, vals, valid):
            state, stats = dht_ops.dht_write(
                state, keys, vals, valid, axis_name=axes, l1_meta=True)
            l1d = jax.tree.map(lambda x: x[0], l1)
            l1d = l1cache.with_shard_wmarks(l1d, stats.pop("wmark_post"))
            l1 = jax.tree.map(lambda x: x[None], l1d)
            return state, l1, _psum_stats(stats, axes)

        stats_spec = {k: (batch_spec if k == "code" else P())
                      for k in ("inserted", "updated", "evicted", "dropped",
                                "rounds", "lock_tokens", "epoch",
                                "wire_words", "fill_frac", "code")
                      + SKEW_KEYS}
        return jax.jit(
            shard_map(
                fn, mesh=self.mesh,
                in_specs=(state_spec, l1_spec, batch_spec, batch_spec,
                          batch_spec),
                out_specs=(state_spec, l1_spec, stats_spec),
            )
        )

    # -- k-successor replication (DESIGN.md §13) ---------------------------
    _WRITE_REP_KEYS = ("inserted", "updated", "evicted", "dropped",
                       "rounds", "lock_tokens", "epoch", "wire_words",
                       "fill_frac", "code", "acked", "replica_writes")

    def write_replicated_fn(self, state: DHTState | None = None):
        """Replicated write round (``dht.dht_write_replicated``): every
        row fans to its k ring successors inside the SAME engine batch —
        zero extra collective rounds, only wire words.  Selected by
        :meth:`write` when ``cfg.n_replicas > 1``; the k=1 path keeps
        using :meth:`write_fn` (bit-for-bit identical to before)."""
        assert self.l1 is None, (
            "L1 attached: write through write() (write_replicated_refresh_"
            "fn) so the coherence watermarks refresh")
        axes, state_spec, batch_spec = self._specs(state)

        def fn(state, keys, vals, valid):
            state, stats = dht_ops.dht_write_replicated(
                state, keys, vals, valid, axis_name=axes)
            return state, _psum_stats(stats, axes)

        stats_spec = {k: (batch_spec if k == "code" else P())
                      for k in self._WRITE_REP_KEYS + SKEW_KEYS}
        return jax.jit(
            shard_map(
                fn, mesh=self.mesh,
                in_specs=(state_spec, batch_spec, batch_spec, batch_spec),
                out_specs=(state_spec, stats_spec),
            )
        )

    def write_replicated_refresh_fn(self, state: DHTState | None = None):
        """Replicated write that also refreshes the L1 coherence table
        (the replica copies bump k shards' watermarks in one round)."""
        axes, state_spec, batch_spec = self._specs(state)
        l1_spec = self._l1_spec()

        def fn(state, l1, keys, vals, valid):
            state, stats = dht_ops.dht_write_replicated(
                state, keys, vals, valid, axis_name=axes, l1_meta=True)
            l1d = jax.tree.map(lambda x: x[0], l1)
            l1d = l1cache.with_shard_wmarks(l1d, stats.pop("wmark_post"))
            l1 = jax.tree.map(lambda x: x[None], l1d)
            return state, l1, _psum_stats(stats, axes)

        stats_spec = {k: (batch_spec if k == "code" else P())
                      for k in self._WRITE_REP_KEYS + SKEW_KEYS}
        return jax.jit(
            shard_map(
                fn, mesh=self.mesh,
                in_specs=(state_spec, l1_spec, batch_spec, batch_spec,
                          batch_spec),
                out_specs=(state_spec, l1_spec, stats_spec),
            )
        )

    def repair_fn(self, state: DHTState | None = None):
        """Anti-entropy get-or-put round pinned to an explicit destination
        (the recovered shard).  Replica-aware routing would deliver the
        batch to the keys' live owners — where the copies already exist —
        so the ``placement`` lane overrides it (DESIGN.md §13)."""
        axes, state_spec, batch_spec = self._specs(state)

        def fn(state, keys, vals, valid, dest):
            ops = dht_ops.migrate_ops(keys, vals, valid)
            state, _, _out, found, code, es = dht_ops.dht_execute(
                state, ops, kinds=("migrate",), axis_name=axes,
                placement=(dest, state.ring.epoch))
            return state, found, code, _psum_stats(es, axes)

        stats_spec = {k: P() for k in
                      ("mismatches", "rounds", "lock_tokens", "dropped",
                       "epoch", "wire_words", "wire_send_words",
                       "wire_reply_words", "fill_frac", "dispatch_rounds",
                       "n_shards", "capacity", "fallback_reads")
                      + SKEW_KEYS}
        return jax.jit(
            shard_map(
                fn, mesh=self.mesh,
                in_specs=(state_spec, batch_spec, batch_spec, batch_spec,
                          batch_spec),
                out_specs=(state_spec, batch_spec, batch_spec, stats_spec),
            )
        )

    def read_many_refresh_fn(self, state: DHTState | None = None):
        """Neighborhood read that refreshes the L1 coherence table (the
        stencil fan-out itself is not L1-served, but its round may flag
        INVALID buckets — a meta transition cached lines must observe)."""
        axes, state_spec, batch_spec = self._specs(state)
        l1_spec = self._l1_spec()

        def fn(state, l1, keys, valid):
            state, vals, found, stats = dht_ops.dht_read_many(
                state, keys, valid, axis_name=axes, l1_meta=True)
            l1d = jax.tree.map(lambda x: x[0], l1)
            l1d = l1cache.with_shard_wmarks(l1d, stats.pop("wmark_post"))
            l1 = jax.tree.map(lambda x: x[None], l1d)
            return state, l1, vals, found, _psum_stats(stats, axes)

        stats_spec = {k: P() for k in
                      ("hits", "misses", "mismatches", "dropped",
                       "lock_tokens", "epoch", "wire_words", "fill_frac",
                       "fallback_reads")
                      + SKEW_KEYS}
        return jax.jit(
            shard_map(
                fn, mesh=self.mesh,
                in_specs=(state_spec, l1_spec, batch_spec, batch_spec),
                out_specs=(state_spec, l1_spec, batch_spec, batch_spec,
                           stats_spec),
            )
        )

    def _ones(self, shape):
        """All-true valid mask, cached per batch shape (satellite: the
        old per-call device_put showed up on every read/write)."""
        shape = (shape,) if isinstance(shape, int) else tuple(shape)
        mask = self._ones_cache.get(shape)
        if mask is None:
            mask = jax.device_put(
                jnp.ones(shape, bool),
                NamedSharding(self.mesh, P(mesh_axes(self.mesh))),
            )
            self._ones_cache[shape] = mask
        return mask

    # convenience stateful wrappers (closures come from the keyed cache).
    # Each is the host side of one executed engine round, so each flushes
    # the round's (already psum'd) stat lanes into the telemetry registry
    # — the jitted bodies above never touch it (jit-safety, DESIGN.md
    # §10).  Per-process registries merge via obs.metrics.merge_snapshots.
    def _write_dispatch(self, keys, vals, valid, extra=()):
        """One write round through the cfg-selected closure: replicated
        fan-out when ``cfg.n_replicas > 1`` (ring attached), the
        unchanged single-copy path otherwise."""
        replicated = self.cfg.n_replicas > 1 and self.ring is not None
        if self.l1 is not None:
            if replicated:
                fn = self._cached_fn("write_replicated_refresh",
                                     self.write_replicated_refresh_fn,
                                     extra=(self.l1cfg,) + extra)
            else:
                fn = self._cached_fn("write_refresh", self.write_refresh_fn,
                                     extra=(self.l1cfg,) + extra)
            self.state, self.l1, stats = fn(
                self.state, self.l1, keys, vals, valid)
        else:
            if replicated:
                fn = self._cached_fn("write_replicated",
                                     self.write_replicated_fn, extra=extra)
            else:
                fn = self._cached_fn("write", self.write_fn, extra=extra)
            self.state, stats = fn(self.state, keys, vals, valid)
        return stats

    def write(self, keys, vals, valid=None, *, max_retries: int = 2):
        """Write a batch; rows the router dropped on overflow are
        re-issued up to ``max_retries`` times (satellite of DESIGN.md
        §13: traced auto-capacity can under-provision a skewed round, and
        a silently dropped insert is a lost acked write).  Only the FINAL
        round's unrecovered drops stay on the ``dropped``/
        ``engine.dropped`` lanes; recovered rows count as ``requeued``."""
        valid = self._ones(keys.shape[0]) if valid is None else valid
        n_ops = int(keys.shape[0])
        total = None
        attempt = 0
        while True:
            t_a = time.perf_counter()
            stats = self._write_dispatch(keys, vals, valid)
            code = stats["code"]
            retry = valid & (code == dht_ops.W_DROPPED)
            n_retry = int(jnp.sum(retry))
            final = n_retry == 0 or attempt >= max_retries
            flush = dict(stats)
            if not final:
                # this round's drops are about to be re-issued — flush
                # them as requeued so engine.dropped keeps meaning
                # "lost for good" (what the CI ratio gate measures)
                flush["requeued"] = flush.pop("dropped")
            obs_trace.record_round("sharded.write", flush,
                                   ops={"write": n_ops}, t_start=t_a)
            if total is None:
                total = dict(stats)
            else:
                for lane in ("inserted", "updated", "evicted", "acked",
                             "replica_writes", "lock_tokens", "wire_words",
                             "rounds"):
                    if lane in total:
                        total[lane] = total[lane] + stats[lane]
                # a retried row's fresh outcome overrides its drop code
                total["code"] = jnp.where(code != dht_ops.W_DROPPED,
                                          code, total["code"])
                total["dropped"] = stats["dropped"]
            if final:
                total["write_retries"] = jnp.int32(attempt)
                return total
            attempt += 1
            n_ops = n_retry
            valid = retry

    def read(self, keys, valid=None):
        t0 = time.perf_counter()
        valid = self._ones(keys.shape[0]) if valid is None else valid
        if self.l1 is not None:
            fn = self._cached_fn("read_cached", self.read_cached_fn,
                                 extra=(self.l1cfg,))
            self.state, self.l1, vals, found, stats = fn(
                self.state, self.l1, keys, valid)
            source = "sharded.read_cached"
        else:
            fn = self._cached_fn("read", self.read_fn)
            self.state, vals, found, stats = fn(self.state, keys, valid)
            source = "sharded.read"
        obs_trace.record_round(source, stats,
                               ops={"read": int(keys.shape[0])}, t_start=t0)
        return vals, found, stats

    def read_many(self, keys, valid=None):
        t0 = time.perf_counter()
        if valid is None:
            valid = self._ones(keys.shape[:2])
        if self.l1 is not None:
            fn = self._cached_fn("read_many_refresh",
                                 self.read_many_refresh_fn,
                                 extra=(self.l1cfg,))
            self.state, self.l1, vals, found, stats = fn(
                self.state, self.l1, keys, valid)
        else:
            fn = self._cached_fn("read_many", self.read_many_fn)
            self.state, vals, found, stats = fn(self.state, keys, valid)
        obs_trace.record_round(
            "sharded.read_many", stats,
            ops={"read": int(keys.shape[0] * keys.shape[1])}, t_start=t0)
        return vals, found, stats

    # -- issue/commit pipelined wrappers (DESIGN.md §12) -------------------
    # The jitted closures are asynchronous already — a call returns device
    # futures immediately — so the issue half is simply "call and don't
    # fetch".  The sync wrappers above fetch eagerly when they flush the
    # stat lanes to the registry (int()/record_round force the scalars);
    # these defer that fetch to the commit half, letting the caller run
    # compute (or issue the next round) against the in-flight collective.

    def read_async(self, keys, valid=None) -> ShardedRound:
        """Issue a read round without waiting; pair with
        :meth:`read_commit`.  At most ``pipeline_depth`` rounds should be
        in flight (use :meth:`round_queue`)."""
        t0 = time.perf_counter()
        valid = self._ones(keys.shape[0]) if valid is None else valid
        if self.l1 is not None:
            fn = self._cached_fn("read_cached", self.read_cached_fn,
                                 extra=(self.l1cfg,) + self._async_key())
            self.state, self.l1, vals, found, stats = fn(
                self.state, self.l1, keys, valid)
            source = "sharded.read_cached"
        else:
            fn = self._cached_fn("read", self.read_fn,
                                 extra=self._async_key())
            self.state, vals, found, stats = fn(self.state, keys, valid)
            source = "sharded.read"
        return ShardedRound(source=source, outs=(vals, found), stats=stats,
                            ops={"read": int(keys.shape[0])}, t_start=t0,
                            t_issued=time.perf_counter())

    def write_async(self, keys, vals, valid=None) -> ShardedRound:
        """Issue a write round without waiting; pair with
        :meth:`write_commit`."""
        t0 = time.perf_counter()
        valid = self._ones(keys.shape[0]) if valid is None else valid
        stats = self._write_dispatch(keys, vals, valid,
                                     extra=self._async_key())
        # no retry loop here — it would force a mid-pipeline fetch; the
        # pipelined caller re-issues dropped rows itself (the surrogate
        # driver retires only non-dropped keys from its PendingWrites)
        return ShardedRound(source="sharded.write",
                            outs=(stats["code"],), stats=stats,
                            ops={"write": int(keys.shape[0])}, t_start=t0,
                            t_issued=time.perf_counter())

    def _commit(self, rnd: ShardedRound) -> tuple:
        assert not rnd.committed, "ShardedRound committed twice"
        rnd.committed = True
        t_commit = time.perf_counter()
        jax.block_until_ready(rnd.outs)
        now = time.perf_counter()
        dur = max(now - rnd.t_start, 0.0)
        hidden = max(t_commit - rnd.t_issued, 0.0)
        stats = dict(rnd.stats)
        stats["issue_us"] = (rnd.t_issued - rnd.t_start) * 1e6
        stats["hidden_us"] = hidden * 1e6
        stats["commit_wait_us"] = max(now - t_commit, 0.0) * 1e6
        stats["overlap_frac"] = min(hidden / dur, 1.0) if dur > 0 else 0.0
        obs_trace.record_round(
            rnd.source, stats, ops=rnd.ops, t_start=rnd.t_start,
            phase_marks=[("issue", rnd.t_start), ("hidden", rnd.t_issued),
                         ("commit", t_commit)])
        return rnd.outs + (stats,)

    def read_commit(self, rnd: ShardedRound):
        """Commit an issued read -> ``(vals, found, stats)``; ``stats``
        gains the overlap lanes (``issue_us`` / ``hidden_us`` /
        ``commit_wait_us`` / ``overlap_frac``)."""
        assert rnd.source in ("sharded.read", "sharded.read_cached"), rnd
        return self._commit(rnd)

    def write_commit(self, rnd: ShardedRound):
        """Commit an issued write -> ``stats`` (with overlap lanes)."""
        assert rnd.source == "sharded.write", rnd
        return self._commit(rnd)[-1]

    def round_queue(self, commit=None) -> RoundQueue:
        """A ``pipeline_depth``-deep FIFO for this table's in-flight
        rounds (depth 2 = double buffering); ``commit`` defaults to the
        source-dispatching :meth:`_commit`."""
        return RoundQueue(self.pipeline_depth, commit or self._commit)

    def telemetry_snapshot(self) -> dict:
        """This process's registry snapshot (see
        ``obs.metrics.merge_snapshots`` for cross-process aggregation)."""
        return obs_metrics.get_registry().snapshot()

    # -- elastic membership (DESIGN.md §4-5) ------------------------------
    @property
    def ring(self):
        return self.state.ring

    def apply_ring(self, new_ring, batch: int = 512) -> dict:
        """Online in-place resharding to ``new_ring`` on the sharded
        backend: owner-changed entries stream in bounded batches through
        the shard_map/all_to_all op-engine as get-or-put rounds — presence
        guard and insert in ONE collective round per batch (extraction of
        the source entries is host-side, like the paper's migration
        driver)."""
        from . import migrate  # local import: migrate is backend-agnostic

        n_dev = self.mesh.devices.size
        batch = -(-batch // n_dev) * n_dev  # multiple of the mesh size
        plan = migrate.plan_migration(self.state, new_ring, self.cfg)
        assert plan.inplace, "sharded backend reshards in place (fixed mesh)"

        # open the new epoch: same slabs, new ring, per-batch capacity
        mig_cfg = dataclasses.replace(plan.mig_cfg, capacity=batch // n_dev)
        new_state = DHTState(mig_cfg, self.state.keys, self.state.vals,
                             self.state.meta, self.state.csum, new_ring)
        new_state = jax.device_put(
            new_state, _state_shardings(self.mesh, new_state))
        efn = self._cached_fn(
            "execute", lambda: self.execute_fn(("migrate",), new_state),
            state=new_state, extra=(("migrate",),))

        kw, vw = self.cfg.key_words, self.cfg.val_words
        src_keys = np.asarray(self.state.keys).reshape(-1, kw)
        src_vals = np.asarray(self.state.vals).reshape(-1, vw)
        bspec = NamedSharding(self.mesh, P(mesh_axes(self.mesh)))
        moved = evicted = 0
        for lo in range(0, plan.n_moved, batch):
            t_b = time.perf_counter()
            idx = plan.src[lo:lo + batch]
            n = int(idx.shape[0])
            pad = np.zeros((batch,), np.int64)
            pad[:n] = idx
            keys = jax.device_put(jnp.asarray(src_keys[pad]), bspec)
            vals = jax.device_put(jnp.asarray(src_vals[pad]), bspec)
            valid = jax.device_put(
                jnp.asarray(np.arange(batch) < n), bspec)
            new_state, _, found, code, es = efn(new_state, keys, vals, valid)
            obs_trace.record_round("sharded.migrate", es,
                                   ops={"migrate": n}, t_start=t_b)
            assert int(es["dropped"]) == 0
            moved += int(jnp.sum(valid & ~found))
            evicted += int(jnp.sum(code == dht_ops.W_EVICT))

        # retire: reclaim source buckets whose stored key now lives
        # elsewhere (shared invariant: migrate.stale_sources)
        meta = np.array(new_state.meta)
        csum = np.array(new_state.csum)
        if plan.n_moved:
            s_idx, b_idx, foreign = migrate.stale_sources(
                new_state.keys, plan.src, new_ring,
                self.cfg.buckets_per_shard)
            meta[s_idx[foreign], b_idx[foreign]] = 0
            csum[s_idx[foreign], b_idx[foreign]] = 0
        final = DHTState(self.cfg, new_state.keys, new_state.vals,
                         jnp.asarray(meta), jnp.asarray(csum), new_ring)
        self.state = jax.device_put(final, _state_shardings(self.mesh, final))
        result = {"n_live": plan.n_live, "n_planned": plan.n_moved,
                  "moved": moved, "evicted_at_dest": evicted,
                  "epoch": int(new_ring.epoch)}
        obs_metrics.inc("migrate.moved", moved)
        obs_metrics.inc("migrate.evicted", evicted)
        obs_trace.record_event("sharded.apply_ring", result)
        return result

    def leave(self, shard_id: int, batch: int = 512) -> dict:
        from .membership import ring_create, ring_leave

        ring = self.ring or ring_create(self.cfg.n_shards)
        return self.apply_ring(ring_leave(ring, shard_id), batch)

    def join(self, shard_id: int, batch: int = 512) -> dict:
        from .membership import ring_join

        assert self.ring is not None, "join needs a ring"
        return self.apply_ring(ring_join(self.ring, shard_id), batch)

    # -- crash tolerance (DESIGN.md §13) ----------------------------------
    def crash(self, shard_id: int, *, wipe: bool = True) -> None:
        """Abrupt shard death: liveness bit down, epoch + 1, placement
        preserved (``membership.ring_crash``) and — by default — the dead
        shard's slab rows wiped.  Reads fail over to ring successors in
        the same number of collective rounds; with ``cfg.n_replicas > 1``
        every acked write survives on the surviving copies.  The epoch
        bump is the L1's crash fence (every pre-crash line goes
        epoch-stale), so no explicit cache flush is needed."""
        from .membership import ring_crash

        assert self.ring is not None, "crash tolerance needs a ring"
        new_ring = ring_crash(self.ring, shard_id)
        keys, vals = self.state.keys, self.state.vals
        meta, csum = self.state.meta, self.state.csum
        if wipe:
            keys = np.array(keys); keys[shard_id] = 0
            vals = np.array(vals); vals[shard_id] = 0
            meta = np.array(meta); meta[shard_id] = 0
            csum = np.array(csum); csum[shard_id] = 0
            keys, vals = jnp.asarray(keys), jnp.asarray(vals)
            meta, csum = jnp.asarray(meta), jnp.asarray(csum)
        final = DHTState(self.cfg, keys, vals, meta, csum, new_ring)
        self.state = jax.device_put(final, _state_shardings(self.mesh, final))
        obs_metrics.inc("faults.crashes")
        obs_trace.record_event("sharded.crash", {"shard": shard_id,
                                                 "wipe": int(wipe)})

    def recover(self, shard_id: int) -> None:
        """The crashed shard returns (empty) at epoch + 1; run
        :meth:`repair` to re-converge its replica set."""
        from .membership import ring_recover

        assert self.ring is not None, "crash tolerance needs a ring"
        final = DHTState(self.cfg, self.state.keys, self.state.vals,
                         self.state.meta, self.state.csum,
                         ring_recover(self.ring, shard_id))
        self.state = jax.device_put(final, _state_shardings(self.mesh, final))
        obs_metrics.inc("faults.recoveries")
        obs_trace.record_event("sharded.recover", {"shard": shard_id})

    def repair(self, shard_id: int, batch: int = 512) -> dict:
        """Anti-entropy repair of a recovered shard on the sharded
        backend: the generation-watermark diff (``migrate.plan_repair``)
        enumerates exactly the replica copies the shard lost, then
        bounded get-or-put batches stream them back through the
        shard_map/all_to_all engine with an explicit placement lane —
        low-priority background traffic on the query data path, NOT a
        table scan (DESIGN.md §13)."""
        from . import migrate  # local import: migrate is backend-agnostic

        assert self.ring is not None and bool(self.ring.alive[shard_id]), (
            "repair target must be recovered (live) first")
        n_dev = self.mesh.devices.size
        batch = -(-batch // n_dev) * n_dev
        t0 = time.perf_counter()
        plan = migrate.plan_repair(self.state, shard_id)

        # explicit capacity: the whole batch routes to ONE destination
        # bin, so each device's full local slice must fit (the traced
        # auto heuristic assumes a spread and would drop most rows)
        rep_cfg = dataclasses.replace(self.cfg, capacity=batch // n_dev)
        rep_state = DHTState(rep_cfg, self.state.keys, self.state.vals,
                             self.state.meta, self.state.csum, self.ring)
        rep_state = jax.device_put(
            rep_state, _state_shardings(self.mesh, rep_state))
        fn = self._cached_fn("repair", lambda: self.repair_fn(rep_state),
                             state=rep_state)

        kw, vw = self.cfg.key_words, self.cfg.val_words
        src_keys = np.asarray(self.state.keys).reshape(-1, kw)
        src_vals = np.asarray(self.state.vals).reshape(-1, vw)
        bspec = NamedSharding(self.mesh, P(mesh_axes(self.mesh)))
        dest = jax.device_put(
            jnp.full((batch,), shard_id, jnp.int32), bspec)
        healed = skipped = rounds = 0
        for lo in range(0, plan.n_missing, batch):
            t_b = time.perf_counter()
            idx = plan.src[lo:lo + batch]
            n = int(idx.shape[0])
            pad = np.zeros((batch,), np.int64)
            pad[:n] = idx
            keys = jax.device_put(jnp.asarray(src_keys[pad]), bspec)
            vals = jax.device_put(jnp.asarray(src_vals[pad]), bspec)
            valid = jax.device_put(jnp.asarray(np.arange(batch) < n), bspec)
            rep_state, found, _code, es = fn(
                rep_state, keys, vals, valid, dest)
            obs_trace.record_round("sharded.repair", es,
                                   ops={"migrate": n}, t_start=t_b)
            assert int(es["dropped"]) == 0, "repair round overflowed"
            healed += int(jnp.sum(valid & ~found))
            skipped += int(jnp.sum(valid & found))
            rounds += 1

        final = DHTState(self.cfg, rep_state.keys, rep_state.vals,
                         rep_state.meta, rep_state.csum, self.ring)
        self.state = jax.device_put(final, _state_shardings(self.mesh, final))
        result = {"n_candidates": plan.n_candidates,
                  "n_present": plan.n_present,
                  "n_planned": plan.n_missing,
                  "healed": healed, "skipped": skipped, "rounds": rounds,
                  "diff_after": migrate.repair_diff(self.state, shard_id)}
        obs_metrics.inc("repair.rounds", rounds)
        obs_metrics.inc("repair.keys_healed", healed)
        obs_trace.record_event("sharded.repair_run", result, t_start=t0)
        return result


def make_mesh_1d(n: int | None = None, name: str = "dht") -> Mesh:
    """One-axis ``Auto`` mesh over the first ``n`` devices (all by
    default) — one DHT shard per device."""
    devs = jax.devices()
    n = n or len(devs)
    return make_mesh((n,), (name,), devices=devs[:n])
