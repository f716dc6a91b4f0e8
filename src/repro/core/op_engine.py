"""Unified one-round op-engine for the DHT hot path (DESIGN.md §8).

Every DHT operation is a *request record* — an op tag (``OP_READ`` /
``OP_WRITE`` / ``OP_MIGRATE``), a key, and (for the writing kinds) a value
— and :func:`dht_execute` dispatches an arbitrary mix of them in **one**
routing round: one ``bin_by_dest``/``dispatch``/``collect`` cycle on both
backends.  The public wrappers in ``core/dht.py`` (``dht_read``,
``dht_write``, the ``_many`` and ``_dual`` variants) are thin shims over
this engine, as are the surrogate write-back and migration paths.

Mixed-op serialization contract (the engine's analogue of the paper's
consistency modes, DESIGN.md §2/§8):

- All probing ops (``OP_READ`` and the presence check of ``OP_MIGRATE``)
  observe the table **as of the start of the round** (snapshot).
- Write application follows: lock-free in a single optimistic pass
  (bounded re-probe on slot conflicts), fine/coarse in conflict-ranked
  rounds with the same lock-token accounting as before — ranked rounds now
  cover the write side of a mixed batch, and probing ops are charged one
  shared-lock round trip.

``OP_MIGRATE`` is the compound get-or-put the migration and surrogate
write-back paths need: return the stored value if the key is present
(code ``W_SKIP``), else insert the carried value — the read-then-
write-if-absent sequence that used to cost two collective rounds.

Dual-epoch probing rides the same round: when ``prev`` (the previous-
epoch table of an in-flight migration) is supplied, each request carries
an epoch-select lane and is routed to the owner under *that* epoch's
placement; the per-shard handler probes the corresponding slab.  A
dual-epoch read is therefore one dispatch, not two sequential reads.

Issue/commit split (DESIGN.md §12): :func:`dht_execute` is now the
composition of two halves.  :func:`dht_issue` runs the whole
bin/dispatch/apply/collect cycle *asynchronously* — JAX's async dispatch
means every returned array is a future — and packages the results into
an :class:`InFlightRound` handle; :func:`dht_commit` waits for the
round's replies, resolves any pending-write forwards, and flushes the
round's telemetry (with issue/hidden/commit marks and an ``overlap_frac``
lane measuring what fraction of the round's latency the caller hid by
doing other work between the two calls).  Because JAX chains dataflow
through the returned ``state``, issuing round N+1 against round N's
un-committed output state is safe and bit-for-bit equal to the
synchronous sequence — the only read-after-write hazard is a *promised*
write that has not been issued yet, which the ``pending`` conflict
filter handles (see ``core/pipeline.py``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from . import faults as _faults
from . import routing
from .hashing import (
    base_bucket,
    checksum32,
    hash64,
    owner_shard,
    probe_indices,
    ring_owner,
)
from .layout import (
    GEN_SHIFT,
    INVALID,
    MODE_FINE,
    MODE_LOCKFREE,
    OCCUPIED,
    DHTConfig,
    DHTState,
    shard_watermark,
)

# op tags — the request-record discriminator
OP_READ = 0
OP_WRITE = 1
OP_MIGRATE = 2   # get-or-put: present -> return stored value, absent -> insert

# per-item result codes
W_DROPPED = 0   # routing overflow — not applied (cache-miss semantics)
W_INSERT = 1
W_UPDATE = 2
W_EVICT = 3     # probe window exhausted -> overwrote last candidate (paper policy)
W_SKIP = 4      # OP_MIGRATE: key already present in this epoch — nothing written

KINDS = ("read", "write", "migrate")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class OpBatch:
    """An op-tagged request batch: the engine's unit of work.

    ``op``/``vals``/``esel`` are optional lanes — a uniform-kind batch
    (every request the same tag, the wrapper fast path) omits ``op`` and
    states its kind statically via ``dht_execute(..., kinds=)``, so the
    dispatched payload is exactly what the pre-engine per-kind rounds
    sent.  ``esel`` selects the epoch to probe (0 = ``state``, 1 =
    ``prev``) and is only meaningful with a dual-epoch execute."""

    keys: jnp.ndarray               # (n, KW) uint32
    valid: jnp.ndarray              # (n,) bool
    op: jnp.ndarray | None = None   # (n,) int32 tag; None = uniform batch
    vals: jnp.ndarray | None = None  # (n, VW) uint32 write/migrate payload
    esel: jnp.ndarray | None = None  # (n,) int32 epoch select (dual-epoch)

    def tree_flatten(self):
        return (self.keys, self.valid, self.op, self.vals, self.esel), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


def _default_valid(keys: jnp.ndarray, valid) -> jnp.ndarray:
    if valid is None:
        return jnp.ones((keys.shape[0],), bool)
    return valid


def read_ops(keys: jnp.ndarray, valid=None) -> OpBatch:
    """Uniform read batch (pair with ``kinds=("read",)``)."""
    return OpBatch(keys=keys, valid=_default_valid(keys, valid))


def write_ops(keys: jnp.ndarray, vals: jnp.ndarray, valid=None) -> OpBatch:
    """Uniform write batch (pair with ``kinds=("write",)``)."""
    return OpBatch(keys=keys, valid=_default_valid(keys, valid),
                   vals=vals.astype(jnp.uint32))


def migrate_ops(keys: jnp.ndarray, vals: jnp.ndarray, valid=None) -> OpBatch:
    """Uniform get-or-put batch (pair with ``kinds=("migrate",)``)."""
    return OpBatch(keys=keys, valid=_default_valid(keys, valid),
                   vals=vals.astype(jnp.uint32))


def mixed_ops(op: jnp.ndarray, keys: jnp.ndarray, vals: jnp.ndarray,
              valid=None, esel=None) -> OpBatch:
    """Explicitly tagged mixed batch."""
    return OpBatch(keys=keys, valid=_default_valid(keys, valid),
                   op=op.astype(jnp.int32), vals=vals.astype(jnp.uint32),
                   esel=None if esel is None else esel.astype(jnp.int32))


def dual_fusable(cfg: DHTConfig, prev_cfg: DHTConfig) -> bool:
    """Whether a dual-epoch probe can ride one round: the two epochs'
    slabs must agree on the record geometry (word widths, probe window)
    and the previous shard set must be addressable inside the current
    routing space (always true for in-place migrations, whose slab rows
    are the union of the two shard sets)."""
    return (
        prev_cfg.key_words == cfg.key_words
        and prev_cfg.val_words == cfg.val_words
        and prev_cfg.n_probe == cfg.n_probe
        and prev_cfg.n_shards <= cfg.n_shards
    )


# ---------------------------------------------------------------------------
# shard-side machinery
# ---------------------------------------------------------------------------

def _conflict_rank(group: jnp.ndarray, valid: jnp.ndarray,
                   n_groups: int | None = None) -> jnp.ndarray:
    """Rank of each valid item among items of the same conflict group
    (stable in item order).  One definition for the whole substrate:
    this is the same sort-based rank that bins routing destinations and
    MoE tokens (``routing.stable_rank_by_group``); a caller that bounds
    the group ids gets the packed single-sort fast path."""
    return routing.stable_rank_by_group(group, valid, n_groups=n_groups)


def _gather_window(slab: dict[str, jnp.ndarray], idx: jnp.ndarray):
    """Gather the (C, P) probe windows from a shard slab."""
    return {
        "keys": slab["keys"][idx],   # (C, P, KW)
        "vals": slab["vals"][idx],   # (C, P, VW)
        "meta": slab["meta"][idx],   # (C, P)
        "csum": slab["csum"][idx],   # (C, P)
    }


def _probe_window(win, keys):
    """Shared read-probe core: first occupied, non-INVALID, key-equal
    candidate wins.  Returns (has, sel, val, stored_csum)."""
    occupied = (win["meta"] & OCCUPIED) != 0
    invalid = (win["meta"] & INVALID) != 0
    keymatch = jnp.all(win["keys"] == keys[:, None, :], axis=-1) & occupied & ~invalid
    has = jnp.any(keymatch, axis=-1)
    sel = jnp.argmax(keymatch, axis=-1).astype(jnp.int32)
    val = jnp.take_along_axis(win["vals"], sel[:, None, None], axis=1)[:, 0, :]
    stored_csum = jnp.take_along_axis(win["csum"], sel[:, None], axis=1)[:, 0]
    return has, sel, val, stored_csum


def _choose_write_slot(cfg: DHTConfig, win, keys):
    """Paper §3.1 probe policy: same key -> update; else first writable
    (empty or invalid); else overwrite the last candidate."""
    occupied = (win["meta"] & OCCUPIED) != 0
    invalid = (win["meta"] & INVALID) != 0
    keymatch = jnp.all(win["keys"] == keys[:, None, :], axis=-1) & occupied
    writable = (~occupied) | invalid
    has_match = jnp.any(keymatch, axis=-1)
    has_empty = jnp.any(writable, axis=-1)
    first_match = jnp.argmax(keymatch, axis=-1).astype(jnp.int32)
    first_empty = jnp.argmax(writable, axis=-1).astype(jnp.int32)
    sel = jnp.where(
        has_match, first_match,
        jnp.where(has_empty, first_empty, jnp.int32(cfg.n_probe - 1)),
    )
    return sel, has_match, has_empty


def _write_pass(cfg: DHTConfig, slab, base, keys, vals, active):
    """One probe-and-publish pass (== one MPI_Get + MPI_Put round trip in
    the paper's write).  Simultaneous writers on one bucket resolve
    deterministically: highest item index wins ("last writer wins",
    reproducibly)."""
    c = base.shape[0]
    b = cfg.buckets_per_shard
    idx = probe_indices(base, cfg.n_probe)          # (C, P)
    win = _gather_window(slab, idx)
    sel, has_match, has_empty = _choose_write_slot(cfg, win, keys)
    slot = base + sel                                # (C,) absolute bucket
    iota = jnp.arange(c, dtype=jnp.int32)

    # deterministic winner per slot
    prio = jnp.where(active, iota, jnp.int32(-1))
    winner = jnp.full((b,), -1, jnp.int32).at[
        jnp.where(active, slot, b)
    ].max(prio, mode="drop")
    is_winner = active & (winner[slot] == prio)
    wslot = jnp.where(is_winner, slot, b)            # b = dropped row

    old_gen = slab["meta"][slot] >> GEN_SHIFT
    new_meta = jnp.uint32(OCCUPIED) | ((old_gen + 1) << GEN_SHIFT)
    new_csum = checksum32(keys, vals)

    slab = dict(slab)
    slab["keys"] = slab["keys"].at[wslot].set(keys, mode="drop")
    slab["vals"] = slab["vals"].at[wslot].set(vals, mode="drop")
    slab["meta"] = slab["meta"].at[wslot].set(new_meta, mode="drop")
    slab["csum"] = slab["csum"].at[wslot].set(new_csum, mode="drop")

    kind = jnp.where(
        has_match, W_UPDATE, jnp.where(has_empty, W_INSERT, W_EVICT)
    ).astype(jnp.int32)
    # an item is settled when its key now sits at its chosen slot (it won, or
    # a same-key duplicate with higher index won — correct last-writer-wins);
    # losers to a *different* key re-probe, exactly like the paper's write
    # loop finding the bucket taken and moving to the next candidate.
    stored = slab["keys"][slot]
    same_key = jnp.all(stored == keys, axis=-1)
    retry = active & ~same_key & (kind != W_EVICT)
    return slab, kind, retry


def _apply_writes(cfg: DHTConfig, slab, base, keys, vals, valid):
    """Probe-loop write for one shard: bounded retry passes make concurrent
    inserts land on successive candidates instead of silently losing
    (paper §3.1 write policy under concurrency).  Returns
    (slab', per-item code, n_passes)."""

    def body(carry):
        slab_c, active, code, it = carry
        slab_n, kind, retry = _write_pass(cfg, slab_c, base, keys, vals, active)
        code = jnp.where(active, kind, code)
        return slab_n, retry, code, it + 1

    def cond(carry):
        _, active, _, it = carry
        return jnp.any(active) & (it < cfg.n_probe)

    code0 = jnp.zeros(base.shape, jnp.int32)  # W_DROPPED
    slab, _, code, passes = jax.lax.while_loop(
        cond, body, (dict(slab), valid, code0, jnp.int32(0))
    )
    return slab, code, passes


def _validate_and_flag(cfg: DHTConfig, slab, keys, val, stored_csum, slot,
                       mask, has):
    """Lock-free checksum validation + INVALID reclaim flagging — the ONE
    definition of the mismatch policy (paper §4.2), shared by the engine's
    shard handler and the server-KV baseline's ``_apply_reads``.

    In the synchronous SPMD path a re-get returns identical bytes, so a
    mismatch is treated as persistent after ``max_read_retries`` logical
    retries and the bucket is flagged INVALID so writers may reclaim it —
    the retry loop does real work in the async host path
    (``core/async_sim.py``).  Returns (slab', found, mismatch, n_mismatch)."""
    ok = checksum32(keys, val) == stored_csum
    mismatch = mask & has & ~ok
    mslot = jnp.where(mismatch, slot, cfg.buckets_per_shard)
    slab = dict(slab)
    slab["meta"] = slab["meta"].at[mslot].set(
        slab["meta"][slot] | jnp.uint32(INVALID), mode="drop"
    )
    found = mask & has & ok
    return slab, found, mismatch, jnp.sum(mismatch).astype(jnp.int32)


def _apply_reads(cfg: DHTConfig, slab, base, keys, valid):
    """Vectorized probe + (lock-free) checksum validation for one shard.
    Returns (slab', values, found, mismatches)."""
    idx = probe_indices(base, cfg.n_probe)
    win = _gather_window(slab, idx)
    has, sel, val, stored_csum = _probe_window(win, keys)
    slot = base + sel

    if cfg.mode == MODE_LOCKFREE:
        slab, found, _mm, n_mismatch = _validate_and_flag(
            cfg, slab, keys, val, stored_csum, slot, valid, has)
    else:
        found = valid & has
        n_mismatch = jnp.int32(0)

    val = jnp.where(found[:, None], val, jnp.uint32(0))
    return slab, val, found, n_mismatch


def _lock_token(axis_name, n_shards: int) -> jnp.ndarray:
    """One acquire/release round-trip's worth of traffic.  The returned
    token is threaded into the stats so the collective is not DCE'd."""
    if axis_name is None:
        return jnp.int32(1)
    probe = jnp.ones((n_shards, 1), jnp.int32)
    out = jax.lax.all_to_all(probe, axis_name, 0, 0)
    return jnp.sum(out).astype(jnp.int32)


def _locked_write_rounds(cfg: DHTConfig, slab, base, keys, vals, valid, axis_name):
    """fine/coarse modes: serialize conflicting writes into rounds."""
    if cfg.mode == MODE_FINE:
        group = base                      # per-bucket lock granularity
    else:
        group = jnp.zeros_like(base)      # whole-window lock
    rank = _conflict_rank(group, valid, n_groups=cfg.buckets_per_shard)
    rounds = jnp.max(jnp.where(valid, rank, -1)) + 1
    if axis_name is not None:
        # uniform trip count across devices — collectives live in the body
        rounds = jax.lax.pmax(rounds, axis_name)

    code0 = jnp.zeros_like(rank)

    def body(carry):
        r, slab_c, code_c, tok = carry
        mask = valid & (rank == r)
        slab_n, code_r, _passes = _apply_writes(cfg, slab_c, base, keys, vals, mask)
        code_c = jnp.where(mask, code_r, code_c)
        # acquire + release traffic per round (2 RTs) — paper §3.5/§4.1
        tok = tok + _lock_token(axis_name, cfg.n_shards) * 2
        return r + 1, slab_n, code_c, tok

    def cond(carry):
        return carry[0] < rounds

    _, slab, code, tok = jax.lax.while_loop(
        cond, body, (jnp.int32(0), slab, code0, jnp.int32(0))
    )
    return slab, code, rounds.astype(jnp.int32), tok


def _shard_write(cfg: DHTConfig, slab, base, keys, vals, valid, axis_name):
    if cfg.mode == MODE_LOCKFREE:
        slab, code, passes = _apply_writes(cfg, slab, base, keys, vals, valid)
        return slab, code, passes, jnp.int32(0)
    return _locked_write_rounds(cfg, slab, base, keys, vals, valid, axis_name)


def _shard_apply(cfg: DHTConfig, prev_cfg: DHTConfig | None,
                 slab, slab_prev, base, keys, vals, op, esel, valid,
                 axis_name, kinds: tuple[str, ...]):
    """Apply one shard's slice of a mixed request batch.

    The serialization contract: probing ops (reads and migrate presence
    checks) observe the slab as of round start; writes apply after, under
    the mode's schedule (``_shard_write``).  Dual-epoch requests probe
    ``slab_prev`` when their epoch-select lane says so; writes only ever
    target the current-epoch slab.

    Besides the per-item results, the handler reports the locality-tier
    coherence metadata (DESIGN.md §9): the snapshot generation of each
    item's serving bucket (``gen``, garbage where nothing matched — L1
    fills mask on ``found``) and this shard's meta watermark before
    (``wpre``) and after (``wpost``) the round's mutations.  Both ride
    the existing reply lanes when the caller asks for them."""
    do_probe = ("read" in kinds) or ("migrate" in kinds)
    do_write = ("write" in kinds) or ("migrate" in kinds)
    wpre = shard_watermark(slab["meta"])

    if op is None:
        assert len(kinds) == 1, "untagged batches must be uniform-kind"
        only = kinds[0]
        m_probe = valid if only != "write" else jnp.zeros_like(valid)
        m_migrate = valid if only == "migrate" else jnp.zeros_like(valid)
        m_write = valid if only == "write" else jnp.zeros_like(valid)
    else:
        m_probe = valid & (op != OP_WRITE)
        m_migrate = valid & (op == OP_MIGRATE)
        m_write = valid & (op == OP_WRITE)

    c = base.shape[0]
    vw = slab["vals"].shape[-1]
    val = jnp.zeros((c, vw), jnp.uint32)
    found = jnp.zeros((c,), bool)
    gen = jnp.zeros((c,), jnp.uint32)
    n_mm = jnp.int32(0)
    tok = jnp.int32(0)

    if do_probe:
        idx = probe_indices(base, cfg.n_probe)
        win = _gather_window(slab, idx)
        if slab_prev is not None:
            win_prev = _gather_window(slab_prev, idx)
            in_prev = (esel == 1)

            def _sel(cur, old):
                m = in_prev.reshape((-1,) + (1,) * (cur.ndim - 1))
                return jnp.where(m, old, cur)

            win = {k: _sel(win[k], win_prev[k]) for k in win}
        has, sel, pval, stored_csum = _probe_window(win, keys)
        slot = base + sel
        gen = (jnp.take_along_axis(win["meta"], sel[:, None], axis=1)[:, 0]
               >> jnp.uint32(GEN_SHIFT))

        if cfg.mode == MODE_LOCKFREE:
            if slab_prev is None:
                slab, found, _mm, n_mm = _validate_and_flag(
                    cfg, slab, keys, pval, stored_csum, slot, m_probe, has)
            else:
                # flag persistently diverging buckets INVALID in whichever
                # epoch's slab was probed, so its writers may reclaim them
                slab, found_new, mm_new, _ = _validate_and_flag(
                    cfg, slab, keys, pval, stored_csum, slot,
                    m_probe & ~in_prev, has)
                slab_prev, found_old, mm_old, _ = _validate_and_flag(
                    prev_cfg, slab_prev, keys, pval, stored_csum, slot,
                    m_probe & in_prev, has)
                found = found_new | found_old
                n_mm = jnp.sum(mm_new | mm_old).astype(jnp.int32)
        else:
            found = m_probe & has
        val = jnp.where(found[:, None], pval, jnp.uint32(0))

        if cfg.mode != MODE_LOCKFREE:
            tok = _lock_token(axis_name, cfg.n_shards) * 2  # shared lock RTs

    code = jnp.zeros((c,), jnp.int32)
    rounds = jnp.int32(0)
    if do_write:
        wmask = m_write | (m_migrate & ~found)
        slab, wcode, rounds, tok_w = _shard_write(
            cfg, slab, base, keys, vals, wmask, axis_name)
        tok = tok + tok_w
        code = jnp.where(
            wmask, wcode,
            jnp.where(m_migrate & found, jnp.int32(W_SKIP), jnp.int32(0)),
        )

    wpost = shard_watermark(slab["meta"])
    return (slab, slab_prev, val, found, code, n_mm, rounds, tok,
            gen, wpre, wpost)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def replica_placement(state: DHTState, h_hi):
    """Crash-tolerant placement under k-successor replication
    (DESIGN.md §13): route to the key's owner unless its liveness bit is
    down, in which case fall back to the first *live* shard of the key's
    precomputed successor set.  Returns ``(dest, epoch, fallback)`` where
    ``fallback`` marks items not served by their owner.  Requires a ring
    and ``cfg.n_replicas > 1`` (the successor table's column 0 is the
    owner, so a fully-live ring routes identically to ``ring_owner``)."""
    from .membership import ring_successors

    r = state.ring
    succ = ring_successors(r, h_hi, state.cfg.n_replicas)   # (..., k)
    own = succ[..., 0]
    s = r.alive.shape[0]
    ok = (succ >= 0) & r.alive[jnp.clip(succ, 0, s - 1)]
    col = jnp.argmax(ok, axis=-1)
    dest = jnp.take_along_axis(succ, col[..., None], axis=-1)[..., 0]
    # no live replica at all (every successor down): keep the owner — the
    # probe misses / the write drops, exactly like an unreachable rank
    dest = jnp.where(jnp.any(ok, axis=-1), dest, own)
    fallback = dest != own
    return dest.astype(jnp.int32), r.epoch, fallback


def _owner_epoch(state: DHTState, h_hi):
    """Owner placement under this table's membership: static modulo
    (paper) or consistent-hash ring (DESIGN.md §4).  With replication
    enabled (``cfg.n_replicas > 1``) the owner lookup is the crash-
    tolerant replica select — reads and writes transparently fail over
    to the first live successor of a dead owner."""
    if state.ring is None:
        return owner_shard(h_hi, state.cfg.n_shards), jnp.int32(0)
    r = state.ring
    if state.cfg.n_replicas > 1:
        dest, epoch, _fb = replica_placement(state, h_hi)
        return dest, epoch
    return ring_owner(h_hi, r.positions, r.owners, r.n_live), r.epoch


def _flat_axis_index(axis_name) -> jnp.ndarray:
    """This device's flattened shard id under (possibly multi-axis)
    shard_map — row-major over the axis tuple, matching how
    ``distributed.shard_spec`` flattens the mesh."""
    names = axis_name if isinstance(axis_name, (tuple, list)) else (axis_name,)
    idx = jnp.int32(0)
    for name in names:
        idx = idx * jax.lax.psum(1, name) + jax.lax.axis_index(name)
    return idx


def _route_ops(state: DHTState, prev: DHTState | None, ops: OpBatch,
               capacity: int | None, hashes=None, bin_valid=None,
               placement=None):
    """One binning for the whole batch: each request routed to its owner
    under the epoch its ``esel`` lane names.

    ``hashes`` takes a precomputed ``hash64(ops.keys)`` pair so a caller
    that already hashed for the L1 set index doesn't pay the murmur chain
    twice; ``placement`` likewise takes a precomputed ``(dest, epoch)``
    so the ring-owner searchsorted is not repeated.  ``bin_valid`` masks
    items out of the binning entirely (self-elided or otherwise locally
    served traffic): they take no bin slot and do not inflate the
    count-driven capacity.  Returns ``(binned, base, dest,
    used_prologue)``."""
    cfg = state.cfg
    h_hi, h_lo = hash64(ops.keys) if hashes is None else hashes
    dest, epoch = (_owner_epoch(state, h_hi) if placement is None
                   else placement)
    base = base_bucket(h_lo, cfg.buckets_per_shard, cfg.n_probe)
    if prev is not None:
        dest_prev, _ = _owner_epoch(prev, h_hi)
        base_prev = base_bucket(
            h_lo, prev.cfg.buckets_per_shard, prev.cfg.n_probe)
        in_prev = ops.esel == 1
        dest = jnp.where(in_prev, dest_prev, dest)
        base = jnp.where(in_prev, base_prev, base)
    n = ops.keys.shape[0]
    cap = capacity or cfg.capacity
    used_prologue = False
    if not cap:
        if isinstance(dest, jax.core.Tracer):
            # traced: buffer shapes must be fixed before the trace, so the
            # static expected-load heuristic stands in
            cap = routing.auto_capacity(n, cfg.n_shards)
        else:
            # eager: count-exchange prologue — tight pow-2-bucketed
            # capacity from the actual max bin load (zero drops).  Items
            # the round will not route (bin_valid False) are excluded.
            vv = bin_valid
            if vv is not None and isinstance(vv, jax.core.Tracer):
                vv = None
            cap = routing.plan_capacity(dest, cfg.n_shards, valid=vv)
            used_prologue = True
    binned = routing.bin_by_dest(dest, cfg.n_shards, cap, epoch=epoch,
                                 valid=bin_valid)
    return binned, base, dest, used_prologue


def _slab_of(state: DHTState):
    return {"keys": state.keys, "vals": state.vals,
            "meta": state.meta, "csum": state.csum}


def _state_from(state: DHTState, slab) -> DHTState:
    return DHTState(state.cfg, slab["keys"], slab["vals"], slab["meta"],
                    slab["csum"], state.ring)


def _pad_rows(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    if x.shape[0] == rows:
        return x
    pad = [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad)


@dataclasses.dataclass
class InFlightRound:
    """An issued-but-uncommitted engine round (DESIGN.md §12).

    A host-side handle, NOT a pytree: it holds the round's (future)
    result arrays plus the bookkeeping :func:`dht_commit` needs to wait,
    forward, and record.  ``state`` is the round's output table — safe to
    issue the next round against immediately (dataflow chains through
    it), which is exactly how the pipelined drivers overlap rounds.

    ``conflict``/``pending`` carry the pending-write hazard bookkeeping:
    rows masked out of the probe at issue time because a promised-but-
    not-yet-issued write to the same key would make the table stale for
    them; commit resolves them from the pending table's published values
    (store-to-load forwarding).  ``meta`` is free-form wrapper state
    (e.g. the ShardedDHT commit closure and its L1 bookkeeping).
    """

    state: DHTState
    prev: DHTState | None
    vals: jnp.ndarray
    found: jnp.ndarray
    code: jnp.ndarray
    estats: dict[str, Any]
    kinds: tuple[str, ...]
    source: str
    mix: dict[str, int] | None
    rec: bool
    t_start: float
    t_issued: float
    pending: Any = None
    conflict: Any = None          # np bool (n,) — forwarded rows
    keys_np: Any = None           # np uint32 (n, KW) — forward lookup keys
    committed: bool = False
    meta: dict = dataclasses.field(default_factory=dict)


def dht_issue(
    state: DHTState,
    ops: OpBatch,
    *,
    kinds: Sequence[str] = KINDS,
    prev: DHTState | None = None,
    axis_name: Any = None,
    capacity: int | None = None,
    hashes: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    placement: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    l1_meta: bool = False,
    elide_self: bool | None = None,
    source: str | None = None,
    pending: Any = None,
) -> InFlightRound:
    """Issue an op-tagged request batch as ONE collective round and
    return without waiting: the issue half of the engine.

    ``kinds`` is the static set of op kinds the batch may contain — it
    prunes the dispatched lanes and the shard-side machinery, so a
    uniform read batch costs exactly what the dedicated read round used
    to.  ``prev`` enables dual-epoch probing (``ops.esel`` required);
    ``capacity`` overrides the routing capacity for this call.

    Locality tier (DESIGN.md §9):

    - ``hashes`` / ``placement`` — precomputed ``hash64(ops.keys)`` and
      ``(dest, epoch)``, so the L1 front end and the router share one
      hash chain and one ring-owner lookup (``placement`` requires
      ``prev is None`` — dual-epoch routing derives its own mix).
    - ``l1_meta`` — piggyback the coherence metadata on the reply lanes:
      ``estats`` gains ``bucket_gen`` (per item, the serving bucket's
      snapshot generation), ``wmark_pre``/``wmark_post`` ((n_shards,)
      per-shard meta watermarks before/after this round's mutations).
      Costs 3 reply lanes, zero extra rounds.
    - ``elide_self`` — on the sharded backend, requests owned by the
      local shard skip the ``all_to_all`` entirely: they are masked out
      of the binning (taking no bin slot) and probed against the local
      slab as extra rows of the same ``_shard_apply`` call, so the merged
      result is bit-for-bit the cacheless one.  Default (``None``): on
      for uniform read rounds under shard_map, off otherwise (write
      rounds keep full routing so the cross-device last-writer-wins
      priority — buffer row order — is unchanged).

    Pipelining extras over the classic ``dht_execute`` keywords:

    - ``source`` — the trace-event name flushed at commit (defaults to
      ``"engine.<kinds>"``, matching the synchronous path).
    - ``pending`` — a ``core.pipeline.PendingWrites`` table.  Read rows
      whose key has a *promised-but-not-yet-issued* write are masked out
      of the probe (no bin slot, no wire) and resolved at commit time by
      store-to-load forwarding from the table's published values.  Reads
      issued *after* a write round was issued need no filter: dataflow
      through the chained ``state`` already orders them.  Eager uniform
      read rounds only.

    Returns an :class:`InFlightRound`; pass it to :func:`dht_commit` for
    the classic ``(state', prev', vals, found, code, estats)`` tuple.
    Commit order across rounds must be issue order (FIFO) whenever a
    ``pending`` filter is in play.
    """
    cfg = state.cfg
    kinds = tuple(kinds)
    assert kinds and all(k in KINDS for k in kinds), kinds
    # deterministic fault injection (core/faults.py): an installed plan
    # may drop rows (they come back W_DROPPED / not-found, exactly like a
    # routing overflow — the callers' retry paths can't tell the
    # difference, which is the point) or delay the issue.  Host-side and
    # eager-only: traced closures never see it.
    fplan = _faults.get_plan()
    if (fplan is not None
            and not isinstance(ops.keys, jax.core.Tracer)
            and not isinstance(state.keys, jax.core.Tracer)):
        ops = fplan.perturb(ops, kinds)
    conflict = keys_np = None
    if pending is not None:
        assert kinds == ("read",) and prev is None and ops.op is None, (
            "pending-write filtering applies to uniform read rounds")
        assert not isinstance(ops.keys, jax.core.Tracer), (
            "pending-write filtering is a host-side (eager) mechanism")
        import numpy as np

        cmask = pending.conflicts(np.asarray(ops.keys),
                                  np.asarray(ops.valid))
        if cmask.any():
            conflict, keys_np = cmask, np.asarray(ops.keys)
            ops = OpBatch(keys=ops.keys,
                          valid=ops.valid & jnp.asarray(~cmask))
    do_write = ("write" in kinds) or ("migrate" in kinds)
    if do_write:
        assert ops.vals is not None, "write/migrate batches need a value lane"
    if prev is not None:
        assert ops.esel is not None, "dual-epoch execute needs ops.esel"
        assert kinds == ("read",), (
            "dual-epoch execute is read-only: an esel==1 write row would be "
            "routed by old-epoch placement but applied to the new-epoch "
            "slab — unreachable afterwards.  Writes go through a separate "
            "single-epoch round (they always target the new epoch).")
        assert dual_fusable(cfg, prev.cfg), (
            "single-round dual-epoch probe needs compatible geometry; "
            "use the sequential dht_read_dual fallback")

    assert placement is None or prev is None, (
        "precomputed placement is single-epoch only")
    # Telemetry (DESIGN.md §10): the engine self-records only on the
    # eager host path — under jit/shard_map the stat lanes ride the
    # estats return value and the *caller's* host code flushes them
    # (e.g. the ShardedDHT wrappers), so nothing here runs at trace time.
    rec = (obs_metrics.enabled() and axis_name is None
           and not isinstance(ops.keys, jax.core.Tracer)
           and not isinstance(state.keys, jax.core.Tracer))
    t_start = time.perf_counter() if rec else 0.0
    # Device-side phases (DESIGN.md §10): every op of a phase carries its
    # name (obs.trace.PHASES) in its op_name metadata, on the eager and
    # the shard_map path alike, so a profiler trace sums device time per
    # phase.  Scopes change metadata only, never fusion.
    with jax.named_scope("bin"):
        # replica-select lane (DESIGN.md §13): under k-successor
        # replication the round's placement is the crash-tolerant
        # first-live-replica select, and the count of items NOT served by
        # their owner rides the stats as ``fallback_reads``.  Callers that
        # precompute ``placement`` (the L1 front end, the replicated write
        # fan-out, repair) account for their own routing.
        n_fallback = jnp.int32(0)
        if (cfg.n_replicas > 1 and state.ring is not None
                and placement is None and prev is None):
            hashes = hash64(ops.keys) if hashes is None else hashes
            dest_r, epoch_r, fb = replica_placement(state, hashes[0])
            placement = (dest_r, epoch_r)
            n_fallback = jnp.sum(ops.valid & fb).astype(jnp.int32)
        elidable = (axis_name is not None and kinds == ("read",)
                    and prev is None and ops.op is None)
        elide = elidable if elide_self is None else bool(elide_self)
        assert not elide or elidable, (
            "self-traffic elision needs a sharded uniform read round")
        if elide:
            hashes = hash64(ops.keys) if hashes is None else hashes
            if placement is None:
                placement = _owner_epoch(state, hashes[0])
            my = _flat_axis_index(axis_name)
            is_self = ops.valid & (placement[0] == my)
            bin_valid = ops.valid & ~is_self
        else:
            is_self = None
            bin_valid = ops.valid

        binned, base, _dest, used_prologue = _route_ops(
            state, prev, ops, capacity, hashes, bin_valid, placement)
        payload_valid = (ops.valid & binned.kept).astype(jnp.int32)
        payloads = [base, ops.keys]
        if do_write:
            payloads.append(ops.vals.astype(jnp.uint32))
        if ops.op is not None:
            payloads.append(ops.op.astype(jnp.int32))
        if prev is not None:
            payloads.append(ops.esel.astype(jnp.int32))
        payloads.append(payload_valid)
    with jax.named_scope("dispatch"):
        inc = routing.dispatch(binned, payloads, axis_name)

    def _unpack(parts):
        it = iter(parts)
        b, k = next(it), next(it)
        v = next(it) if do_write else None
        o = next(it) if ops.op is not None else None
        e = next(it) if prev is not None else None
        m = next(it)
        return b, k, v, o, e, m

    def _replies(val, found, code, gen, wpre, wpost):
        out = [val, found.astype(jnp.int32), code]
        if l1_meta:
            shape = gen.shape  # (S, cap) local / (rows,) sharded
            out += [gen.astype(jnp.uint32),
                    jnp.broadcast_to(
                        wpre.reshape(wpre.shape + (1,) * (gen.ndim - wpre.ndim)),
                        shape).astype(jnp.uint32),
                    jnp.broadcast_to(
                        wpost.reshape(wpost.shape + (1,) * (gen.ndim - wpost.ndim)),
                        shape).astype(jnp.uint32)]
        return out

    prev_cfg = None if prev is None else prev.cfg
    with jax.named_scope("apply"):
        if axis_name is None:
            slab = _slab_of(state)
            if prev is not None:
                rows = slab["meta"].shape[0]
                pslab = {k: _pad_rows(v, rows)
                         for k, v in _slab_of(prev).items()}

                def handler(sl, psl, *parts):
                    b, k, v, o, e, m = _unpack(parts)
                    return _shard_apply(cfg, prev_cfg, sl, psl, b, k, v, o,
                                        e, m.astype(bool), None, kinds)

                out = jax.vmap(handler)(slab, pslab, *inc)
            else:

                def handler(sl, *parts):
                    b, k, v, o, e, m = _unpack(parts)
                    return _shard_apply(cfg, None, sl, None, b, k, v, o, e,
                                        m.astype(bool), None, kinds)

                out = jax.vmap(handler)(slab, *inc)
            (slab, pslab, val, found, code, n_mm, rounds, tok,
             gen, wpre, wpost) = out
            n_mm, tok = jnp.sum(n_mm), jnp.sum(tok)
            rounds = jnp.max(rounds)
        else:
            slab = jax.tree.map(lambda x: x[0], _slab_of(state))
            pslab = (None if prev is None
                     else jax.tree.map(lambda x: x[0], _slab_of(prev)))
            b, k, v, o, e, m = _unpack(inc)
            if elide:
                # self-owned requests ride the SAME _shard_apply call as
                # extra rows after the incoming buffer — one pass,
                # identical probe semantics, no collective
                b = jnp.concatenate([b, base])
                k = jnp.concatenate([k, ops.keys])
                m = jnp.concatenate([m, is_self.astype(jnp.int32)])
            (slab, pslab, val, found, code, n_mm, rounds, tok,
             gen, wpre, wpost) = _shard_apply(
                cfg, prev_cfg, slab, pslab, b, k, v, o, e,
                m.astype(bool), axis_name, kinds)
            buf_rows = binned.n_dest * binned.capacity
            if elide:
                val, val_l = val[:buf_rows], val[buf_rows:]
                found, found_l = found[:buf_rows], found[buf_rows:]
                code, code_l = code[:buf_rows], code[buf_rows:]
                gen, gen_l = gen[:buf_rows], gen[buf_rows:]
            slab = jax.tree.map(lambda x: x[None], slab)
            if pslab is not None:
                pslab = jax.tree.map(lambda x: x[None], pslab)

    with jax.named_scope("collect"):
        coll = routing.collect(
            binned, _replies(val, found, code, gen, wpre, wpost), axis_name,
            block_rows=l1_meta)
        items, blocks = coll if l1_meta else (coll, None)
        val_b, found_b, code_b = items[0], items[1], items[2]
        found_out = (found_b > 0) & ops.valid & binned.kept
        code_out = jnp.where(ops.valid & binned.kept, code_b, W_DROPPED)
        gen_out = items[3] if l1_meta else None
        if elide:
            found_out = jnp.where(is_self, found_l, found_out)
            val_b = jnp.where(is_self[:, None], val_l, val_b)
            code_out = jnp.where(is_self, code_l, code_out)
            if l1_meta:
                gen_out = jnp.where(is_self, gen_l, gen_out)
        val_out = jnp.where(found_out[:, None], val_b, jnp.uint32(0))
    # wire accounting: both legs' buffer words + the padding fraction
    # (reply leg lanes: value words + found + code [+ 3 coherence lanes]),
    # plus the count-exchange prologue's histogram words (S counters each
    # way) when this round was sized by it; the elided self block (pure
    # padding, never crosses the fabric) is dropped from both legs
    wire = routing.wire_stats(
        binned, routing.lane_width(payloads),
        cfg.val_words + 2 + (3 if l1_meta else 0),
        prologue_words=2 * cfg.n_shards if used_prologue else 0,
        n_self_rows=binned.capacity if elide else 0)
    # per-round skew lanes (DESIGN.md §11): the per-destination histogram
    # of what this round puts on the wire, reduced to scalar diagnostics
    # that ride the trace — imbalance = max/mean bin load, hot_frac = the
    # hottest shard's share of the routed traffic.  The full (S,) counts
    # vector is returned too for host-side consumers (obs/skew.py); it is
    # skipped by the scalar trace flush.
    bcounts = routing.bin_counts(binned)
    btotal = jnp.maximum(jnp.sum(bcounts), 1).astype(jnp.float32)
    bmax = jnp.max(bcounts).astype(jnp.float32)
    estats = {
        "mismatches": n_mm.astype(jnp.int32),
        "rounds": rounds.astype(jnp.int32),
        "lock_tokens": tok.astype(jnp.int32),
        "dropped": binned.n_dropped,
        "epoch": binned.epoch,
        "wire_words": wire["wire_words"],
        "wire_send_words": wire["wire_send_words"],
        "wire_reply_words": wire["wire_reply_words"],
        "fill_frac": wire["fill_frac"],
        # one dispatch/collect cycle per execute — the host-side flush
        # advances engine.rounds by this lane (pmax'd across shards)
        "dispatch_rounds": jnp.int32(1),
        # static round geometry, stamped so trace events are self-
        # describing (the cost model fits on these, obs/costmodel.py)
        "n_shards": jnp.int32(cfg.n_shards),
        "capacity": jnp.int32(binned.capacity),
        "bin_counts": bcounts,
        "bin_max_load": jnp.max(bcounts).astype(jnp.int32),
        "bin_imbalance": (bmax * jnp.float32(cfg.n_shards)
                          / btotal).astype(jnp.float32),
        "hot_frac": (bmax / btotal).astype(jnp.float32),
        # replication lane (DESIGN.md §13): items this round routed to a
        # successor because their owner's liveness bit was down (always 0
        # at k=1 — the lane exists so stats_specs stay shape-stable)
        "fallback_reads": n_fallback,
    }
    if l1_meta:
        estats["bucket_gen"] = gen_out.astype(jnp.uint32)
        estats["wmark_pre"] = blocks[4].astype(jnp.uint32)
        estats["wmark_post"] = blocks[5].astype(jnp.uint32)
    state_out = _state_from(state, slab)
    if prev is None:
        prev_out = None
    else:
        # drop the row padding added for the paired vmap (no-op when the
        # epochs already share a shard count, and on the sharded backend)
        prows = prev.meta.shape[0]
        prev_out = _state_from(
            prev, {k2: v2[:prows] for k2, v2 in pslab.items()})
    mix = None
    t_issued = 0.0
    if rec:
        if ops.op is None:
            mix = {kinds[0]: int(jnp.sum(ops.valid))}
        else:
            mix = {name: int(jnp.sum(ops.valid & (ops.op == tag)))
                   for name, tag in (("read", OP_READ), ("write", OP_WRITE),
                                     ("migrate", OP_MIGRATE))
                   if name in kinds}
        if conflict is not None:
            # forwarded rows were masked out of the probe but are still
            # this round's logical traffic
            mix["read"] = mix.get("read", 0) + int(conflict.sum())
        t_issued = time.perf_counter()
    return InFlightRound(
        state=state_out, prev=prev_out, vals=val_out, found=found_out,
        code=code_out, estats=estats, kinds=kinds,
        source=source or ("engine." + "+".join(kinds)), mix=mix, rec=rec,
        t_start=t_start, t_issued=t_issued,
        pending=pending, conflict=conflict, keys_np=keys_np)


def dht_commit(
    rnd: InFlightRound,
) -> tuple[DHTState, DHTState | None, jnp.ndarray, jnp.ndarray,
           jnp.ndarray, dict[str, jnp.ndarray]]:
    """Wait for an issued round's replies: the commit half.

    Resolves pending-write forwards (conflicted rows get the promised
    value, ``found=True`` — bit-for-bit what a synchronous read after
    the write round would have returned), blocks until the reply arrays
    are device-complete (eager only — under a trace this is a no-op and
    the pair degenerates to the classic fused round), and flushes the
    round's telemetry with two extra ingredients over the synchronous
    path: ``issue``/``hidden``/``commit`` marks, and ``issue_us`` /
    ``hidden_us`` / ``commit_wait_us`` / ``overlap_frac`` stat lanes.
    ``hidden_us`` is the host time spent *elsewhere* between issue
    returning and commit being called — latency the caller successfully
    overlapped; ``overlap_frac`` is its share of the round's total
    duration.

    Returns the classic engine tuple
    ``(state', prev', vals, found, code, estats)``.
    """
    assert not rnd.committed, "InFlightRound committed twice"
    rnd.committed = True
    vals, found, code = rnd.vals, rnd.found, rnd.code
    n_fwd = 0
    if rnd.conflict is not None:
        fvals = rnd.pending.resolve(rnd.keys_np, rnd.conflict)
        cm = jnp.asarray(rnd.conflict)
        vals = jnp.where(cm[:, None], jnp.asarray(fvals), vals)
        found = found | cm
        n_fwd = int(rnd.conflict.sum())
    t_commit = time.perf_counter() if rnd.rec else 0.0
    if not isinstance(vals, jax.core.Tracer):
        jax.block_until_ready((vals, found, code))
    if rnd.rec:
        now = time.perf_counter()
        dur = max(now - rnd.t_start, 0.0)
        hidden = max(t_commit - rnd.t_issued, 0.0)
        stats = dict(rnd.estats)
        stats["issue_us"] = (rnd.t_issued - rnd.t_start) * 1e6
        stats["hidden_us"] = hidden * 1e6
        stats["commit_wait_us"] = max(now - t_commit, 0.0) * 1e6
        stats["overlap_frac"] = min(hidden / dur, 1.0) if dur > 0 else 0.0
        if n_fwd:
            stats["forwarded"] = n_fwd
        obs_trace.record_round(
            rnd.source, stats, ops=rnd.mix, t_start=rnd.t_start,
            phase_marks=[("issue", rnd.t_start), ("hidden", rnd.t_issued),
                         ("commit", t_commit)])
    return rnd.state, rnd.prev, vals, found, code, rnd.estats


def dht_execute(
    state: DHTState,
    ops: OpBatch,
    *,
    kinds: Sequence[str] = KINDS,
    prev: DHTState | None = None,
    axis_name: Any = None,
    capacity: int | None = None,
    hashes: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    placement: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    l1_meta: bool = False,
    elide_self: bool | None = None,
) -> tuple[DHTState, DHTState | None, jnp.ndarray, jnp.ndarray,
           jnp.ndarray, dict[str, jnp.ndarray]]:
    """Execute an op-tagged request batch in ONE collective round,
    synchronously: ``dht_commit(dht_issue(...))``.  See
    :func:`dht_issue` for the keyword semantics and :func:`dht_commit`
    for the return tuple."""
    return dht_commit(dht_issue(
        state, ops, kinds=kinds, prev=prev, axis_name=axis_name,
        capacity=capacity, hashes=hashes, placement=placement,
        l1_meta=l1_meta, elide_self=elide_self))


__all__ = [
    "KINDS",
    "InFlightRound",
    "OP_MIGRATE",
    "OP_READ",
    "OP_WRITE",
    "OpBatch",
    "W_DROPPED",
    "W_EVICT",
    "W_INSERT",
    "W_SKIP",
    "W_UPDATE",
    "dht_commit",
    "dht_execute",
    "dht_issue",
    "dual_fusable",
    "migrate_ops",
    "mixed_ops",
    "read_ops",
    "replica_placement",
    "write_ops",
]
