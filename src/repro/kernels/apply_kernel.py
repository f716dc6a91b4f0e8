"""Pallas TPU kernel: fused DHT shard-apply (the op-engine hot path).

One tile pass per (query, candidate) does everything the mixed-op shard
handler (``core/op_engine._shard_apply``) needs from the probe window:

  probe-window gather -> keymatch -> checksum-validate -> slot-select

i.e. both the read result (first occupied, non-INVALID, key-equal,
checksum-valid candidate) and the write-slot decision of the paper's
§3.1 probe policy (same key -> update; else first writable — empty or
INVALID; else overwrite the last candidate) in a single pass over the
window.  The engine's ``OP_MIGRATE`` get-or-put needs exactly this pair:
presence + where-to-insert.

Same TPU idiom as ``probe_kernel``: the per-query window base indices
are scalar-prefetched to SMEM and drive the BlockSpec index maps
(``PrefetchScalarGridSpec``), so the DMA for query i+1's window overlaps
query i's compare/checksum compute; grid is (C, P) query-major with the
output blocks resident across the inner candidate loop, accumulating
first-match-wins state (the standard revisiting-output pattern).
Validated bit-for-bit against ``kernels/ref.ref_shard_apply``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import murmur32_words
from repro.core.layout import INVALID, OCCUPIED
from repro.core.op_engine import W_EVICT, W_INSERT, W_UPDATE

_SEED = 0xB5297A4D  # checksum seed — must match core.hashing.checksum32


def _apply_kernel(base_ref,   # scalar prefetch: (C,) int32 window bases
                  qkeys_ref,  # (1, KW) current query key
                  bkeys_ref,  # (1, KW) candidate bucket key
                  bvals_ref,  # (1, VW) candidate bucket value
                  bmeta_ref,  # (1, 1) candidate meta word
                  bcsum_ref,  # (1, 1) candidate checksum
                  val_out,    # (1, VW) read result value
                  found_out,  # (1, 1) read result flag
                  wsel_out,   # (1, 1) write slot (relative); loop: 1+first match
                  wkind_out,  # (1, 1) write code; loop: 1+first writable
                  *, n_probe: int, validate_checksum: bool):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        val_out[...] = jnp.zeros_like(val_out)
        found_out[...] = jnp.zeros_like(found_out)
        wsel_out[...] = jnp.zeros_like(wsel_out)
        wkind_out[...] = jnp.zeros_like(wkind_out)

    q = qkeys_ref[...]
    bk = bkeys_ref[...]
    meta = bmeta_ref[0, 0]
    occupied = (meta & OCCUPIED) != 0
    invalid = (meta & INVALID) != 0
    keys_eq = jnp.all(bk == q)

    # -- read lane: the FIRST occupied, valid, key-equal candidate is the
    #    selected bucket (exactly core/op_engine._probe_window); only that
    #    candidate is checksum-validated — a failed checksum must not fall
    #    through to a later candidate.  found_out is tri-state while the
    #    loop runs: 0 = no match yet, 1 = found, -1 = selected but invalid.
    fresh = (occupied & jnp.logical_not(invalid) & keys_eq
             & (found_out[0, 0] == 0))
    bv = bvals_ref[...]
    if validate_checksum:
        csum = murmur32_words(jnp.concatenate([q, bv], axis=-1), _SEED)[0]
        ok = csum == bcsum_ref[0, 0]
    else:
        ok = jnp.bool_(True)

    @pl.when(fresh & ok)
    def _store():
        val_out[...] = bv
        found_out[0, 0] = jnp.int32(1)

    @pl.when(fresh & jnp.logical_not(ok))
    def _reject():
        found_out[0, 0] = jnp.int32(-1)

    # -- write lane: paper §3.1 slot policy (INVALID does not veto a match,
    #    it makes the bucket writable) — accumulate 1+first occurrence
    wmatch = occupied & keys_eq
    writable = jnp.logical_not(occupied) | invalid

    @pl.when(wmatch & (wsel_out[0, 0] == 0))
    def _first_match():
        wsel_out[0, 0] = j + 1

    @pl.when(writable & (wkind_out[0, 0] == 0))
    def _first_writable():
        wkind_out[0, 0] = j + 1

    # -- finalize on the last candidate: turn the accumulators into the
    #    (slot, code) decision of core/op_engine._choose_write_slot
    @pl.when(j == n_probe - 1)
    def _finalize():
        mm = wsel_out[0, 0]
        me = wkind_out[0, 0]
        sel = jnp.where(
            mm > 0, mm - 1,
            jnp.where(me > 0, me - 1, jnp.int32(n_probe - 1)),
        )
        kind = jnp.where(
            mm > 0, jnp.int32(W_UPDATE),
            jnp.where(me > 0, jnp.int32(W_INSERT), jnp.int32(W_EVICT)),
        )
        wsel_out[0, 0] = sel
        wkind_out[0, 0] = kind


@functools.partial(
    jax.jit, static_argnames=("n_probe", "validate_checksum", "interpret")
)
def shard_apply_pallas(
    slab_keys: jnp.ndarray,   # (B, KW) uint32
    slab_vals: jnp.ndarray,   # (B, VW) uint32
    slab_meta: jnp.ndarray,   # (B,) uint32
    slab_csum: jnp.ndarray,   # (B,) uint32
    qkeys: jnp.ndarray,       # (C, KW) uint32
    base: jnp.ndarray,        # (C,) int32, window start per query
    *,
    n_probe: int = 6,
    validate_checksum: bool = True,
    interpret: bool = True,
):
    """Returns ``(vals (C, VW) uint32, found (C,) bool, wsel (C,) int32,
    wkind (C,) int32)`` — the read result plus the write-slot decision
    (relative candidate index and W_UPDATE/W_INSERT/W_EVICT code)."""
    c, kw = qkeys.shape
    b, vw = slab_vals.shape
    meta2 = slab_meta.reshape(b, 1)
    csum2 = slab_csum.reshape(b, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(c, n_probe),
        in_specs=[
            pl.BlockSpec((1, kw), lambda i, j, base_ref: (i, 0)),
            pl.BlockSpec((1, kw), lambda i, j, base_ref: (base_ref[i] + j, 0)),
            pl.BlockSpec((1, vw), lambda i, j, base_ref: (base_ref[i] + j, 0)),
            pl.BlockSpec((1, 1), lambda i, j, base_ref: (base_ref[i] + j, 0)),
            pl.BlockSpec((1, 1), lambda i, j, base_ref: (base_ref[i] + j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, vw), lambda i, j, base_ref: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j, base_ref: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j, base_ref: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j, base_ref: (i, 0)),
        ],
    )
    kernel = functools.partial(
        _apply_kernel, n_probe=n_probe, validate_checksum=validate_checksum)
    val, found, wsel, wkind = pl.pallas_call(
        kernel,
        name="shard_apply_pallas",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((c, vw), jnp.uint32),
            jax.ShapeDtypeStruct((c, 1), jnp.int32),
            jax.ShapeDtypeStruct((c, 1), jnp.int32),
            jax.ShapeDtypeStruct((c, 1), jnp.int32),
        ],
        interpret=interpret,
    )(base, qkeys, slab_keys, slab_vals, meta2, csum2)
    return val, found[:, 0] > 0, wsel[:, 0], wkind[:, 0]
