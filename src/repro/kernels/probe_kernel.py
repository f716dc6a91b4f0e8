"""Pallas TPU kernel: DHT bucket probe (the DHT_read hot path).

The TPU adaptation of the paper's multi-candidate probe (DESIGN.md §2):
candidates form a *contiguous window* of ``n_probe`` buckets, so each
query needs exactly one dynamically addressed block fetch instead of six
scattered remote reads.  Dynamic addressing uses scalar prefetch
(``PrefetchScalarGridSpec``): the per-query window base indices are
prefetched to SMEM and drive the BlockSpec index maps, which is the
TPU-idiomatic way to pipeline data-dependent gathers (the DMA for query
i+1's window overlaps the compare/checksum compute of query i).

Grid is (C, P): query-major, candidate-minor.  The output block for query
i stays resident across the inner j loop, accumulating first-match-wins
state — the standard Pallas revisiting-output pattern.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import murmur32_words
from repro.core.layout import INVALID, OCCUPIED

_SEED = 0xB5297A4D  # checksum seed — must match core.hashing.checksum32


def _probe_kernel(base_ref,  # scalar prefetch: (C,) int32 window bases
                  qkeys_ref,   # (1, KW) current query key
                  bkeys_ref,   # (1, KW) candidate bucket key
                  bvals_ref,   # (1, VW) candidate bucket value
                  bmeta_ref,   # (1, 1) candidate meta word
                  bcsum_ref,   # (1, 1) candidate checksum
                  val_out,     # (1, VW) result value
                  found_out,   # (1, 1) result flag
                  *, validate_checksum: bool):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        val_out[...] = jnp.zeros_like(val_out)
        found_out[...] = jnp.zeros_like(found_out)

    q = qkeys_ref[...]
    bk = bkeys_ref[...]
    meta = bmeta_ref[0, 0]
    occupied = (meta & OCCUPIED) != 0
    invalid = (meta & INVALID) != 0
    keys_eq = jnp.all(bk == q)
    already = found_out[0, 0] > 0
    hit = occupied & jnp.logical_not(invalid) & keys_eq & jnp.logical_not(already)

    bv = bvals_ref[...]
    if validate_checksum:
        csum = murmur32_words(jnp.concatenate([q, bv], axis=-1), _SEED)[0]
        hit = hit & (csum == bcsum_ref[0, 0])

    @pl.when(hit)
    def _store():
        val_out[...] = bv
        found_out[0, 0] = jnp.int32(1)


@functools.partial(
    jax.jit, static_argnames=("n_probe", "validate_checksum", "interpret")
)
def probe_pallas(
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
    """Returns (vals (C, VW) uint32, found (C,) bool)."""
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
        ],
    )
    kernel = functools.partial(_probe_kernel, validate_checksum=validate_checksum)
    val, found = pl.pallas_call(
        kernel,
        name="probe_pallas",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((c, vw), jnp.uint32),
            jax.ShapeDtypeStruct((c, 1), jnp.int32),
        ],
        interpret=interpret,
    )(base, qkeys, slab_keys, slab_vals, meta2, csum2)
    return val, found[:, 0] > 0
