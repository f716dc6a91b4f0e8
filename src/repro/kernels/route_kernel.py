"""Pallas TPU kernels: fused routing pack/unpack (the dispatch hot path).

``core/routing.dispatch``/``collect`` bit-pack every payload of a round
into one (n, L) uint32 lane matrix; these kernels move that matrix
between item order and bin order in ONE tile pass over all lanes —
replacing the per-payload ``buf.at[slot].set`` / fancy-gather loops:

- :func:`route_pack_pallas`   — scatter-to-bins: (n, L) items -> (rows, L)
  send buffer, where ``rows = n_dest * capacity``.  Driven by the tiny
  inverse permutation ``inv`` (bin row -> item index, -1 = fill) that the
  router derives from the sort-based binning, so the kernel itself is a
  pure gather: row i's DMA source is item ``inv[i]`` or the fill row.
- :func:`route_unpack_pallas` — gather-from-bins: (rows, L) reply buffer
  -> (n, L) in original item order via the per-item ``slot``; items that
  overflowed capacity (``kept == 0``) receive the fill row.

Same TPU idiom as ``apply_kernel``: the per-row indirection arrays are
scalar-prefetched to SMEM and drive the BlockSpec index maps
(``PrefetchScalarGridSpec``), so the DMA for row i+1 overlaps row i's
select/store; one grid step touches one (1, L) lane row.  Mosaic tiles
the last two dims of a block, and a (1, L) block of an (n, L) array is
neither (8, 128)-aligned nor the full array, so the matrices ride a
squeezed leading row axis: array (n, 1, L), block (None, 1, L).  Validated
bit-for-bit against ``kernels/ref.ref_route_pack``/``ref_route_unpack``
(pinned to the production jnp path in ``core/routing.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pack_kernel(inv_ref,    # scalar prefetch: (rows,) int32 item index or -1
                 mat_ref,    # (1, L) source item lane row (clamped index)
                 fill_ref,   # (1, L) fill lane row
                 out_ref):   # (1, L) send-buffer row
    i = pl.program_id(0)
    live = inv_ref[i] >= 0

    @pl.when(live)
    def _copy():
        out_ref[...] = mat_ref[...]

    @pl.when(jnp.logical_not(live))
    def _fill():
        out_ref[...] = fill_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def route_pack_pallas(
    mat: jnp.ndarray,       # (n, L) uint32 item lane matrix
    inv: jnp.ndarray,       # (rows,) int32 bin-row -> item index, -1 = fill
    fill_row: jnp.ndarray,  # (L,) uint32 per-lane fill words
    *,
    interpret: bool = True,
) -> jnp.ndarray:
    """Returns the (rows, L) uint32 send buffer in bin order."""
    n, width = mat.shape
    rows = inv.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows,),
        in_specs=[
            pl.BlockSpec((None, 1, width),
                         lambda i, inv_ref: (jnp.maximum(inv_ref[i], 0), 0, 0)),
            pl.BlockSpec((1, width), lambda i, inv_ref: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, width),
                               lambda i, inv_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _pack_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, 1, width), jnp.uint32),
        interpret=interpret,
    )(inv, mat.reshape(n, 1, width), fill_row.reshape(1, width))
    return out.reshape(rows, width)


def _unpack_kernel(slot_ref,  # scalar prefetch: (n,) int32 bin row per item
                   kept_ref,  # scalar prefetch: (n,) int32 0 = overflowed
                   buf_ref,   # (1, L) reply-buffer row at slot[i]
                   fill_ref,  # (1, L) fill lane row
                   out_ref):  # (1, L) per-item reply row
    i = pl.program_id(0)
    live = kept_ref[i] != 0

    @pl.when(live)
    def _copy():
        out_ref[...] = buf_ref[...]

    @pl.when(jnp.logical_not(live))
    def _fill():
        out_ref[...] = fill_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def route_unpack_pallas(
    buf: jnp.ndarray,       # (rows, L) uint32 reply buffer in bin order
    slot: jnp.ndarray,      # (n,) int32 bin row per item (pre-clamped)
    kept: jnp.ndarray,      # (n,) int32 validity (0 = fill)
    fill_row: jnp.ndarray,  # (L,) uint32 per-lane fill words
    *,
    interpret: bool = True,
) -> jnp.ndarray:
    """Returns the (n, L) uint32 reply matrix in original item order."""
    rows, width = buf.shape
    n = slot.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((None, 1, width),
                         lambda i, slot_ref, kept_ref: (slot_ref[i], 0, 0)),
            pl.BlockSpec((1, width), lambda i, slot_ref, kept_ref: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, width),
                               lambda i, slot_ref, kept_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _unpack_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, width), jnp.uint32),
        interpret=interpret,
    )(slot, kept, buf.reshape(rows, 1, width), fill_row.reshape(1, width))
    return out.reshape(n, width)
