"""Pallas TPU kernel: fused L1 hot-key probe (the locality-tier front end).

The pre-routing filter of ``core/l1cache.l1_probe`` (DESIGN.md §9): for
each query, compare the key against the ways of its L1 set and select the
value of the first coherent match.  The coherence decision itself (live ∧
epoch ∧ watermark, ``l1cache.serve_flags``) is a tiny whole-cache vector
op computed once per batch *outside* the kernel; the kernel fuses the
expensive per-item part — the multi-word key compare across ways and the
value select — into one tile pass so the filter stays off the hot path's
critical time.

Same TPU idiom as ``probe_kernel``: the per-query set indices are
scalar-prefetched to SMEM and drive the BlockSpec index maps
(``PrefetchScalarGridSpec``), the grid is (query, way) with the output
block revisited across the inner way loop accumulating first-match-wins
state.  Rows ride a squeezed leading axis (array (n, 1, W), block
(None, 1, W)) so Mosaic accepts the one-row blocks, and each line's
coherence flag travels as one extra value lane, so the kernel moves no
(1, 1) blocks.  Validated bit-for-bit against ``kernels/ref.ref_l1_probe``,
which is pinned to the production jnp path in ``core/l1cache.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _l1_kernel(set_ref,    # scalar prefetch: (n,) int32 set index per query
               qkeys_ref,  # (1, KW) current query key
               lkeys_ref,  # (1, KW) candidate line key
               lvals_ref,  # (1, VW + 1) candidate line value ‖ coherence flag
               out_ref):   # (1, VW + 1) result value ‖ hit flag
    j = pl.program_id(1)
    vw = out_ref.shape[-1] - 1

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # all-vector first-match-wins: no scalar is read back from VMEM
    line = lvals_ref[...]
    prev = out_ref[...]
    keys_eq = jnp.min((lkeys_ref[...] == qkeys_ref[...]).astype(jnp.int32),
                      axis=-1, keepdims=True) > 0          # (1, 1)
    hit = keys_eq & (line[:, vw:] != 0) & (prev[:, vw:] == 0)
    out_ref[...] = jnp.where(hit, line, prev)


@functools.partial(jax.jit, static_argnames=("interpret",))
def l1_probe_pallas(
    l1_keys: jnp.ndarray,   # (sets, ways, KW) uint32
    l1_vals: jnp.ndarray,   # (sets, ways, VW) uint32
    flags: jnp.ndarray,     # (sets, ways) bool/int coherence flags
    qkeys: jnp.ndarray,     # (n, KW) uint32
    set_idx: jnp.ndarray,   # (n,) int32
    *,
    interpret: bool = True,
):
    """Returns (hit (n,) bool, vals (n, VW) uint32)."""
    sets, ways, kw = l1_keys.shape
    vw = l1_vals.shape[-1]
    n = qkeys.shape[0]
    lkeys = l1_keys.reshape(sets * ways, 1, kw)
    # the coherence flag rides one extra value lane, and the hit flag
    # comes back in the same lane of the result
    lvals = jnp.concatenate(
        [l1_vals.reshape(sets * ways, vw),
         flags.astype(jnp.uint32).reshape(sets * ways, 1)],
        axis=1).reshape(sets * ways, 1, vw + 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, ways),
        in_specs=[
            pl.BlockSpec((None, 1, kw), lambda i, j, set_ref: (i, 0, 0)),
            pl.BlockSpec((None, 1, kw),
                         lambda i, j, set_ref: (set_ref[i] * ways + j, 0, 0)),
            pl.BlockSpec((None, 1, vw + 1),
                         lambda i, j, set_ref: (set_ref[i] * ways + j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, vw + 1),
                               lambda i, j, set_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _l1_kernel,
        name="l1_probe_pallas",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, vw + 1), jnp.uint32),
        interpret=interpret,
    )(set_idx, qkeys.reshape(n, 1, kw), lkeys, lvals)
    out = out.reshape(n, vw + 1)
    return out[:, vw] != 0, out[:, :vw]
