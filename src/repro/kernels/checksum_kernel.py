"""Pallas TPU kernel: bucket checksum over key || value.

The lock-free DHT's consistency primitive (paper §4.2): writers append a
32-bit checksum to every bucket; readers recompute and compare.  This is
the per-op hot loop of the lock-free mode, so it gets a kernel: one grid
step checksums a (BLOCK_N, KW+VW) tile — the key and value tiles are DMA'd
to VMEM once and the murmur chain is unrolled over the static word count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hashing import murmur32_words

BLOCK_N = 256
_SEED = 0xB5297A4D  # must match repro.core.hashing.checksum32


def _checksum_kernel(keys_ref, vals_ref, out_ref):
    both = jnp.concatenate([keys_ref[...], vals_ref[...]], axis=-1)
    out_ref[...] = murmur32_words(both, _SEED)[:, None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def checksum_pallas(
    keys: jnp.ndarray, vals: jnp.ndarray, *, interpret: bool = True
) -> jnp.ndarray:
    """(N, KW) x (N, VW) uint32 -> (N,) uint32."""
    n, kw = keys.shape
    vw = vals.shape[1]
    n_pad = -(-n // BLOCK_N) * BLOCK_N
    keys_p = jnp.pad(keys, ((0, n_pad - n), (0, 0)))
    vals_p = jnp.pad(vals, ((0, n_pad - n), (0, 0)))
    out = pl.pallas_call(
        _checksum_kernel,
        name="checksum_pallas",
        grid=(n_pad // BLOCK_N,),
        in_specs=[
            pl.BlockSpec((BLOCK_N, kw), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_N, vw), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_N, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.uint32),
        interpret=interpret,
    )(keys_p, vals_p)
    return out[:n, 0]
