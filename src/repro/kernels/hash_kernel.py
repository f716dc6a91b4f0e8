"""Pallas TPU kernel: batched 64-bit key hashing.

The DHT's front door — every read/write hashes its key to find the owner
shard and probe-window base.  One grid step hashes a (BLOCK_N, KW) tile of
keys resident in VMEM; the murmur chain is unrolled over the KW word
columns (KW is small and static: 20 for POET keys), so the whole tile is
register/VPU work after one DMA.

Layout notes (TPU): BLOCK_N is a multiple of 8x128 packing for uint32
lanes; KW rides in the minor-most dimension of the input tile but every
op is elementwise over the N axis, so lane alignment of N is what
matters.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hashing import SEED_HI, SEED_LO, murmur32_words

BLOCK_N = 256


def _hash_kernel(keys_ref, out_ref):
    keys = keys_ref[...]                       # (BLOCK_N, KW) uint32, in VMEM
    hi = murmur32_words(keys, SEED_HI)         # unrolled murmur chain
    lo = murmur32_words(keys, SEED_LO)
    out_ref[...] = jnp.stack([hi, lo], axis=-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def hash64_pallas(keys: jnp.ndarray, *, interpret: bool = True) -> jnp.ndarray:
    """(N, KW) uint32 -> (N, 2) uint32 [hi, lo].  N padded to BLOCK_N."""
    n, kw = keys.shape
    n_pad = -(-n // BLOCK_N) * BLOCK_N
    keys_p = jnp.pad(keys, ((0, n_pad - n), (0, 0)))
    out = pl.pallas_call(
        _hash_kernel,
        name="hash64_pallas",
        grid=(n_pad // BLOCK_N,),
        in_specs=[pl.BlockSpec((BLOCK_N, kw), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BLOCK_N, 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 2), jnp.uint32),
        interpret=interpret,
    )(keys_p)
    return out[:n]
