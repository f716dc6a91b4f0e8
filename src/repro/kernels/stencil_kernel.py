"""Pallas TPU kernel: fused stencil-key generation for neighborhood queries.

The neighborhood-query front end (DESIGN.md §6) runs, per query row:
round -> enumerate the ±radius lattice stencil -> bitcast-pack each point
into a DHT key -> hash -> derive the contiguous probe-window base.  Done
naively that is M = 1 + 2·radius·D (+1) separate round/pack/hash launches
per batch.  This kernel fuses the whole front end into one VMEM tile pass:
each (BLOCK_R, D) input block is expanded in-register to all M stencil
points, packed (even-slot f32→u32 interleave, exactly
``core.layout.pack_floats``) and hashed down to the per-key probe-window
base that feeds the probe kernel — the query-side counterpart of
``probe_kernel.py``'s bucket side.

The stencil enumeration order, rounding math and murmur constants are
imported from ``core.neighbors`` / ``core.hashing``, so the kernel is
validated **bit-for-bit** against the pure-JAX reference
(``kernels/ref.ref_stencil_keys``, tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hashing import SEED_LO, murmur32_words
from repro.core.neighbors import lattice_step, round_significant, stencil_offsets

BLOCK_R = 8


def _pack_rows(p: jnp.ndarray, key_words: int) -> jnp.ndarray:
    # core.layout.pack_floats for one (R, D) tile: value words in even
    # slots, zero words between (the paper's 80-byte f64-shaped layout)
    r, d = p.shape
    u = jax.lax.bitcast_convert_type(p, jnp.uint32)
    interleaved = jnp.stack(
        [u, jnp.zeros_like(u)], axis=-1).reshape(r, 2 * d)
    if key_words <= 2 * d:
        return interleaved[:, :key_words]
    pad = jnp.zeros((r, key_words - 2 * d), jnp.uint32)
    return jnp.concatenate([interleaved, pad], axis=1)


def _stencil_kernel(x_ref, keys_out, base_out, *, sig_digits: int,
                    offsets, key_words: int, span: int):
    # the canonical jnp helpers run unchanged inside the kernel — one
    # definition of the lattice math, bit-for-bit by construction
    x = x_ref[...]                                        # (R, D)
    center = round_significant(x, sig_digits)
    step = lattice_step(center, sig_digits)
    col = jax.lax.broadcasted_iota(jnp.int32, center.shape, 1)

    key_tiles = []
    base_tiles = []
    for dim, off in offsets:                              # static unroll
        if dim == -1:
            p = center
        elif dim == -2:
            # coarse tier re-expressed on the sig-lattice (see neighbors.py)
            p = round_significant(
                round_significant(center, sig_digits - 1), sig_digits)
        else:
            shifted = jnp.where(col == dim, center + off * step, center)
            p = round_significant(shifted, sig_digits)
        k = _pack_rows(p, key_words)                      # (R, KW)
        key_tiles.append(k)
        h_lo = murmur32_words(k, SEED_LO)                 # (R,)
        base_tiles.append((h_lo % jnp.uint32(span)).astype(jnp.int32))
    keys_out[...] = jnp.concatenate(key_tiles, axis=1)    # (R, M*KW)
    base_out[...] = jnp.stack(base_tiles, axis=1)         # (R, M)


@functools.partial(jax.jit, static_argnames=(
    "sig_digits", "key_words", "radius", "coarse_tier", "n_buckets",
    "n_probe", "interpret"))
def stencil_keys_pallas(
    x: jnp.ndarray,            # (n, D) float32 queries
    sig_digits: int,
    key_words: int,
    *,
    radius: int = 1,
    coarse_tier: bool = True,
    n_buckets: int = 1024,
    n_probe: int = 6,
    interpret: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused stencil front end.

    Returns ``(keys (n, M, KW) uint32, base (n, M) int32)`` — the packed
    neighborhood keys plus each key's contiguous probe-window start
    (``core.hashing.base_bucket`` semantics), ready for the probe kernel.
    """
    n, d = x.shape
    offsets = tuple(stencil_offsets(d, radius, coarse_tier))
    m = len(offsets)
    span = max(n_buckets - n_probe + 1, 1)

    n_pad = -(-n // BLOCK_R) * BLOCK_R
    xp = jnp.pad(x.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
    kernel = functools.partial(
        _stencil_kernel, sig_digits=sig_digits, offsets=offsets,
        key_words=key_words, span=span)
    keys, base = pl.pallas_call(
        kernel,
        name="stencil_keys_pallas",
        grid=(n_pad // BLOCK_R,),
        in_specs=[pl.BlockSpec((BLOCK_R, d), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((BLOCK_R, m * key_words), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_R, m), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, m * key_words), jnp.uint32),
            jax.ShapeDtypeStruct((n_pad, m), jnp.int32),
        ],
        interpret=interpret,
    )(xp)
    return keys[:n].reshape(n, m, key_words), base[:n]
