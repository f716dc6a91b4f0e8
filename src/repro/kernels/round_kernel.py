"""Pallas TPU kernel: significant-digit rounding (surrogate key derivation).

POET rounds every chemistry input to a user-chosen number of significant
digits before hashing (paper §5.4) — this runs once per grid cell per time
step, in front of every DHT op, so it is fused into one elementwise VMEM
tile pass: |x| -> decimal exponent via log10 -> scale -> round -> unscale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.neighbors import round_significant

BLOCK_R = 8
BLOCK_C = 128


def _round_kernel(x_ref, out_ref, *, sig_digits: int):
    # the canonical lattice projection runs unchanged inside the kernel
    # (zeros/denormals -> 0, inf/nan pass through, pow10(±e) rescale)
    out_ref[...] = round_significant(x_ref[...], sig_digits)


@functools.partial(jax.jit, static_argnames=("sig_digits", "interpret"))
def round_sig_pallas(
    x: jnp.ndarray, sig_digits: int, *, interpret: bool = True
) -> jnp.ndarray:
    """Elementwise round-to-significant-digits; any shape, f32."""
    shape = x.shape
    flat = x.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    per_tile = BLOCK_R * BLOCK_C
    n_pad = -(-n // per_tile) * per_tile
    tiled = jnp.pad(flat, (0, n_pad - n)).reshape(-1, BLOCK_C)
    rows = tiled.shape[0]
    out = pl.pallas_call(
        functools.partial(_round_kernel, sig_digits=sig_digits),
        name="round_sig_pallas",
        grid=(rows // BLOCK_R,),
        in_specs=[pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, BLOCK_C), jnp.float32),
        interpret=interpret,
    )(tiled)
    return out.reshape(-1)[:n].reshape(shape)
