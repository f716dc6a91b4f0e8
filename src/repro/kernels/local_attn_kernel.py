"""Pallas TPU kernel: sliding-window (local) flash attention.

The model-side hot spot for the local-attention layers (gemma3-12b runs 5
of 6 layers with a 1024-token window; recurrentgemma 1 of 3 with 2048).
Unlike the XLA chunked path (models/flash.py) which computes full
rectangles and masks, this kernel touches ONLY the KV band each query
block can see: grid (batch*heads, q_blocks, band_tiles) with the band's
block indices derived from the query block index — O(S*W) work and
traffic.

Per grid step: one (BQ, D) query block stays resident; (BK, D) K/V band
tiles stream through VMEM; online-softmax statistics (m, l) live in VMEM
scratch across the band loop — the canonical flash structure.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, window: int, bq: int, bk: int, causal: bool):
    i = pl.program_id(1)          # query block
    j = pl.program_id(2)          # band tile
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the band tile's block index as the index map computed it.  Clamped
    # (would-be-negative) tiles duplicate block 0, so they are masked out
    # entirely: coverage of block 0 comes from the j with unclamped == 0.
    q_start = i * bq
    unclamped = i * (bq // bk) - window // bk + j
    k_start = jnp.maximum(unclamped, 0) * bk

    q = q_ref[0].astype(jnp.float32)                # (BQ, D)
    k = k_ref[0].astype(jnp.float32)                # (BK, D)
    s = jnp.dot(q, k.T) / math.sqrt(q.shape[-1])    # (BQ, BK)

    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = ((q_pos - k_pos) < window) & (unclamped >= 0)
    if causal:
        valid &= k_pos <= q_pos
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    m_ref[...] = m_new
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + jnp.dot(p, v_ref[0].astype(jnp.float32)))

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "causal", "bq", "bk", "interpret"))
def local_attention_pallas(
    q: jnp.ndarray,    # (BH, S, D)
    k: jnp.ndarray,    # (BH, S, D)
    v: jnp.ndarray,    # (BH, S, D)
    *,
    window: int,
    causal: bool = True,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    bh, s, d = q.shape
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and window % bk == 0 and bq % bk == 0, (s, bq, bk, window)
    band_tiles = window // bk + bq // bk   # [q_end - W - BQ, q_end) coverage

    def q_map(b, i, j):
        return (b, i, 0)

    def kv_map(b, i, j):
        return (b, jnp.maximum(i * (bq // bk) - window // bk + j, 0), 0)

    kernel = functools.partial(
        _kernel, window=window, bq=bq, bk=bk, causal=causal)
    return pl.pallas_call(
        kernel,
        name="local_attention_pallas",
        grid=(bh, s // bq, band_tiles),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), q_map),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
