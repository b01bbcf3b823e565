"""Masked coordinate-wise trimmed-mean kernel (TRIMMED_MEAN's hot spot).

The jnp reference sorts each coordinate's K values (masked rows pushed to
+inf) and averages positions ``[trim, m - trim)``.  TPUs have no efficient
small-K in-register sort, so — like ``coord_median.py`` — we ADAPT: the sort
is replaced by **compare-count rank selection** among the live rows.  For
each coordinate j and live row i:

    rank_i = #{k live : x_kj < x_ij} + #{k live : x_kj == x_ij and k < i}

(strict total order via index tie-break), then row i's value is kept iff
``trim <= rank_i < m - trim``.  The kept set is exactly the set the sort
would keep, so the trimmed mean is value-identical up to f32 summation
order.  When the trim window is empty (``m <= 2*trim``) the kernel degrades
to the masked mean, mirroring the reference's fallback.

Grid over d blocks; the (K, K, BLOCK_D) compare cube bounds VMEM exactly as
for the median kernel.  K stays exact — the mask rides in as a (K, 1)
column, so no zero-row padding is ever needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.meta import register_kernel_geometry


def _trimmed_mean_kernel(u_ref, mask_ref, out_ref, *, K: int, trim: int):
    x = u_ref[...].astype(jnp.float32)       # (K, BD)
    live = mask_ref[...] != 0                # (K, 1)
    m = jnp.sum(live.astype(jnp.int32))
    lt = (x[None, :, :] < x[:, None, :]) & live[None, :, :]
    idx = jax.lax.broadcasted_iota(jnp.int32, (K, K, 1), 0) > jax.lax.broadcasted_iota(
        jnp.int32, (K, K, 1), 1
    )  # i > k  (tie-break: equal values ordered by client index)
    eq = (x[None, :, :] == x[:, None, :]) & idx & live[None, :, :]
    rank = jnp.sum(lt.astype(jnp.int32) + eq.astype(jnp.int32), axis=1)  # (K, BD)
    keep = live & (rank >= trim) & (rank < m - trim)
    cnt = jnp.maximum(m - 2 * trim, 1).astype(jnp.float32)
    trimmed = jnp.sum(jnp.where(keep, x, 0.0), axis=0) / cnt
    mean = jnp.sum(jnp.where(live, x, 0.0), axis=0) / jnp.maximum(m, 1).astype(
        jnp.float32
    )
    out_ref[...] = jnp.where(m > 2 * trim, trimmed, mean)[None, :]


def trimmed_mean(
    updates: jnp.ndarray,  # (K, d), d % block_d == 0
    mask: jnp.ndarray,     # (K, 1) int32 — 1 = live row
    *,
    trim: int,
    block_d: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    K, d = updates.shape
    assert d % block_d == 0, (d, block_d)
    out = pl.pallas_call(
        functools.partial(_trimmed_mean_kernel, K=K, trim=trim),
        name="_trimmed_mean_kernel",
        grid=(d // block_d,),
        in_specs=[
            pl.BlockSpec((K, block_d), lambda b: (0, b)),
            pl.BlockSpec((K, 1), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda b: (0, b)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(updates, mask)
    return out[0]


# Declared grid-geometry contract (kernels/meta.py): one distinct output
# d-block per grid step — parallel-grid safe.
register_kernel_geometry(
    "_trimmed_mean_kernel", "per-step", True,
    "one distinct trimmed-mean d-block per grid step",
)
