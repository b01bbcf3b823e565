"""Coordinate-wise median kernel (the COMED aggregation hot spot).

GPU implementations sort each coordinate's K values.  TPUs have no efficient
small-K in-register sort, so we ADAPT rather than port: median by
**compare-count rank selection**.  For each coordinate j:

    rank_i = #{k : x_kj < x_ij}  +  #{k : x_kj == x_ij and k < i}

(strict total order via index tie-break), then the median is the mean of the
values whose ranks are (K-1)//2 and K//2.  This is O(K^2) broadcast compares
per coordinate — pure VPU work with perfect lanes utilization and no data
movement, a bargain for K <= a few hundred clients.

Grid over d blocks; the (K, K, BLOCK_D) compare cube bounds VMEM, so BLOCK_D
shrinks as K grows (handled in ops.py).  Unlike the dot/norm kernels, K is
NEVER zero-padded here — an extra zero row would shift the median — so the
client axis stays exact and only d is padded to the block multiple.

The masked variant ranks each live row against the live subset only and
selects ranks ``(m-1)//2`` / ``m//2`` — the same two order statistics the
reference's ±inf-filled sort picks, so blocked clients never shift the
median and the whole rule stays a single launch even under a traced mask
(no host row-selection round-trip).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.meta import register_kernel_geometry


def _coord_median_kernel(u_ref, med_ref, *, K: int):
    x = u_ref[...].astype(jnp.float32)  # (K, BD)
    lt = (x[None, :, :] < x[:, None, :]).astype(jnp.int32)  # cmp[i,k,:] = x_k < x_i
    idx = jax.lax.broadcasted_iota(jnp.int32, (K, K, 1), 0) > jax.lax.broadcasted_iota(
        jnp.int32, (K, K, 1), 1
    )  # i > k  (tie-break: equal values ordered by client index)
    eq = (x[None, :, :] == x[:, None, :]) & idx
    rank = jnp.sum(lt + eq.astype(jnp.int32), axis=1)  # (K, BD)
    lo, hi = (K - 1) // 2, K // 2
    v_lo = jnp.sum(jnp.where(rank == lo, x, 0.0), axis=0)
    v_hi = jnp.sum(jnp.where(rank == hi, x, 0.0), axis=0)
    med_ref[...] = (0.5 * (v_lo + v_hi))[None, :]


def _coord_median_masked_kernel(u_ref, mask_ref, med_ref, *, K: int):
    x = u_ref[...].astype(jnp.float32)       # (K, BD)
    live = mask_ref[...] != 0                # (K, 1)
    m = jnp.sum(live.astype(jnp.int32))
    lt = (x[None, :, :] < x[:, None, :]) & live[None, :, :]
    idx = jax.lax.broadcasted_iota(jnp.int32, (K, K, 1), 0) > jax.lax.broadcasted_iota(
        jnp.int32, (K, K, 1), 1
    )
    eq = (x[None, :, :] == x[:, None, :]) & idx & live[None, :, :]
    rank = jnp.sum(lt.astype(jnp.int32) + eq.astype(jnp.int32), axis=1)  # (K, BD)
    lo = jnp.maximum((m - 1) // 2, 0)
    hi = jnp.maximum(m // 2, 0)
    v_lo = jnp.sum(jnp.where(live & (rank == lo), x, 0.0), axis=0)
    v_hi = jnp.sum(jnp.where(live & (rank == hi), x, 0.0), axis=0)
    med_ref[...] = jnp.where(m > 0, 0.5 * (v_lo + v_hi), 0.0)[None, :]


def coord_median(
    updates: jnp.ndarray,  # (K, d), d % block_d == 0
    mask: jnp.ndarray | None = None,  # (K, 1) int32 — 1 = live row
    *,
    block_d: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    K, d = updates.shape
    assert d % block_d == 0, (d, block_d)
    if mask is None:
        out = pl.pallas_call(
            functools.partial(_coord_median_kernel, K=K),
            name="_coord_median_kernel",
            grid=(d // block_d,),
            in_specs=[pl.BlockSpec((K, block_d), lambda b: (0, b))],
            out_specs=pl.BlockSpec((1, block_d), lambda b: (0, b)),
            out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
            interpret=interpret,
        )(updates)
        return out[0]
    out = pl.pallas_call(
        functools.partial(_coord_median_masked_kernel, K=K),
        name="_coord_median_masked_kernel",
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
# d-block per grid step — parallel-grid safe (both mask variants).
register_kernel_geometry(
    "_coord_median_kernel", "per-step", True,
    "one distinct median d-block per grid step",
)
register_kernel_geometry(
    "_coord_median_masked_kernel", "per-step", True,
    "one distinct median d-block per grid step, mask-aware ranking",
)
