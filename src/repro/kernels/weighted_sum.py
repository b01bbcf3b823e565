"""Reputation-weighted aggregation kernel: w_agg = c @ U.

The write path of AFA's eq. (3): a (1, K) x (K, BLOCK_D) matvec per tile,
grid over d, at ``precision=HIGHEST`` (Mosaic's default rounds f32
operands to bf16 on the MXU).  Exists mostly so the whole robust-aggregation pipeline
(gram/cosine -> while-loop on scalars -> weighted sum) can run on-chip without
bouncing the update matrix through HBM more than twice.

Packed-operand contract (ops.py): d is the FULL packed model width padded to
a BLOCK_D multiple; K is padded to the 8-row sublane tile with ZERO weights
on the pad rows, so the matvec is exact and only d-columns need slicing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.meta import register_kernel_geometry


def _weighted_sum_kernel(c_ref, u_ref, out_ref):
    c = c_ref[...].astype(jnp.float32)  # (1, K)
    u = u_ref[...].astype(jnp.float32)  # (K, BD)
    out_ref[...] = jax.lax.dot_general(
        c, u, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def weighted_sum(
    weights: jnp.ndarray,  # (1, K)
    updates: jnp.ndarray,  # (K, d), d % block_d == 0
    *,
    block_d: int = 2048,
    interpret: bool = True,
) -> jnp.ndarray:
    K, d = updates.shape
    assert d % block_d == 0, (d, block_d)
    out = pl.pallas_call(
        _weighted_sum_kernel,
        name="_weighted_sum_kernel",
        grid=(d // block_d,),
        in_specs=[
            pl.BlockSpec((1, K), lambda b: (0, 0)),
            pl.BlockSpec((K, block_d), lambda b: (0, b)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda b: (0, b)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(weights, updates)
    return out[0]


# Declared grid-geometry contract (kernels/meta.py): every grid step writes
# its own distinct (1, BLOCK_D) output block — parallel-grid safe.
register_kernel_geometry(
    "_weighted_sum_kernel", "per-step", True,
    "one distinct output d-block per grid step, no revisits",
)
