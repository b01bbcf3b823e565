"""Blocked cosine-similarity kernel: K client updates vs the aggregate.

The AFA hot loop computes ``s_k = <u_k, w> / (|u_k||w|)`` over d ~ 1e8..1e11
parameters.  The kernel streams the (K, d) update matrix and the (d,)
aggregate through VMEM in ``(K, BLOCK_D)`` / ``(1, BLOCK_D)`` tiles, grid over
the d axis, accumulating three partial reductions in f32 VMEM scratch-free
output accumulators:

    dots   (K,)  = sum_b  U[:, b] @ w[b]
    unorm2 (K,)  = sum_b  sum(U[:, b]^2, axis=1)
    wnorm2 (1,)  = sum_b  sum(w[b]^2)

TPU grid iterations are sequential, so read-modify-write accumulation on the
outputs is safe; the final divide happens in ops.py (O(K), negligible).
The dots and the squares are elementwise products and lane reductions on
the VPU, in f32 (no MXU, so no bf16 operand rounding).

Packed-operand contract (ops.py): d is the FULL packed model width, zero-
padded to a BLOCK_D multiple, and K arrives zero-padded to the 8-row f32
sublane tile — zero rows contribute zero dots/norms and are sliced off after
the kernel, so padding is exact.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.meta import register_kernel_geometry


def _cosine_sim_kernel(u_ref, w_ref, dots_ref, unorm2_ref, wnorm2_ref):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        dots_ref[...] = jnp.zeros_like(dots_ref)
        unorm2_ref[...] = jnp.zeros_like(unorm2_ref)
        wnorm2_ref[...] = jnp.zeros_like(wnorm2_ref)

    u = u_ref[...].astype(jnp.float32)  # (K, BD)
    w = w_ref[...].astype(jnp.float32)  # (1, BD)
    dots_ref[...] += jnp.sum(u * w, axis=1, keepdims=True)  # (K, 1)
    unorm2_ref[...] += jnp.sum(u * u, axis=1, keepdims=True)
    wnorm2_ref[...] += jnp.sum(w * w, axis=1, keepdims=True)


def cosine_sim_parts(
    updates: jnp.ndarray,  # (K, d) — d padded to BLOCK_D multiple by ops.py
    agg: jnp.ndarray,      # (1, d)
    *,
    block_d: int = 2048,
    interpret: bool = True,
):
    K, d = updates.shape
    assert d % block_d == 0, (d, block_d)
    grid = (d // block_d,)
    out_shapes = (
        jax.ShapeDtypeStruct((K, 1), jnp.float32),
        jax.ShapeDtypeStruct((K, 1), jnp.float32),
        jax.ShapeDtypeStruct((1, 1), jnp.float32),
    )
    return pl.pallas_call(
        _cosine_sim_kernel,
        name="_cosine_sim_kernel",
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, block_d), lambda b: (0, b)),
            pl.BlockSpec((1, block_d), lambda b: (0, b)),
        ],
        out_specs=(
            pl.BlockSpec((K, 1), lambda b: (0, 0)),
            pl.BlockSpec((K, 1), lambda b: (0, 0)),
            pl.BlockSpec((1, 1), lambda b: (0, 0)),
        ),
        out_shape=out_shapes,
        interpret=interpret,
    )(updates, agg)


# Declared grid-geometry contract (kernels/meta.py): the three partial
# reductions accumulate into constant-index blocks across the d grid —
# sequential grids only (repro.analysis.races re-derives and enforces this).
register_kernel_geometry(
    "_cosine_sim_kernel", "cross-step", False,
    "dots/unorm2/wnorm2 blocks accumulated over the d grid axis",
)
