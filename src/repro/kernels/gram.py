"""Blocked Gram-matrix kernel: G = U @ U^T for K client updates.

Backs both MKRUM's pairwise distances (d2_ij = G_ii + G_jj - 2 G_ij) and the
one-shot "gram" variant of AFA.  Two layouts over the packed (K, D) operand:

* **single-tile** (``block_k=None``): grid over the d axis only; each step
  loads one (K, BLOCK_D) tile and accumulates the whole (K, K) outer product
  on the MXU.  K is the client count (<= a few hundred), so the (K, K) f32
  accumulator lives comfortably in VMEM for the whole pass.
* **K-tiled** (``block_k=BK``): grid (K/BK, K/BK, D/BLOCK_D) with the d axis
  minor-most, so each (BK, BK) output tile sees its d-steps sequentially and
  read-modify-write accumulation stays safe (TPU grid iterations are
  sequential).  For packed stacks too wide for a VMEM-resident (K, K)
  accumulator.

Both accumulate at ``precision=HIGHEST`` (Mosaic's default rounds f32
operands to bf16 on the MXU).

ops.py zero-pads K to the block/sublane multiple — zero rows contribute zero
dot products, so the padded Gram rows/columns are sliced off exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.meta import register_kernel_geometry


def _gram_kernel(u_ref, g_ref):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    u = u_ref[...].astype(jnp.float32)
    g_ref[...] += jax.lax.dot_general(
        u, u, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def _gram_kernel_tiled(ui_ref, uj_ref, g_ref):
    b = pl.program_id(2)  # d-axis is minor-most: sequential per output tile

    @pl.when(b == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    ui = ui_ref[...].astype(jnp.float32)  # (BK, BD) row block i
    uj = uj_ref[...].astype(jnp.float32)  # (BK, BD) row block j
    g_ref[...] += jax.lax.dot_general(
        ui, uj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def gram(
    updates: jnp.ndarray,  # (K, d), d % block_d == 0 (and K % block_k when tiled)
    *,
    block_d: int = 2048,
    block_k: int | None = None,
    interpret: bool = True,
) -> jnp.ndarray:
    K, d = updates.shape
    assert d % block_d == 0, (d, block_d)
    if block_k is None or block_k >= K:
        return pl.pallas_call(
            _gram_kernel,
            name="_gram_kernel",
            grid=(d // block_d,),
            in_specs=[pl.BlockSpec((K, block_d), lambda b: (0, b))],
            out_specs=pl.BlockSpec((K, K), lambda b: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((K, K), jnp.float32),
            interpret=interpret,
        )(updates)
    assert K % block_k == 0, (K, block_k)
    return pl.pallas_call(
        _gram_kernel_tiled,
        name="_gram_kernel_tiled",
        grid=(K // block_k, K // block_k, d // block_d),
        in_specs=[
            pl.BlockSpec((block_k, block_d), lambda i, j, b: (i, b)),
            pl.BlockSpec((block_k, block_d), lambda i, j, b: (j, b)),
        ],
        out_specs=pl.BlockSpec((block_k, block_k), lambda i, j, b: (i, j)),
        out_shape=jax.ShapeDtypeStruct((K, K), jnp.float32),
        interpret=interpret,
    )(updates, updates)


# Declared grid-geometry contract (kernels/meta.py), cross-checked statically
# by repro.analysis.races: both gram layouts accumulate their (K, K) / (BK,
# BK) output block across d-grid steps — sequential grids only.
register_kernel_geometry(
    "_gram_kernel", "cross-step", False,
    "constant-index (K, K) block accumulated over the d grid axis",
)
register_kernel_geometry(
    "_gram_kernel_tiled", "cross-step", False,
    "(BK, BK) output tile accumulated over the minor-most d grid axis",
)
