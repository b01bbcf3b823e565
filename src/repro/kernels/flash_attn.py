"""Pallas TPU flash attention (causal / full), batched-heads tile.

The Perf C conclusion (DESIGN.md §Perf): GSPMD's partitioning of the
attention einsums inserts per-block partial-score psums that constraints
cannot fully remove — the definitive fix is a kernel with explicit layouts.
This kernel is that fix: per (batch·head, q-block) grid cell it streams KV
tiles through VMEM with the online-softmax recurrence entirely on-chip.

Grid: (BH, nq, nk) — nk innermost (sequential on TPU).  The running
(m, l, acc) state lives in f32 VMEM scratch carried across the nk steps; the
output tile is written once at the last kv step.  Causal masking is exact;
fully-masked tiles still execute (documented ~2x waste for causal — a
grid-remap / lower-triangular grid is the next iteration).

Layouts: q tile (BQ, D), kv tiles (BK, D); MXU matmuls (BQ,D)x(D,BK) and
(BQ,BK)x(BK,D) with BQ, BK, D multiples of 128 for hardware alignment.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.meta import register_kernel_geometry
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, block_q, block_k, causal, lk):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # (BQ, D)
    k = k_ref[0].astype(jnp.float32)  # (BK, D)
    v = v_ref[0].astype(jnp.float32)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (BQ, BK)

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < lk  # padded keys contribute nothing
    if causal:
        mask = mask & (kpos <= qpos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]  # (BQ, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_bh(
    q: jnp.ndarray,  # (BH, Lq, D)
    k: jnp.ndarray,  # (BH, Lk, D)
    v: jnp.ndarray,  # (BH, Lk, D)
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    """Batched-heads flash attention; the ops.py wrapper flattens (B, H) ->
    BH and broadcasts GQA kv beforehand."""
    bh, lq, d = q.shape
    _, lk, _ = k.shape
    block_q = min(block_q, max(lq, 8))
    block_k = min(block_k, max(lk, 8))
    pq = (-lq) % block_q
    pk = (-lk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    nq = q.shape[1] // block_q
    nk = k.shape[1] // block_k

    out = pl.pallas_call(
        functools.partial(
            _flash_attn_kernel, block_q=block_q, block_k=block_k, causal=causal, lk=lk
        ),
        name="_flash_attn_kernel",
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, nq * block_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :lq]


# Declared grid-geometry contract (kernels/meta.py): the kv recurrence is
# carried in VMEM scratch across the minor-most nk grid axis — sequential
# grids only; a compiled off-TPU launch fails at lowering rather than race.
register_kernel_geometry(
    "_flash_attn_kernel", "scratch", False,
    "m/l/acc scratch recurrence over the minor-most kv grid axis",
)
