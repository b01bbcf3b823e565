"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics the kernels must match (see tests/test_kernels.py,
which sweeps shapes/dtypes and asserts allclose against these)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-12
HIGHEST = jax.lax.Precision.HIGHEST  # full f32, as the kernels contract


def cosine_sim_ref(updates: jnp.ndarray, agg: jnp.ndarray) -> jnp.ndarray:
    """(K, d), (d,) -> (K,) cosine similarities in f32."""
    u = updates.astype(jnp.float32)
    w = agg.astype(jnp.float32)
    dots = jnp.matmul(u, w, precision=HIGHEST)
    un = jnp.linalg.norm(u, axis=1)
    wn = jnp.linalg.norm(w)
    return dots / (jnp.maximum(un, EPS) * jnp.maximum(wn, EPS))


def gram_ref(updates: jnp.ndarray) -> jnp.ndarray:
    """(K, d) -> (K, K) Gram matrix in f32."""
    u = updates.astype(jnp.float32)
    return jnp.matmul(u, u.T, precision=HIGHEST)


def coord_median_ref(updates: jnp.ndarray) -> jnp.ndarray:
    """(K, d) -> (d,) coordinate-wise median in f32 (numpy convention:
    average of the two central order statistics for even K)."""
    return jnp.median(updates.astype(jnp.float32), axis=0)


def weighted_sum_ref(updates: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """(K, d), (K,) -> (d,) weighted sum in f32."""
    return jnp.matmul(
        weights.astype(jnp.float32), updates.astype(jnp.float32), precision=HIGHEST
    )


def flash_attention_ref(q, k, v, *, causal: bool = True) -> jnp.ndarray:
    """(B, Lq, Hq, D), (B, Lk, Hkv, D) x2 -> (B, Lq, Hq, D), exact softmax."""
    b, lq, hq, d = q.shape
    _, lk, hkv, _ = k.shape
    g = hq // hkv
    qs = q.reshape(b, lq, hkv, g, d).astype(jnp.float32)
    s = jnp.einsum("blhgd,bmhd->bhglm", qs, k.astype(jnp.float32)) / jnp.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq)
        s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhglm,bmhd->blhgd", p, v.astype(jnp.float32))
    return o.reshape(b, lq, hq, d).astype(q.dtype)
