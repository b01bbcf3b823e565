"""Additional robust aggregation rules from the surrounding literature.

* ``geometric_median`` — smoothed Weiszfeld iterations (Pillutla et al. 2019):
  minimizes Σ ||w − u_k||; a stronger classical robust estimator than the
  coordinate-wise median.
* ``centered_clip`` — centered clipping (Karimireddy et al. 2021): iterate
  v ← v + Σ_k clip(u_k − v, τ) / K; robust to ALIE-style inlier attacks.
* ``zeno`` — Zeno (Xie et al. 2019): score each update by estimated loss
  descent minus a norm penalty on a server-held validation function and keep
  the top (K − b).  The paper contrasts AFA against Zeno's fixed-k selection.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.baselines import AggResult, _norm_weights, register_rule

EPS = 1e-8


@functools.partial(jax.jit, static_argnames=("iters",))
def geometric_median_aggregate(
    updates, n_k=None, p_k=None, mask=None, *, iters: int = 8
) -> AggResult:
    K = updates.shape[0]
    mask = jnp.ones((K,), bool) if mask is None else mask
    u = updates.astype(jnp.float32)
    v0 = jnp.sum(jnp.where(mask[:, None], u, 0.0), 0) / jnp.maximum(mask.sum(), 1)

    def step(v, _):
        dist = jnp.sqrt(jnp.sum((u - v[None]) ** 2, axis=1) + EPS)
        w = jnp.where(mask, 1.0 / dist, 0.0)
        v_new = jnp.matmul(w, u, precision=jax.lax.Precision.HIGHEST) / jnp.maximum(
            jnp.sum(w), EPS
        )
        return v_new, None

    v, _ = jax.lax.scan(step, v0, None, length=iters)
    return AggResult(v.astype(updates.dtype), mask)


@functools.partial(jax.jit, static_argnames=("iters",))
def centered_clip_aggregate(
    updates, n_k=None, p_k=None, mask=None, *, clip_tau: float | None = None,
    iters: int = 5
) -> AggResult:
    """clip_tau=None self-tunes: tau = median distance of the (masked) updates
    to the robust center — benign spread passes unclipped, outliers clip."""
    K = updates.shape[0]
    mask = jnp.ones((K,), bool) if mask is None else mask
    u = updates.astype(jnp.float32)
    # robust init: coordinate-wise median (a mean init is already poisoned by
    # large-norm outliers and tau-clipped steps may never recover)
    from repro.core.baselines import comed_aggregate
    from repro.core.stats import masked_median

    v0 = comed_aggregate(updates, mask=mask).aggregate.astype(jnp.float32)
    if clip_tau is None:
        dists = jnp.sqrt(jnp.sum((u - v0[None]) ** 2, axis=1) + EPS)
        clip_tau = 2.0 * masked_median(dists, mask)

    def step(v, _):
        d = u - v[None]
        norms = jnp.sqrt(jnp.sum(d * d, axis=1) + EPS)
        scale = jnp.minimum(1.0, clip_tau / norms)
        d = d * jnp.where(mask, scale, 0.0)[:, None]
        v = v + jnp.sum(d, axis=0) / jnp.maximum(mask.sum(), 1)
        return v, None

    v, _ = jax.lax.scan(step, v0, None, length=iters)
    return AggResult(v.astype(updates.dtype), mask)


def zeno_aggregate(
    updates,
    n_k=None,
    p_k=None,
    mask=None,
    *,
    loss_fn: Callable,            # (flat_params,) -> scalar validation loss
    w_prev,                       # (d,) current server params
    num_keep: int,
    rho: float = 1e-3,
) -> AggResult:
    """Zeno suspicion score: loss(w_prev) − loss(u_k) − rho·||u_k − w_prev||²;
    keep the ``num_keep`` highest.  Requires a server-side validation loss —
    the dependency AFA removes (its score is similarity, not loss)."""
    K = updates.shape[0]
    mask = jnp.ones((K,), bool) if mask is None else mask
    base = loss_fn(w_prev)
    losses = jax.vmap(loss_fn)(updates)
    pen = rho * jnp.sum((updates - w_prev[None]) ** 2, axis=1)
    scores = jnp.where(mask, base - losses - pen, -jnp.inf)
    order = jnp.argsort(-scores)
    ranks = jnp.zeros((K,), jnp.int32).at[order].set(jnp.arange(K, dtype=jnp.int32))
    keep = (ranks < num_keep) & mask
    c = _norm_weights(keep, jnp.ones((K,), jnp.float32))
    agg = jnp.matmul(c, updates.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    return AggResult(agg.astype(updates.dtype), keep)


# Registry hookup.  No Pallas kernel covers the Weiszfeld / clipping
# iterations, so both rules run the jnp reference under every kernel policy
# mode (they never consume ``opts.use_kernels`` — now the registry's ONLY
# kernel-less rules, since trimmed-mean gained its masked rank-trim kernel).
# Both participate in the packed (K, D) dispatch like any other matrix rule.  Zeno stays OUT of the registry: it needs a server-side
# validation loss_fn + w_prev, which the uniform dispatch signature (and the
# paper's trust model) does not carry.
register_rule("geomed", lambda u, n, p, m, o: geometric_median_aggregate(u, mask=m))
register_rule("centered_clip", lambda u, n, p, m, o: centered_clip_aggregate(u, mask=m))
