"""Plain reference of the closed-loop served rounds: the same rows, in the
same submission orders, through ingress admission, Algorithm 1's
screening, the Beta reputation and blocking, and the aggregate, computed
from the rows alone.

``precision``: ``float64`` (Gram matrices and aggregates on the host in
float64: the reference), ``high`` or ``default`` (on the device in float32,
each contraction as three or one bf16 passes with float32 accumulation,
which is what ``Precision.HIGH`` and ``Precision.DEFAULT`` compute on a
TPU, spelled out so that every backend computes it: the controls).
"""

from __future__ import annotations

import functools

import numpy as np

from bench.reference.fl_afa import Reputation, afa_screen

CHUNK = 1 << 16
ACCEPTED, REJECTED_BLOCKED = "accepted", "rejected_blocked"


def _split(a):
    import jax.numpy as jnp

    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def dot_passes(a, b, passes: int):
    """``a @ b`` from bf16 passes with float32 accumulation: three passes
    (``hi*hi + hi*lo + lo*hi``, what ``Precision.HIGH`` computes) or one
    (``hi*hi``, ``Precision.DEFAULT``)."""
    import jax
    import jax.numpy as jnp

    (ah, al), (bh, bl) = _split(a), _split(b)
    dot = functools.partial(jnp.matmul, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.DEFAULT)
    if passes == 1:
        return dot(ah, bh)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


PASSES = {"high": 3, "default": 1}


def _gram(rows, precision):
    if precision == "float64":
        G = np.zeros((rows.shape[0],) * 2)
        for c in range(0, rows.shape[1], CHUNK):
            r = rows[:, c:c + CHUNK].astype(np.float64)
            G += r @ r.T
        return G
    import jax.numpy as jnp

    r = jnp.asarray(rows)
    return np.asarray(dot_passes(r, r.T, PASSES[precision]), np.float64)


def _wsum(w, rows, precision):
    if precision == "float64":
        return np.concatenate([
            np.asarray(w, np.float64) @ rows[:, c:c + CHUNK].astype(np.float64)
            for c in range(0, rows.shape[1], CHUNK)])
    import jax.numpy as jnp

    return np.asarray(dot_passes(jnp.asarray(w, jnp.float32)[None], jnp.asarray(rows),
                                 PASSES[precision])[0], np.float64)


def replay(pool, orders, n_rounds, n_k, cfg, sampled, precision="float64") -> dict:
    P, K, _ = pool.shape
    grams = [_gram(pool[p], precision) for p in range(P)]
    rep = Reputation(K, cfg["alpha0"], cfg["beta0"], cfg["delta_block"])
    n_k = np.asarray(n_k, np.float64)
    sampled = set(sampled)
    decisions, kept_all, weights = [], np.zeros((n_rounds, K), bool), {}
    for r in range(n_rounds):
        slot, order = r % P, orders[r % len(orders)]
        live = ~rep.blocked
        decisions += [REJECTED_BLOCKED if rep.blocked[k] else ACCEPTED for k in order]
        ids = np.nonzero(live)[0]
        kept, w, _ = afa_screen(grams[slot][np.ix_(ids, ids)],
                                rep.p_good()[ids] * n_k[ids],
                                cfg["xi0"], cfg["delta_xi"], cfg.get("afa_max_rounds", 8))
        kept_all[r, ids] = kept
        if r in sampled:
            weights[r] = (slot, ids, w)
        rep.absorb(r, live, kept_all[r])
    aggregates = {r: _wsum(w, pool[slot][ids], precision)
                  for r, (slot, ids, w) in weights.items()}
    return dict(decisions=decisions, kept=kept_all, blocked_round=rep.blocked_round,
                alpha=rep.alpha, beta=rep.beta, aggregates=aggregates)
