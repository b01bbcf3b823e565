"""The paper's three bad-client behaviours + one beyond-paper stealth attack.

Two kinds of hooks:
  * data poisoning (applied to a client's shard before training):
      - ``flip_labels``      — label-flipping attack: all labels -> 0
      - ``noisy_features``   — uniform noise U(-1.4, 1.4) added, re-cropped to
                               [-1, 1] (or 30% random feature flips for binary
                               data), the paper's "noisy clients"
  * update poisoning (replaces the model update a client sends):
      - ``byzantine_update_attack`` — w_t + N(0, 20^2 I), the paper's
                               byzantine clients
      - ``alie_update_attack``      — "A Little Is Enough"-style (Baruch et
                               al. 2019): colluding attackers shift the benign
                               mean by z_max standard deviations, staying
                               inside the benign spread.  The paper names this
                               family as an open weakness; we include it to
                               probe AFA beyond its own evaluation.

The update-level attacks come in two executable forms:
  * legacy numpy helpers operating on flat ``(d,)`` / ``(K, d)`` arrays
    (kept for analysis scripts and unit tests);
  * jit-able *stacked-pytree transforms* (``*_update_tree`` and the
    ``apply_update_attack`` dispatcher) operating on proposals with a leading
    client axis on every leaf — the round-engine path (DESIGN.md §2).  Both
    simulator engines route attacks through the tree transforms so their
    trajectories agree on fixed seeds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

UPDATE_ATTACK_SCENARIOS = ("byzantine", "alie", "ipm")


def flip_labels(x: np.ndarray, y: np.ndarray, rng=None, target: int = 0):
    return x, np.full_like(y, target)


def noisy_features(x: np.ndarray, y: np.ndarray, rng=None, *, binary: bool | None = None):
    rng = rng or np.random.default_rng(0)
    binary = bool(((x == 0) | (x == 1)).all()) if binary is None else binary
    if binary:
        flip = rng.uniform(size=x.shape) < 0.30
        return np.where(flip, 1.0 - x, x).astype(x.dtype), y
    eps = rng.uniform(-1.4, 1.4, size=x.shape).astype(x.dtype)
    return np.clip(x + eps, -1.0, 1.0), y


def byzantine_update_attack(w_prev_flat: np.ndarray, rng, scale: float = 20.0):
    """Paper eq.: w_{t+1}^k <- w_t + Delta, Delta ~ N(0, scale^2 I)."""
    return w_prev_flat + rng.normal(scale=scale, size=w_prev_flat.shape).astype(
        w_prev_flat.dtype
    )


def alie_update_attack(benign_updates: np.ndarray, z_max: float = 1.2):
    """Colluding stealth attack: all attackers send mean - z_max * std of the
    *benign* updates (coordinate-wise), staying within the benign spread.

    Default ``z_max`` matches ``alie_update_tree`` / ``EngineConfig`` (1.2),
    so analysis-script numbers agree with engine runs."""
    mu = benign_updates.mean(axis=0)
    sd = benign_updates.std(axis=0)
    return mu - z_max * sd


def ipm_update_attack(benign_updates: np.ndarray, eps: float = 0.5):
    """Inner-product manipulation (Xie et al. 2019a, cited by the paper):
    colluders send −eps × mean(benign) — a small negatively-aligned update
    that flips the aggregate's descent direction without a large norm."""
    return -eps * benign_updates.mean(axis=0)


def sign_flip_update_attack(own_update: np.ndarray, w_prev: np.ndarray, scale: float = 3.0):
    """Reverse and amplify the client's own honest delta."""
    return w_prev - scale * (own_update - w_prev)


ATTACKS = {
    "flipping": flip_labels,
    "noisy": noisy_features,
}


# ---------------------------------------------------------------------------
# jit-able stacked-pytree transforms (the round-engine path)
#
# Proposals arrive as a pytree whose every leaf carries a leading client axis
# K.  ``bad_mask`` / ``benign_mask`` are (K,) bools; behaviour is selected by
# mask, never by Python branching over clients, so one jit call covers any
# honest/attacker split.
# ---------------------------------------------------------------------------


def _row(mask, leaf):
    """(K,) mask broadcast against a (K, ...) leaf."""
    return mask.reshape((-1,) + (1,) * (leaf.ndim - 1))


def _masked_moments(leaf, benign, cnt):
    w = _row(benign, leaf).astype(jnp.float32)
    lf = leaf.astype(jnp.float32)
    mu = jnp.sum(w * lf, axis=0) / cnt
    var = jnp.sum(w * (lf - mu[None]) ** 2, axis=0) / cnt
    return mu, var


def byzantine_update_tree(
    proposals, w_prev, bad_mask, key, *, scale: float = 20.0, client_ids=None
):
    """Bad rows <- w_t + N(0, scale^2 I).

    Noise is keyed per (leaf, client): ``fold_in(fold_in(key, leaf_index),
    client_id)``.  Because each client's perturbation depends only on its
    *original* id — never on its row position or the stacked shape — the
    segmented fused engine can compact blocked clients out of the stack and
    still draw bit-identical noise for the survivors (``client_ids`` carries
    the original ids through the compaction's index map; ``None`` means the
    identity layout ``0..K-1``, the uncompacted case)."""
    leaves, treedef = jax.tree_util.tree_flatten(proposals)
    prev = jax.tree_util.tree_leaves(w_prev)
    K = leaves[0].shape[0]
    ids = (
        jnp.arange(K, dtype=jnp.uint32)
        if client_ids is None
        else jnp.asarray(client_ids, jnp.uint32)
    )
    out = []
    for i, (l, p) in enumerate(zip(leaves, prev)):
        lkey = jax.random.fold_in(key, i)
        noise = jax.vmap(
            lambda cid: scale
            * jax.random.normal(jax.random.fold_in(lkey, cid), l.shape[1:], jnp.float32)
        )(ids)
        adv = (p.astype(jnp.float32)[None] + noise).astype(l.dtype)
        out.append(jnp.where(_row(bad_mask, l), adv, l))
    return jax.tree_util.tree_unflatten(treedef, out)


def _psum_fused(tree, axis_name):
    """``psum`` an f32 pytree as ONE flat vector: ``jax.lax.psum`` of a
    pytree binds one collective per leaf, so the leaves are raveled into a
    single buffer first (elementwise sums, so the values are unchanged)."""
    flat, unravel = ravel_pytree(tree)
    return unravel(jax.lax.psum(flat, axis_name))


def alie_update_tree(
    proposals, bad_mask, benign_mask, *, z_max: float = 1.2, axis_name=None
):
    """Bad rows <- mean − z_max·std of the *benign* rows (coordinate-wise).

    With ``axis_name`` the proposal stack is client-sharded over that mesh
    axis and the benign moments are made global with ONE fused collective:
    the per-leaf partial sums, partial sums of squares, and the benign count
    travel together in a single ``psum`` of one flat f32 vector
    (:func:`_psum_fused`, one collective per attack), then the variance is
    assembled in the one-pass form ``E[x²] − E[x]²`` (clamped at 0 against
    cancellation).  The unsharded path keeps the original two-pass
    computation bit for bit."""
    if axis_name is None:
        cnt = jnp.maximum(jnp.sum(benign_mask.astype(jnp.float32)), 1.0)

        def leaf(l):
            mu, var = _masked_moments(l, benign_mask, cnt)
            adv = (mu - z_max * jnp.sqrt(var)).astype(l.dtype)
            return jnp.where(_row(bad_mask, l), adv[None], l)

        return jax.tree_util.tree_map(leaf, proposals)

    leaves, treedef = jax.tree_util.tree_flatten(proposals)
    s1 = []
    s2 = []
    for l in leaves:
        w = _row(benign_mask, l).astype(jnp.float32)
        lf = l.astype(jnp.float32)
        s1.append(jnp.sum(w * lf, axis=0))
        s2.append(jnp.sum(w * lf * lf, axis=0))
    cnt_local = jnp.sum(benign_mask.astype(jnp.float32))
    s1, s2, cnt = _psum_fused((s1, s2, cnt_local), axis_name)
    cnt = jnp.maximum(cnt, 1.0)
    out = []
    for l, a, b in zip(leaves, s1, s2):
        mu = a / cnt
        var = jnp.maximum(b / cnt - mu * mu, 0.0)
        adv = (mu - z_max * jnp.sqrt(var)).astype(l.dtype)
        out.append(jnp.where(_row(bad_mask, l), adv[None], l))
    return jax.tree_util.tree_unflatten(treedef, out)


def ipm_update_tree(
    proposals, bad_mask, benign_mask, *, eps: float = 0.5, axis_name=None
):
    """Bad rows <- −eps · mean(benign rows): inner-product manipulation.

    With ``axis_name`` the benign mean goes global through ONE fused
    ``psum`` of (per-leaf partial sums, benign count) — see
    :func:`alie_update_tree`."""
    if axis_name is None:
        cnt = jnp.maximum(jnp.sum(benign_mask.astype(jnp.float32)), 1.0)

        def leaf(l):
            w = _row(benign_mask, l).astype(jnp.float32)
            mu = jnp.sum(w * l.astype(jnp.float32), axis=0) / cnt
            return jnp.where(_row(bad_mask, l), (-eps * mu).astype(l.dtype)[None], l)

        return jax.tree_util.tree_map(leaf, proposals)

    leaves, treedef = jax.tree_util.tree_flatten(proposals)
    s1 = [
        jnp.sum(_row(benign_mask, l).astype(jnp.float32) * l.astype(jnp.float32), axis=0)
        for l in leaves
    ]
    cnt_local = jnp.sum(benign_mask.astype(jnp.float32))
    s1, cnt = _psum_fused((s1, cnt_local), axis_name)
    cnt = jnp.maximum(cnt, 1.0)
    out = [
        jnp.where(_row(bad_mask, l), (-eps * (a / cnt)).astype(l.dtype)[None], l)
        for l, a in zip(leaves, s1)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


def apply_update_attack(
    scenario: str,
    proposals,
    w_prev,
    bad_mask,
    benign_mask,
    key,
    *,
    byzantine_scale: float = 20.0,
    z_max: float = 1.2,
    eps: float = 0.5,
    client_ids=None,
    axis_name=None,
):
    """Static dispatch (scenario is a Python string, resolved at trace time)
    of the update-level attacks on stacked proposals.  Data-level scenarios
    (clean/flipping/noisy) poison shards before training and are a no-op here.
    ``client_ids`` maps rows to original client ids when the stack has been
    compacted (byzantine noise is keyed per client id; alie/ipm draw no RNG
    and their benign-masked moments are compaction-invariant).  ``axis_name``
    names the mesh axis when the stack is client-sharded: byzantine is
    row-local (no communication), alie/ipm globalize their benign moments
    with one fused psum each.
    """
    if scenario == "byzantine":
        return byzantine_update_tree(
            proposals, w_prev, bad_mask, key, scale=byzantine_scale,
            client_ids=client_ids,
        )
    if scenario == "alie":
        return alie_update_tree(
            proposals, bad_mask, benign_mask, z_max=z_max, axis_name=axis_name
        )
    if scenario == "ipm":
        return ipm_update_tree(
            proposals, bad_mask, benign_mask, eps=eps, axis_name=axis_name
        )
    return proposals
