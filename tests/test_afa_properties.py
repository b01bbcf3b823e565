"""Property-based tests (hypothesis) for the system's core invariants."""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed (pip install .[test])")
from hypothesis import example, given, settings, strategies as st

from repro.core import (
    afa_aggregate,
    comed_aggregate,
    fa_aggregate,
    init_reputation,
    update_reputation,
    p_good,
)


def _mk_updates(seed, K, d, n_bad, bad_scale):
    r = np.random.default_rng(seed)
    base = r.normal(size=(d,)).astype(np.float32)
    U = base[None] + 0.05 * r.normal(size=(K, d)).astype(np.float32)
    if n_bad:
        U[:n_bad] = bad_scale * r.normal(size=(n_bad, d)).astype(np.float32)
    return U


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    K=st.integers(4, 16),
    d=st.integers(8, 256),
)
def test_afa_permutation_equivariant(seed, K, d):
    """Permuting clients permutes the good mask and leaves the aggregate
    unchanged (no positional bias in Algorithm 1)."""
    r = np.random.default_rng(seed)
    U = _mk_updates(seed, K, d, n_bad=K // 4, bad_scale=20.0)
    n = jnp.asarray(r.uniform(10, 100, K).astype(np.float32))
    p = jnp.asarray(r.uniform(0.3, 0.9, K).astype(np.float32))
    perm = r.permutation(K)
    a = afa_aggregate(jnp.asarray(U), n, p)
    b = afa_aggregate(jnp.asarray(U[perm]), n[perm], p[perm])
    np.testing.assert_array_equal(np.asarray(a.good_mask)[perm], np.asarray(b.good_mask))
    np.testing.assert_allclose(np.asarray(a.aggregate), np.asarray(b.aggregate), rtol=1e-4, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), K=st.integers(3, 12), d=st.integers(4, 128))
# similarities one f32 rounding apart: a tie, not an outlier (SIM_TIE_RTOL)
@example(seed=14336, K=9, d=93)
def test_afa_identical_updates_fixed_point(seed, K, d):
    """If every client sends the same w, the aggregate IS w and all keep."""
    r = np.random.default_rng(seed)
    w = r.normal(size=(d,)).astype(np.float32)
    U = jnp.asarray(np.tile(w, (K, 1)))
    n = jnp.asarray(r.uniform(1, 50, K).astype(np.float32))
    p = jnp.asarray(r.uniform(0.2, 1.0, K).astype(np.float32))
    res = afa_aggregate(U, n, p)
    assert np.asarray(res.good_mask).all()
    np.testing.assert_allclose(np.asarray(res.aggregate), w, rtol=1e-5, atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), K=st.integers(7, 20))
def test_afa_aggregate_within_good_hull(seed, K):
    """The aggregate is a convex combination of kept updates: each coordinate
    lies within [min, max] of the kept rows."""
    d = 64
    U = _mk_updates(seed, K, d, n_bad=K // 3, bad_scale=30.0)
    n = jnp.ones((K,), jnp.float32)
    p = jnp.full((K,), 0.5, jnp.float32)
    res = afa_aggregate(jnp.asarray(U), n, p)
    kept = U[np.asarray(res.good_mask)]
    agg = np.asarray(res.aggregate)
    assert (agg <= kept.max(0) + 1e-4).all()
    assert (agg >= kept.min(0) - 1e-4).all()


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_comed_bounded_by_extremes(seed):
    r = np.random.default_rng(seed)
    U = jnp.asarray(r.normal(size=(9, 50)).astype(np.float32))
    med = np.asarray(comed_aggregate(U).aggregate)
    assert (med <= np.asarray(U).max(0)).all() and (med >= np.asarray(U).min(0)).all()


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    K=st.integers(2, 20),
    rounds=st.integers(1, 12),
)
def test_reputation_counts_conserved(seed, K, rounds):
    """alpha+beta grows by exactly one per participating unblocked round, and
    p_good stays in (0, 1)."""
    r = np.random.default_rng(seed)
    st_ = init_reputation(K)
    total0 = np.asarray(st_.alpha + st_.beta)
    expected = total0.copy()
    for _ in range(rounds):
        good = jnp.asarray(r.random(K) < 0.7)
        part = jnp.asarray(r.random(K) < 0.8)
        active = np.asarray(part & ~st_.blocked)
        st_ = update_reputation(st_, good, part)
        expected += active
        pg = np.asarray(p_good(st_))
        assert ((pg > 0) & (pg < 1)).all()
    np.testing.assert_allclose(np.asarray(st_.alpha + st_.beta), expected)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), K=st.integers(4, 12))
def test_fa_weighted_mean_invariants(seed, K):
    """FA with equal n == plain mean; with one-hot n == that client."""
    r = np.random.default_rng(seed)
    U = jnp.asarray(r.normal(size=(K, 32)).astype(np.float32))
    eq = fa_aggregate(U, jnp.ones((K,)))
    np.testing.assert_allclose(np.asarray(eq.aggregate), np.asarray(U).mean(0), rtol=1e-5, atol=1e-6)
    onehot = jnp.zeros((K,)).at[2].set(1.0)
    solo = fa_aggregate(U, onehot)
    np.testing.assert_allclose(np.asarray(solo.aggregate), np.asarray(U)[2], rtol=1e-5, atol=1e-6)
