"""Fused AFA screening kernel (kernels/afa_screen.py): bit-identity against
the jnp gram oracle, launch-count structure, tiled-route agreement, and
fused-trajectory identity through the registry dispatch.

The strongest contract in the kernel package: on the interpret route the
fused kernel runs on the EXACT unpadded shapes with the same primitives as
``afa_aggregate(variant="gram", use_kernels=False)``, so every output —
aggregate, good_mask, rounds, similarities — must be BIT-identical (f32),
not merely allclose.  The compiled d-tiled two-pass geometry accumulates the
gram in a different block order, so it is gated at allclose.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # the hypothesis property is extra depth; the rest must run regardless
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.afa import SIM_TIE_RTOL, AFAConfig, afa_aggregate
from repro.kernels import afa_screen
from repro.kernels.afa_screen import SIM_TIE_RTOL as KERNEL_SIM_TIE_RTOL

RNG = np.random.default_rng(7)


def _workload(rng, K, d, outlier_rows=1):
    u = jnp.asarray(rng.normal(size=(K, d)).astype(np.float32))
    if outlier_rows:
        u = u.at[:outlier_rows].multiply(30.0)  # make the screening loop iterate
    n_k = jnp.asarray(rng.integers(1, 40, size=K).astype(np.float32))
    p_k = jnp.asarray(rng.uniform(0.1, 0.9, size=K).astype(np.float32))
    return u, n_k, p_k


def _assert_matches_reference(u, n_k, p_k, mask0, cfg, *, bitwise):
    ref = afa_aggregate(
        u, n_k, p_k, mask0=mask0, config=cfg._replace(use_kernels=False)
    )
    agg, good, rounds, sims = afa_screen(
        u, p_k * n_k, jnp.ones(u.shape[0], bool) if mask0 is None else mask0,
        xi0=cfg.xi0, delta_xi=cfg.delta_xi, max_rounds=cfg.max_rounds,
        ddof=cfg.ddof, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(good), np.asarray(ref.good_mask))
    assert int(rounds) == int(ref.rounds)
    if bitwise:
        np.testing.assert_array_equal(np.asarray(agg), np.asarray(ref.aggregate))
        np.testing.assert_array_equal(np.asarray(sims), np.asarray(ref.similarities))
    else:
        np.testing.assert_allclose(
            np.asarray(agg), np.asarray(ref.aggregate), rtol=1e-5, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(sims), np.asarray(ref.similarities), rtol=1e-5, atol=1e-5
        )
    return ref


# ----------------------------- bit-identity ---------------------------------


def _bit_identity_case(K, d, max_rounds, live_frac, seed):
    rng = np.random.default_rng(seed)
    u, n_k, p_k = _workload(rng, K, d)
    mask0 = jnp.asarray(rng.uniform(size=K) < live_frac)
    if int(mask0.sum()) < 2:
        mask0 = jnp.ones((K,), bool)
    cfg = AFAConfig(variant="gram", max_rounds=max_rounds)
    _assert_matches_reference(u, n_k, p_k, mask0, cfg, bitwise=True)


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(
        K=st.integers(3, 21),       # covers non-multiple-of-8 sublane edges
        d=st.integers(1, 300),
        max_rounds=st.sampled_from([0, 1, 8]),
        live_frac=st.floats(0.3, 1.0),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_fused_kernel_bit_identical_property(K, d, max_rounds, live_frac, seed):
        """Hypothesis property: the fused screening kernel is bit-identical
        (f32) to afa_aggregate(variant="gram", use_kernels=False) across
        random masks, reputations, max_rounds in {0, 1, 8}, and ragged K (no
        8-row padding on the interpret route — padding a matvec is NOT
        bitwise-exact)."""
        _bit_identity_case(K, d, max_rounds, live_frac, seed)


@pytest.mark.parametrize("K,d,max_rounds,live_frac,seed", [
    (7, 33, 8, 1.0, 0),     # ragged K, full participation
    (13, 129, 8, 0.6, 1),   # ragged K + random mask
    (16, 64, 0, 0.8, 2),    # max_rounds=0: round-0 sims path
    (9, 200, 1, 0.5, 3),    # single screening round
])
def test_fused_kernel_bit_identical_pinned(K, d, max_rounds, live_frac, seed):
    """Pinned-seed slice of the property above — runs even without
    hypothesis (the CI kernel-parity job and bare containers)."""
    _bit_identity_case(K, d, max_rounds, live_frac, seed)


def test_fused_route_through_afa_aggregate_bitwise():
    """The wired route: variant="gram" + use_kernels="interpret" (default
    kernel_launch="fused") equals the jnp reference bit for bit."""
    u, n_k, p_k = _workload(RNG, 13, 129)
    ref = afa_aggregate(u, n_k, p_k, config=AFAConfig(variant="gram"))
    fused = afa_aggregate(
        u, n_k, p_k,
        config=AFAConfig(variant="gram", use_kernels="interpret"),
    )
    np.testing.assert_array_equal(
        np.asarray(fused.aggregate), np.asarray(ref.aggregate)
    )
    np.testing.assert_array_equal(
        np.asarray(fused.good_mask), np.asarray(ref.good_mask)
    )
    np.testing.assert_array_equal(
        np.asarray(fused.similarities), np.asarray(ref.similarities)
    )
    assert int(fused.rounds) == int(ref.rounds)


def test_fused_kernel_ddof_and_thresholds():
    """Non-default screening knobs thread through to the in-kernel loop."""
    u, n_k, p_k = _workload(RNG, 12, 80, outlier_rows=2)
    cfg = AFAConfig(variant="gram", xi0=1.0, delta_xi=0.25, max_rounds=6, ddof=1)
    ref = _assert_matches_reference(u, n_k, p_k, None, cfg, bitwise=True)
    assert int(ref.rounds) >= 1  # the planted outliers force screening work


@pytest.mark.parametrize("variant", ["gram", "iterative"])
@pytest.mark.parametrize("mode", ["interpret", "jnp"])
def test_rounding_ties_are_kept(variant, mode):
    """Identical client rows give similarities a few f32 roundings apart;
    every route treats them as ties (``SIM_TIE_RTOL``) and keeps them all."""
    assert KERNEL_SIM_TIE_RTOL == SIM_TIE_RTOL  # the kernel mirrors core
    r = np.random.default_rng(14336)
    w = r.normal(size=(93,)).astype(np.float32)
    u = jnp.asarray(np.tile(w, (9, 1)))
    n_k = jnp.asarray(r.uniform(1, 50, 9).astype(np.float32))
    p_k = jnp.asarray(r.uniform(0.2, 1.0, 9).astype(np.float32))
    res = afa_aggregate(u, n_k, p_k, config=AFAConfig(variant=variant, use_kernels=mode))
    assert np.asarray(res.good_mask).all()
    np.testing.assert_allclose(np.asarray(res.aggregate), w, rtol=1e-5, atol=1e-6)


# --------------------------- launch structure --------------------------------


def test_one_pallas_launch_per_aggregation():
    """The tentpole claim, verified on the jaxpr via the repro.analysis
    launch-count API: the fused route binds EXACTLY one pallas_call; the
    chained route at least two (gram + weighted-sum); the jnp route none."""
    from repro.analysis import LaunchBudget
    from repro.analysis.launches import assert_launch_budget

    u, n_k, p_k = _workload(RNG, 10, 64)

    def route(kernel_launch):
        cfg = AFAConfig(variant="gram", use_kernels="interpret",
                        kernel_launch=kernel_launch)
        return lambda u_, n_, p_: afa_aggregate(u_, n_, p_, config=cfg)

    assert_launch_budget(route("fused"), u, n_k, p_k,
                         budget=LaunchBudget(exact=1), target="afa[fused]")
    assert_launch_budget(route("chained"), u, n_k, p_k,
                         budget=LaunchBudget(min=2), target="afa[chained]")
    cfg_jnp = AFAConfig(variant="gram", use_kernels=False)
    assert_launch_budget(
        lambda u_, n_, p_: afa_aggregate(u_, n_, p_, config=cfg_jnp),
        u, n_k, p_k, budget=LaunchBudget(exact=0), target="afa[jnp]")


# ------------------------- two-pass tiled geometry ---------------------------


@pytest.mark.parametrize("K,d,block_d", [
    (16, 512, 128),
    (9, 384, 128),    # ragged K: row-pad path of the compiled geometry
    (24, 256, 256),   # single d block but still the two-pass grid
])
def test_two_pass_tiled_route_matches_reference(K, d, block_d):
    """Forcing block_d exercises the compiled TPU geometry (grid (2, nb),
    resident gram/norms/weights blocks) under the interpreter.  Different
    d-block accumulation order -> allclose, not bitwise; the mask and round
    count are discrete and must still be exact."""
    rng = np.random.default_rng(K * 1000 + d)
    u, n_k, p_k = _workload(rng, K, d)
    mask0 = jnp.asarray(rng.uniform(size=K) < 0.8)
    if int(mask0.sum()) < 2:
        mask0 = jnp.ones((K,), bool)
    ref = afa_aggregate(
        u, n_k, p_k, mask0=mask0, config=AFAConfig(variant="gram")
    )
    agg, good, rounds, sims = afa_screen(
        u, p_k * n_k, mask0, xi0=2.0, delta_xi=0.5, max_rounds=8,
        block_d=block_d, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(good), np.asarray(ref.good_mask))
    assert int(rounds) == int(ref.rounds)
    np.testing.assert_allclose(
        np.asarray(agg), np.asarray(ref.aggregate), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(sims), np.asarray(ref.similarities), rtol=1e-4, atol=1e-4
    )


# ------------------------ dispatch-level trajectory --------------------------


def test_fused_trajectory_identity_through_dispatch_rule():
    """Multi-round AFA trajectory through dispatch_rule: reputation-weighted
    re-aggregation with the fused kernel stays bit-identical to the jnp
    route round after round (mask and reputation feed back, so one diverging
    bit would compound)."""
    from repro.core import RuleOptions, dispatch_rule

    K, d, T = 10, 50, 5
    rng = np.random.default_rng(11)
    n_k = jnp.asarray(rng.integers(5, 50, size=K).astype(np.float32))
    cfg_ref = AFAConfig(variant="gram", use_kernels=False)
    cfg_fused = AFAConfig(variant="gram", use_kernels="interpret")
    p_ref = p_fused = jnp.full((K,), 0.5, jnp.float32)
    m_ref = m_fused = jnp.ones((K,), bool)
    for t in range(T):
        u = jnp.asarray(rng.normal(size=(K, d)).astype(np.float32))
        u = u.at[0].multiply(20.0 + t)
        r_ref = dispatch_rule("afa", u, n_k, p_ref, m_ref,
                              RuleOptions(afa=cfg_ref))
        r_fused = dispatch_rule("afa", u, n_k, p_fused, m_fused,
                                RuleOptions(afa=cfg_fused))
        np.testing.assert_array_equal(
            np.asarray(r_fused.aggregate), np.asarray(r_ref.aggregate),
            err_msg=f"trajectory diverged at round {t}",
        )
        np.testing.assert_array_equal(
            np.asarray(r_fused.good_mask), np.asarray(r_ref.good_mask)
        )
        np.testing.assert_array_equal(
            np.asarray(r_fused.similarities), np.asarray(r_ref.similarities)
        )
        # Beta-posterior style reputation feedback: the next round's p_k
        # depends on this round's mask, so divergence would compound
        p_ref = jnp.where(r_ref.good_mask, p_ref * 1.1, p_ref * 0.5)
        p_fused = jnp.where(r_fused.good_mask, p_fused * 1.1, p_fused * 0.5)
        m_ref = r_ref.good_mask
        m_fused = r_fused.good_mask


def test_afa_config_rejects_bogus_kernel_launch_and_variant():
    """Anything but the exact mode strings raises instead of silently
    falling through to the chained / iterative route (which would skew
    fused-vs-chained benchmarks without a whisper)."""
    u, n_k, p_k = _workload(RNG, 6, 40)
    for launch in ("Fused", "chain", "", "FUSED"):
        with pytest.raises(ValueError, match="kernel_launch"):
            afa_aggregate(
                u, n_k, p_k,
                config=AFAConfig(variant="gram", kernel_launch=launch),
            )
    with pytest.raises(ValueError, match="variant"):
        afa_aggregate(u, n_k, p_k, config=AFAConfig(variant="Gram"))
    from repro.core.afa import afa_aggregate_tree

    with pytest.raises(ValueError, match="variant"):
        afa_aggregate_tree(
            {"w": u}, n_k, p_k, config=AFAConfig(variant="bogus")
        )


# --------------- compiled-off-TPU (pallas-gpu) one-pass gate -----------------
#
# Triton grids are parallel, so the accumulating kernels (gram, cosine-sim,
# the fused screen) only get a single-grid-step geometry off-TPU — the whole
# operand must be one resident block.  Oversized operands must raise at
# trace time, never compile into racy accumulation or an OOMing mega-block.
# jax.eval_shape traces without materializing, so these run anywhere (the
# gate keys off the backend, not on actually having a GPU).


def test_gpu_onepass_gate_refuses_oversized_operands():
    if jax.default_backend() == "tpu":
        pytest.skip("the one-pass gate only applies to compiled off-TPU launches")
    from repro.kernels import afa_screen as afa_screen_op
    from repro.kernels import cosine_sim, gram

    big = jax.ShapeDtypeStruct((8, 1_000_000), jnp.float32)
    vec = jax.ShapeDtypeStruct((1_000_000,), jnp.float32)
    kvec = jax.ShapeDtypeStruct((8,), jnp.float32)
    kmask = jax.ShapeDtypeStruct((8,), jnp.int32)
    with pytest.raises(NotImplementedError, match="pallas-gpu"):
        jax.eval_shape(lambda u: gram(u, interpret=False), big)
    with pytest.raises(NotImplementedError, match="pallas-gpu"):
        jax.eval_shape(lambda u, w: cosine_sim(u, w, interpret=False), big, vec)
    with pytest.raises(NotImplementedError, match="pallas-gpu"):
        jax.eval_shape(
            lambda u, pn, m: afa_screen_op(
                u, pn, m, xi0=2.0, delta_xi=0.5, max_rounds=3, interpret=False
            ),
            big, kvec, kmask,
        )


def test_gpu_onepass_gate_allows_block_resident_operands():
    if jax.default_backend() == "tpu":
        pytest.skip("the one-pass gate only applies to compiled off-TPU launches")
    from repro.kernels import gram

    small = jax.ShapeDtypeStruct((8, 256), jnp.float32)
    out = jax.eval_shape(lambda u: gram(u, interpret=False), small)
    assert out.shape == (8, 8)
