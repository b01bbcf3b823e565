"""Fused-engine tests: scan-vs-eager bit-equivalence, rule coverage through
the pure server core, the vmapped seed sweep, and the padded shard stacking
the device-side batch draw depends on."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import make_mnist_like, padded_stack
from repro.fed import (
    ServerConfig,
    SimConfig,
    client_keys,
    client_keys_traced,
    run_simulation,
    run_sweep,
)
from repro.fed.simulator import first_segment


@pytest.fixture(scope="module")
def eq_data():
    return make_mnist_like(n_train=1000, n_test=300, dim=196)


def _sim(scenario, engine, rounds=5, seed=3):
    return SimConfig(
        num_clients=8, scenario=scenario, rounds=rounds, local_epochs=2,
        batch_size=100, hidden=(64, 32), dropout=True, seed=seed, engine=engine,
    )


def _run(data, scenario, engine, rule="afa", rounds=5):
    return run_simulation(
        data, _sim(scenario, engine, rounds), ServerConfig(rule=rule, num_clients=8)
    )


# --------------------- scan vs eager bit-equivalence -------------------------


@pytest.mark.parametrize("scenario", ["clean", "byzantine"])
def test_fused_scan_bit_equivalent_to_eager_rounds(eq_data, scenario):
    """The fused lax.scan and the identical round body dispatched eagerly one
    round at a time must produce the SAME per-round (test error, good_mask)
    trajectory — the scan adds no numerics of its own."""
    fused = _run(eq_data, scenario, "fused")
    eager = _run(eq_data, scenario, "fused_eager")
    np.testing.assert_array_equal(
        np.asarray(fused.test_error), np.asarray(eager.test_error)
    )
    assert len(fused.good_mask_history) == len(eager.good_mask_history)
    for gf, ge in zip(fused.good_mask_history, eager.good_mask_history):
        np.testing.assert_array_equal(np.asarray(gf), np.asarray(ge))
    np.testing.assert_array_equal(fused.blocked_round, eager.blocked_round)
    np.testing.assert_array_equal(
        np.stack(fused.similarity_history), np.stack(eager.similarity_history)
    )
    for lf, le in zip(jax.tree_util.tree_leaves(fused.params),
                      jax.tree_util.tree_leaves(eager.params)):
        np.testing.assert_array_equal(np.asarray(lf), np.asarray(le))


def test_fused_engine_trains(eq_data):
    """Error decreases over rounds; trajectory is finite throughout."""
    res = _run(eq_data, "clean", "fused", rounds=6)
    assert np.isfinite(res.test_error).all()
    assert res.test_error[-1] < res.test_error[0]


@pytest.mark.parametrize("rule", ["afa", "fa", "mkrum", "comed", "trimmed_mean"])
def test_fused_engine_serves_registry_rules(eq_data, rule):
    """The pure server core dispatches every rule family inside the scan:
    native tree form (AFA) and the in-jit flatten fallback alike."""
    res = _run(eq_data, "clean", "fused", rule=rule, rounds=3)
    assert np.isfinite(res.test_error).all()
    assert len(res.good_mask_history) == 3
    assert res.good_mask_history[0].shape == (8,)


def test_fused_matches_batched_phenomenology(eq_data):
    """Fused and batched draw different minibatch streams (device vs host
    RNG), so trajectories differ bitwise — but on the same workload both
    must land in the same regime."""
    fused = _run(eq_data, "clean", "fused", rounds=6)
    batched = _run(eq_data, "clean", "batched", rounds=6)
    assert abs(fused.test_error[-1] - batched.test_error[-1]) < 15.0


# --------------------- segmented compaction ----------------------------------


def _seg_sim(scenario, rounds=12, **kw):
    """40% byzantine at K = 10: AFA blocks 4 clients mid-run, dropping the
    bucket from 10 to 8 — real compaction, not just segmentation."""
    return SimConfig(
        num_clients=10, bad_frac=0.4, scenario=scenario, rounds=rounds,
        local_epochs=2, batch_size=100, hidden=(64, 32), dropout=True, seed=3,
        engine="fused", **kw,
    )


def _assert_same_trajectory(a, b):
    np.testing.assert_array_equal(np.asarray(a.test_error), np.asarray(b.test_error))
    good = np.stack(a.good_mask_history)
    np.testing.assert_array_equal(good, np.stack(b.good_mask_history))
    np.testing.assert_array_equal(a.blocked_round, b.blocked_round)
    # compaction drops blocked clients' rows, so their similarities are not
    # computed there: compare the clients the rule kept.  The similarity
    # dots then run over fewer rows, which may move one f32 rounding
    np.testing.assert_allclose(
        np.stack(a.similarity_history)[good], np.stack(b.similarity_history)[good],
        rtol=2e-7, atol=0,
    )
    for la, lb in zip(jax.tree_util.tree_leaves(a.params),
                      jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_segmented_compacted_bit_equals_one_shot_fused(eq_data):
    """Compaction must be a pure layout change: dropping blocked clients
    between segments (original-id-keyed RNG streams, masked-zero reductions)
    produces the SAME (test_error, good_mask, blocked) trajectory, bit for
    bit, as the one-shot full-K scan."""
    cfg = ServerConfig(rule="afa", num_clients=10)
    base = run_simulation(eq_data, _seg_sim("byzantine"), cfg)
    seg = run_simulation(
        eq_data, _seg_sim("byzantine", segment_rounds=4, compact=True), cfg
    )
    # the scenario actually engages compaction (bucket 10 -> 8)
    assert int((base.blocked_round > 0).sum()) == 4
    _assert_same_trajectory(base, seg)


def test_segmented_without_compaction_bit_equals_one_shot(eq_data):
    """Segmentation alone (compact=False keeps every row resident) is also a
    pure control-flow change — trajectories identical to the single scan."""
    cfg = ServerConfig(rule="afa", num_clients=10)
    base = run_simulation(eq_data, _seg_sim("clean", rounds=7), cfg)
    seg = run_simulation(
        eq_data, _seg_sim("clean", rounds=7, segment_rounds=3, compact=False), cfg
    )
    _assert_same_trajectory(base, seg)


def test_segmented_ragged_last_segment(eq_data):
    """T not divisible by S: the remainder segment stitches correctly."""
    cfg = ServerConfig(rule="afa", num_clients=10)
    base = run_simulation(eq_data, _seg_sim("byzantine", rounds=11), cfg)
    seg = run_simulation(
        eq_data, _seg_sim("byzantine", rounds=11, segment_rounds=5), cfg
    )
    _assert_same_trajectory(base, seg)


@pytest.mark.parametrize("rule", ["afa", "fa"])
def test_result_carries_similarities_and_final_params(eq_data, rule):
    """A fused run reports, each round, AFA's final-iteration similarities
    (kept clients score above the byzantine ones it dropped; zeros for a
    rule without them) and its final global parameters."""
    sim = _seg_sim("byzantine", rounds=4, segment_rounds=2, compact=True)
    res = run_simulation(eq_data, sim, ServerConfig(rule=rule, num_clients=10))
    sims = np.stack(res.similarity_history)
    assert sims.shape == (4, 10)
    if rule == "afa":
        good0, bad = res.good_mask_history[0], res.bad_clients
        assert not good0[bad].any()
        assert sims[0][good0].min() > sims[0][bad].max()
    else:
        assert not sims.any()
    init = jax.tree_util.tree_leaves(res.params)
    assert init and all(np.isfinite(np.asarray(leaf)).all() for leaf in init)


def test_first_segment_is_the_runs_first_program(eq_data):
    """``first_segment`` hands out the run's first segment call: compiled
    ahead of time, the call compiles nothing more, and its rounds are the
    run's first ``segment_rounds`` rounds."""
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_, **__: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None
    )
    cfg = ServerConfig(rule="afa", num_clients=10)
    sim = _seg_sim("byzantine", segment_rounds=4, compact=True)
    seg_fn, args = first_segment(eq_data, sim, cfg)
    seg_fn.lower(*args).compile()
    n_compiled = len(compiles)
    _, _, traj = seg_fn(*args)
    assert len(compiles) == n_compiled
    run = run_simulation(eq_data, sim, cfg)
    np.testing.assert_array_equal(
        np.asarray(traj.test_error, np.float64) * 100.0, run.test_error[:4]
    )
    np.testing.assert_array_equal(
        np.asarray(traj.good_mask), np.stack(run.good_mask_history[:4])
    )


@pytest.mark.parametrize("segment_rounds", [0, 4])
def test_phase_scopes_leave_the_trajectory_bit_identical(
    eq_data, monkeypatch, segment_rounds
):
    """The round body's ``named_scope`` phases change op metadata only: the
    scoped program and the same program built with every scope a no-op give
    the same trajectory bit for bit (one-shot and segmented, compacting)."""
    from repro.fed import engine

    cfg = ServerConfig(rule="afa", num_clients=10)
    sim = _seg_sim("byzantine", segment_rounds=segment_rounds, compact=True)
    scoped = run_simulation(eq_data, sim, cfg)
    caches = (engine._make_fused_sim_cached, engine._make_fused_segment_cached)
    for cache in caches:
        cache.cache_clear()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    try:
        unscoped = run_simulation(eq_data, sim, cfg)
    finally:
        for cache in caches:
            cache.cache_clear()
    np.testing.assert_array_equal(scoped.test_error, unscoped.test_error)
    np.testing.assert_array_equal(np.stack(scoped.good_mask_history),
                                  np.stack(unscoped.good_mask_history))
    np.testing.assert_array_equal(scoped.blocked_round, unscoped.blocked_round)
    np.testing.assert_array_equal(np.stack(scoped.similarity_history),
                                  np.stack(unscoped.similarity_history))
    for a, b in zip(jax.tree_util.tree_leaves(scoped.params),
                    jax.tree_util.tree_leaves(unscoped.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------ seed sweep -----------------------------------


def test_run_sweep_vmaps_over_seeds(eq_data):
    sim = _sim("byzantine", "fused")
    sw = run_sweep(eq_data, sim, ServerConfig(rule="afa", num_clients=8), [3, 4, 5])
    assert sw.test_error.shape == (3, sim.rounds)
    assert sw.good_mask_history.shape == (3, sim.rounds, 8)
    assert sw.blocked_round.shape == (3, 8)
    assert sw.detection_rate.shape == (3,)
    assert np.isfinite(sw.test_error).all()
    # seeds differ -> trajectories differ (different init + batch streams)
    assert not np.array_equal(sw.test_error[0], sw.test_error[1])


def test_run_sweep_row_matches_single_fused_run(eq_data):
    """Sweep row for seed s == the single fused simulation with sim.seed=s
    (same shard split base seed, same init, same device RNG streams)."""
    sim = _sim("byzantine", "fused", seed=3)
    sw = run_sweep(eq_data, sim, ServerConfig(rule="afa", num_clients=8), [3])
    single = run_simulation(eq_data, sim, ServerConfig(rule="afa", num_clients=8))
    np.testing.assert_allclose(
        sw.test_error[0], np.asarray(single.test_error), rtol=0, atol=1e-4
    )
    np.testing.assert_array_equal(sw.blocked_round[0], single.blocked_round)


def test_segmented_sweep_matches_unsegmented_sweep(eq_data):
    """Union-of-live compaction across the seed axis: each seed's row of the
    segmented sweep equals the unsegmented vmapped sweep bit for bit (a
    client leaves the stack only when blocked in EVERY seed; per-seed masks
    cover the rest)."""
    cfg = ServerConfig(rule="afa", num_clients=10)
    seeds = [3, 4, 5]
    base = run_sweep(eq_data, _seg_sim("byzantine"), cfg, seeds)
    seg = run_sweep(
        eq_data, _seg_sim("byzantine", segment_rounds=4, compact=True), cfg, seeds
    )
    np.testing.assert_array_equal(base.test_error, seg.test_error)
    np.testing.assert_array_equal(base.good_mask_history, seg.good_mask_history)
    np.testing.assert_array_equal(base.blocked_round, seg.blocked_round)


def test_run_sweep_distinct_seeds_distinct_draws_and_trajectories(eq_data):
    """Property (over several seed pairs): distinct seeds must yield distinct
    device minibatch draws and distinct trajectories — guards the seed axis
    actually threading through the vmapped fused sim, unsegmented AND
    segmented+compacted.  (A dropped seed axis would silently collapse every
    sweep row onto one stream.)"""
    import jax

    from repro.fed.engine import _BATCH_STREAM

    # key-stream level: the engine's per-(seed, round, client) batch keys
    # (fold_in(fold_in(PRNGKey(seed), BATCH_STREAM), rnd * K + id)) yield
    # distinct index draws for distinct seeds
    def draw(seed, rnd, cid, K=10):
        bkey = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), _BATCH_STREAM),
            rnd * K + cid,
        )
        return np.asarray(jax.random.randint(bkey, (4, 8), 0, 100))

    for s_a, s_b in [(0, 1), (3, 4), (7, 1000)]:
        for rnd in (0, 5):
            assert not np.array_equal(draw(s_a, rnd, 2), draw(s_b, rnd, 2))

    # simulation level, through compaction: rows differ pairwise
    cfg = ServerConfig(rule="afa", num_clients=10)
    sw = run_sweep(
        eq_data, _seg_sim("byzantine", segment_rounds=4, compact=True), cfg,
        [3, 4, 5],
    )
    for i in range(3):
        for j in range(i + 1, 3):
            assert not np.array_equal(sw.test_error[i], sw.test_error[j])


# --------------------------- padded stacking ---------------------------------


def test_padded_stack_geometry_and_content():
    rng = np.random.default_rng(0)
    shards = [
        (rng.normal(size=(n, 4)).astype(np.float32), rng.integers(0, 3, n))
        for n in (5, 3, 7)
    ]
    x, y, lengths = padded_stack(shards)
    assert x.shape == (3, 7, 4) and y.shape == (3, 7)
    np.testing.assert_array_equal(lengths, [5, 3, 7])
    for k, (xs, ys) in enumerate(shards):
        np.testing.assert_array_equal(x[k, : len(xs)], xs)
        np.testing.assert_array_equal(y[k, : len(ys)], ys)
        assert (x[k, len(xs):] == 0).all()  # pad rows zeroed, never sampled


def test_client_keys_traced_matches_host_version():
    """The id-subset key builder must reproduce rows of the full key stack:
    this is the compaction invariant — a surviving client keeps its exact
    key stream no matter which row it is compacted into."""
    for rnd in (0, 1, 17):
        full = np.asarray(client_keys(11, rnd, 6))
        np.testing.assert_array_equal(
            np.asarray(client_keys_traced(11, jnp.int32(rnd), jnp.arange(6, dtype=jnp.uint32), 6)),
            full,
        )
        ids = jnp.asarray([1, 3, 5], jnp.uint32)
        np.testing.assert_array_equal(
            np.asarray(client_keys_traced(11, jnp.int32(rnd), ids, 6)),
            full[[1, 3, 5]],
        )
