"""Blocking bookkeeping contracts (paper Table 2): ``rounds_blocked`` is
1-indexed, ``detection_rate`` counts clients blocked in round 1, and a
simulated byzantine run blocks bad clients in exactly
``min_rounds_to_block()`` rounds."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mark_blocked_round, min_rounds_to_block
from repro.data import make_mnist_like
from repro.fed import (
    ServerConfig,
    SimConfig,
    detection_stats,
    init_server_state,
    run,
)


# ------------------------- unit: 1-indexed bookkeeping -----------------------


def test_mark_blocked_round_is_one_indexed():
    """A client blocked while absorbing round index 0 (the FIRST round) is
    recorded as blocked in round 1."""
    rb = jnp.full((3,), -1, jnp.int32)
    before = jnp.asarray([False, False, False])
    after = jnp.asarray([True, False, False])
    out = mark_blocked_round(rb, before, after, jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(out), [1, -1, -1])


def test_mark_blocked_round_never_overwrites():
    """The recorded round is the round of FIRST blocking; staying blocked in
    later rounds must not move it."""
    rb = jnp.asarray([2, -1, -1], jnp.int32)
    before = jnp.asarray([True, False, False])
    after = jnp.asarray([True, True, False])
    out = mark_blocked_round(rb, before, after, jnp.int32(6))
    np.testing.assert_array_equal(np.asarray(out), [2, 7, -1])


def test_init_server_state_starts_unblocked():
    st = init_server_state(4)
    np.testing.assert_array_equal(np.asarray(st.rounds_blocked), [-1] * 4)
    assert not np.asarray(st.reputation.blocked).any()
    assert int(st.round) == 0


# ---------------------- unit: detection-rate semantics -----------------------


def test_detection_rate_counts_round_one_blocks():
    """blocked_round == 1 (blocked during the very first round) must count as
    detected — the 1-indexed convention leaves 0 unused, so `> 0` is the
    detected predicate."""
    rate, mean_rounds = detection_stats(np.asarray([1, -1]), np.asarray([0, 1]))
    assert rate == 0.5
    assert mean_rounds == 1.0


def test_detection_stats_edge_cases():
    rate, mean_rounds = detection_stats(np.asarray([-1, -1, 5]), np.asarray([]))
    assert np.isnan(rate) and np.isnan(mean_rounds)
    rate, mean_rounds = detection_stats(np.asarray([-1, -1]), np.asarray([0, 1]))
    assert rate == 0.0 and np.isnan(mean_rounds)
    rate, mean_rounds = detection_stats(np.asarray([3, 5, -1]), np.asarray([0, 1]))
    assert rate == 1.0 and mean_rounds == 4.0


# -------------- integration: Table 2 minimum rounds to block -----------------


@pytest.mark.parametrize(
    "segment_rounds",
    [pytest.param(0, id="fused"), pytest.param(4, id="fused_segmented")],
)
def test_byzantine_clients_block_in_minimum_rounds(segment_rounds):
    """With w_t + N(0, 20^2 I) updates AFA flags bad clients every round from
    round 1, so each is blocked in exactly the prior's minimum number of
    observations (paper Table 2) — and blocked_round is 1-indexed, so the
    value IS that count.  Run one-shot and segmented, compacting the blocked
    clients out between segments."""
    data = make_mnist_like(n_train=2000, n_test=400, dim=784)
    sim = SimConfig(
        num_clients=10, scenario="byzantine", rounds=8, local_epochs=2,
        batch_size=100, hidden=(512, 256), dropout=False, seed=3,
        segment_rounds=segment_rounds, compact=True,
    )
    res = run(None, sim, ServerConfig(rule="afa", num_clients=10), data=data)
    n_min = min_rounds_to_block()
    assert res.detection_rate == 1.0
    np.testing.assert_array_equal(
        res.blocked_round[res.bad_clients], [n_min] * len(res.bad_clients)
    )
    assert res.mean_rounds_to_block == float(n_min)
    # good clients never blocked
    good = np.setdiff1d(np.arange(10), res.bad_clients)
    np.testing.assert_array_equal(res.blocked_round[good], [-1] * len(good))
