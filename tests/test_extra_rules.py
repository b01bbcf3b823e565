"""Tests for the beyond-paper aggregation rules, attacks, and the Pallas
kernel route through the server."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.attacks import alie_update_attack, ipm_update_attack, sign_flip_update_attack
from repro.core import (
    centered_clip_aggregate,
    fa_aggregate,
    geometric_median_aggregate,
    zeno_aggregate,
)
from repro.fed import FedServer, ServerConfig

RNG = np.random.default_rng(5)


def _updates(K=10, d=64, n_bad=3, scale=30.0):
    base = RNG.normal(size=(d,)).astype(np.float32)
    U = base[None] + 0.05 * RNG.normal(size=(K, d)).astype(np.float32)
    U[:n_bad] = scale * RNG.normal(size=(n_bad, d)).astype(np.float32)
    return jnp.asarray(U), base


def test_geometric_median_robust_to_outliers():
    U, base = _updates()
    gm = np.asarray(geometric_median_aggregate(U).aggregate)
    fa = np.asarray(fa_aggregate(U, jnp.ones(10)).aggregate)
    assert np.linalg.norm(gm - base) < 0.2 * np.linalg.norm(fa - base)


def test_geometric_median_clean_is_near_mean():
    U, base = _updates(n_bad=0)
    gm = np.asarray(geometric_median_aggregate(U).aggregate)
    mean = np.asarray(U).mean(0)
    assert np.linalg.norm(gm - mean) < 0.1 * np.linalg.norm(mean)


def test_centered_clip_robust_to_outliers():
    U, base = _updates(scale=100.0)
    cc = np.asarray(centered_clip_aggregate(U, clip_tau=5.0).aggregate)
    fa = np.asarray(fa_aggregate(U, jnp.ones(10)).aggregate)
    assert np.linalg.norm(cc - base) < 0.2 * np.linalg.norm(fa - base)


def test_zeno_keeps_low_loss_updates():
    d = 32
    target = RNG.normal(size=(d,)).astype(np.float32)

    def loss(w):
        return jnp.sum((w - jnp.asarray(target)) ** 2)

    good = target[None] + 0.1 * RNG.normal(size=(7, d)).astype(np.float32)
    bad = 10 * RNG.normal(size=(3, d)).astype(np.float32)
    U = jnp.asarray(np.concatenate([bad, good]))
    out = zeno_aggregate(
        U, loss_fn=loss, w_prev=jnp.zeros((d,)), num_keep=7
    )
    keep = np.asarray(out.good_mask)
    assert not keep[:3].any() and keep[3:].all()


def test_ipm_attack_flips_mean_direction():
    benign = np.ones((7, 16), np.float32) + 0.01 * RNG.normal(size=(7, 16)).astype(np.float32)
    adv = ipm_update_attack(benign, eps=0.5)
    assert float(adv @ benign.mean(0)) < 0


def test_sign_flip_reverses_delta():
    w_prev = np.zeros(8, np.float32)
    own = np.ones(8, np.float32)
    out = sign_flip_update_attack(own, w_prev, scale=3.0)
    np.testing.assert_allclose(out, -3.0 * np.ones(8))


def test_alie_stays_within_spread():
    benign = RNG.normal(size=(8, 32)).astype(np.float32)
    adv = alie_update_attack(benign, z_max=1.0)
    lo = benign.mean(0) - 3 * benign.std(0)
    assert (adv > lo).all()


@pytest.mark.parametrize("rule", ["geomed", "centered_clip"])
def test_server_dispatch_extra_rules(rule):
    U, base = _updates()
    server = FedServer(ServerConfig(rule=rule, num_clients=10))
    agg, info = server.aggregate(U, np.ones(10, np.float32), np.arange(10))
    assert np.linalg.norm(np.asarray(agg) - base) < 2.0


def test_server_comed_kernel_route_matches_reference():
    U, _ = _updates(n_bad=0)
    n = np.ones(10, np.float32)
    a1, _ = FedServer(ServerConfig(rule="comed", num_clients=10)).aggregate(U, n, np.arange(10))
    a2, _ = FedServer(ServerConfig(rule="comed", num_clients=10, use_kernels=True)).aggregate(U, n, np.arange(10))
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), rtol=1e-5, atol=1e-6)


def test_ipm_scenario_in_simulator():
    from repro.data import make_mnist_like
    from repro.fed import SimConfig, run

    data = make_mnist_like(n_train=1500, n_test=400, dim=196)
    sim = SimConfig(num_clients=10, scenario="ipm", rounds=5, local_epochs=2,
                    batch_size=100, hidden=(64, 32), dropout=False)
    res = run(None, sim, ServerConfig(rule="afa", num_clients=10), data=data)
    assert np.isfinite(res.test_error[-1])
