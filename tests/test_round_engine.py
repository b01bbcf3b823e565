"""Round-engine tests: engine selection, the client key scheme, registry
dispatch coverage (every rule in RULES reachable from ServerConfig.rule, in
both proposal layouts), and the stacked-pytree attack transforms."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attacks import (
    alie_update_attack,
    alie_update_tree,
    byzantine_update_tree,
    ipm_update_tree,
)
from repro.core import RULES, RuleOptions, dispatch_rule_tree
from repro.data import make_mnist_like
from repro.fed import FedServer, ServerConfig, SimConfig, run

RNG = np.random.default_rng(7)


# ------------------------------ engine selection ------------------------------


@pytest.mark.parametrize("engine", ["batched", "looped", "warp"])
def test_unknown_engine_rejected(engine):
    """Only the fused scan and its eager reference remain: a retired or
    misspelled engine fails loudly instead of running another one."""
    data = make_mnist_like(n_train=200, n_test=50, dim=16)
    sim = SimConfig(num_clients=4, rounds=1, local_epochs=1, batch_size=50,
                    hidden=(8,), engine=engine)
    with pytest.raises(ValueError, match="unknown engine .*fused \\| fused_eager"):
        run(None, sim, ServerConfig(rule="afa", num_clients=4), data=data)


# --------------------------- client key scheme -------------------------------


def test_client_keys_injective_across_rounds_at_large_k():
    """Regression: the old ``PRNGKey(round * 1000 + k)`` collided whenever
    K >= 1000 (round r, client 1000 == round r+1, client 0), silently giving
    two different clients identical dropout streams.  The shared scheme
    ``fold_in(fold_in(PRNGKey(seed), CLIENT_STREAM), round * K + k)`` is
    injective over (round, client)."""
    from repro.fed import client_keys

    K = 1001
    keys = np.concatenate(
        [np.asarray(client_keys(0, rnd, K)) for rnd in range(3)]
    )
    assert len(np.unique(keys, axis=0)) == len(keys)


def test_client_keys_disjoint_from_attack_stream():
    """Client keys live under their own fold_in stream — none of them equals
    an attack-noise key (the old raw-PRNGKey scheme had no such separation)."""
    from repro.fed import attack_key, client_keys

    K, rounds = 64, 16
    ck = np.concatenate([np.asarray(client_keys(5, r, K)) for r in range(rounds)])
    ak = np.stack([np.asarray(attack_key(5, r)) for r in range(rounds)])
    ck_set = {tuple(row) for row in ck}
    assert not any(tuple(row) in ck_set for row in ak)


def test_client_keys_depend_on_experiment_seed():
    """The old scheme ignored the experiment seed entirely; now each seed
    draws its own dropout streams (what the seed sweep varies)."""
    from repro.fed import client_keys

    a = np.asarray(client_keys(0, 2, 8))
    b = np.asarray(client_keys(1, 2, 8))
    assert not np.array_equal(a, b)


# --------------------------- registry dispatch -------------------------------


def _updates(K=10, d=48):
    base = RNG.normal(size=(d,)).astype(np.float32)
    U = base[None] + 0.05 * RNG.normal(size=(K, d)).astype(np.float32)
    return jnp.asarray(U)


@pytest.mark.parametrize("rule", sorted(RULES))
def test_every_rule_reachable_from_server_config(rule):
    K = 10
    U = _updates(K)
    server = FedServer(ServerConfig(rule=rule, num_clients=K))
    agg, info = server.aggregate(U, np.ones(K, np.float32), np.arange(K))
    assert np.isfinite(np.asarray(agg)).all()
    assert info["good_mask"].shape == (K,)


@pytest.mark.parametrize("rule", sorted(RULES))
def test_every_rule_dispatches_tree_form(rule):
    """Tree dispatch must serve every rule: native tree form (AFA) or the
    in-jit flatten fallback — aggregate comes back with template structure."""
    K = 8
    stacked = {
        "w": jnp.asarray(RNG.normal(size=(K, 6, 4)).astype(np.float32)),
        "b": jnp.asarray(RNG.normal(size=(K, 4)).astype(np.float32)),
    }
    server = FedServer(ServerConfig(rule=rule, num_clients=K))
    agg, info = server.aggregate_tree(stacked, np.ones(K, np.float32), np.arange(K))
    assert agg["w"].shape == (6, 4) and agg["b"].shape == (4,)
    assert np.isfinite(np.asarray(agg["w"])).all()
    assert info["good_mask"].shape == (K,)


@pytest.mark.parametrize("rule", sorted(RULES))
def test_tree_and_matrix_dispatch_agree(rule):
    """Flatten-fallback tree dispatch == matrix dispatch on the same rows."""
    K, d = 8, 24
    U = _updates(K, d)
    stacked = {"w": U.reshape(K, 6, 4)}
    n_k = jnp.ones((K,), jnp.float32)
    p_k = jnp.full((K,), 0.5, jnp.float32)
    mask = jnp.ones((K,), bool)
    opts = RuleOptions()
    from repro.core import dispatch_rule

    mat = dispatch_rule(rule, U, n_k, p_k, mask, opts)
    tre = dispatch_rule_tree(rule, stacked, n_k, p_k, mask, opts)
    np.testing.assert_allclose(
        np.asarray(tre.aggregate["w"]).reshape(-1), np.asarray(mat.aggregate),
        rtol=2e-5, atol=2e-6,
    )
    np.testing.assert_array_equal(np.asarray(tre.good_mask), np.asarray(mat.good_mask))


def test_unknown_rule_raises():
    with pytest.raises(ValueError, match="unknown rule"):
        FedServer(ServerConfig(rule="nope", num_clients=4)).aggregate(
            _updates(4), np.ones(4, np.float32), np.arange(4)
        )


@pytest.mark.parametrize("rule", sorted(RULES))
def test_use_kernels_flag_accepted_by_every_rule(rule):
    """On non-TPU backends use_kernels falls back to the jnp reference, so
    results must be identical with the flag on or off — for every rule."""
    K = 10
    U = _updates(K)
    n = np.ones(K, np.float32)
    a_ref, _ = FedServer(ServerConfig(rule=rule, num_clients=K)).aggregate(
        U, n, np.arange(K)
    )
    a_krn, _ = FedServer(
        ServerConfig(rule=rule, num_clients=K, use_kernels=True)
    ).aggregate(U, n, np.arange(K))
    np.testing.assert_allclose(
        np.asarray(a_ref), np.asarray(a_krn), rtol=1e-6, atol=1e-7
    )


# ------------------------ stacked-pytree attacks -----------------------------


def _stacked(K=6):
    return {
        "w": jnp.asarray(RNG.normal(size=(K, 5, 3)).astype(np.float32)),
        "b": jnp.asarray(RNG.normal(size=(K, 3)).astype(np.float32)),
    }


def test_byzantine_tree_touches_only_bad_rows():
    K = 6
    props = _stacked(K)
    w_prev = {"w": jnp.zeros((5, 3)), "b": jnp.zeros((3,))}
    bad = jnp.asarray([True, True, False, False, False, False])
    out = byzantine_update_tree(props, w_prev, bad, jax.random.PRNGKey(0), scale=20.0)
    np.testing.assert_array_equal(np.asarray(out["w"][2:]), np.asarray(props["w"][2:]))
    # bad rows are w_prev + N(0, 20^2): huge relative to the honest rows
    assert float(jnp.abs(out["w"][:2]).mean()) > 5.0


def test_alie_tree_matches_flat_reference():
    K = 6
    props = _stacked(K)
    bad = jnp.asarray([True, False, False, False, False, False])
    benign = ~bad
    out = alie_update_tree(props, bad, benign, z_max=1.2)
    flat = np.asarray(props["w"]).reshape(K, -1)
    mu, sd = flat[1:].mean(0), flat[1:].std(0)
    np.testing.assert_allclose(
        np.asarray(out["w"][0]).reshape(-1), mu - 1.2 * sd, rtol=1e-4, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(out["b"][1:]), np.asarray(props["b"][1:]))


def test_alie_legacy_default_agrees_with_tree_default():
    """Regression: the legacy flat helper defaulted to z_max=1.0 while the
    tree transform / EngineConfig use 1.2, so analysis-script numbers
    silently disagreed with engine runs.  At *defaults* both forms must
    produce the same adversarial row."""
    import inspect

    from repro.fed import EngineConfig

    assert (
        inspect.signature(alie_update_attack).parameters["z_max"].default
        == inspect.signature(alie_update_tree).parameters["z_max"].default
        == EngineConfig().alie_z_max
    )
    K = 6
    props = _stacked(K)
    bad = jnp.asarray([True, False, False, False, False, False])
    tree_out = alie_update_tree(props, bad, ~bad)  # defaults
    flat = np.asarray(props["w"]).reshape(K, -1)
    legacy_row = alie_update_attack(flat[1:])      # defaults
    np.testing.assert_allclose(
        np.asarray(tree_out["w"][0]).reshape(-1), legacy_row, rtol=1e-4, atol=1e-5
    )


def test_ipm_tree_matches_flat_reference():
    K = 6
    props = _stacked(K)
    bad = jnp.asarray([True, True, False, False, False, False])
    benign = ~bad
    out = ipm_update_tree(props, bad, benign, eps=0.5)
    flat = np.asarray(props["b"])
    np.testing.assert_allclose(
        np.asarray(out["b"][0]), -0.5 * flat[2:].mean(0), rtol=1e-5, atol=1e-6
    )
