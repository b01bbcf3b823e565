"""Facade and kernel-plan tests.

``repro.fed.api.run`` runs the fused scan by default and checks its
arguments, and the four kernel/layout knobs must resolve through ONE frozen
``KernelPlan`` with a documented precedence and loud conflicts.
"""

import numpy as np
import pytest

from repro.data import make_mnist_like
from repro.fed import ServerConfig, SimConfig, run, simulate
from repro.fed.server import resolve_server_plan
from repro.kernels.policy import KernelPlan, resolve_kernel_plan


@pytest.fixture(scope="module")
def data():
    return make_mnist_like(n_train=500, n_test=120, dim=16)


@pytest.fixture(scope="module")
def sim():
    return SimConfig(
        num_clients=6, bad_frac=0.34, scenario="byzantine", rounds=5,
        local_epochs=1, batch_size=50, hidden=(8,), dropout=False, seed=0,
        engine="fused",
    )


@pytest.fixture(scope="module")
def server():
    return ServerConfig(rule="afa", num_clients=6)


# ---------------------------------------------------------------------------
# 1. the facade: the fused scan by default, loud argument errors
# ---------------------------------------------------------------------------


def test_default_engine_is_the_fused_scan(data, server):
    """``SimConfig()`` selects the fused scan, and ``api.run`` on it runs
    that scan: the ``fed.run`` span says so and the trajectory is the one
    ``engine="fused"`` gives, bit for bit."""
    from repro.utils import spans

    assert SimConfig().engine == "fused"
    kw = dict(num_clients=6, bad_frac=0.34, scenario="byzantine", rounds=3,
              local_epochs=1, batch_size=50, hidden=(8,), seed=0)
    n0 = max((r.span_id for r in spans.records()), default=0)
    res = run(None, SimConfig(**kw), server, data=data)
    (root,) = [r for r in spans.records()
               if r.span_id > n0 and r.name == "fed.run"]
    assert root.attrs["engine"] == "fused"
    ref = run(None, SimConfig(engine="fused", **kw), server, data=data)
    assert res.test_error == ref.test_error
    assert np.array_equal(res.blocked_round, ref.blocked_round)
    assert np.array_equal(np.stack(res.good_mask_history),
                          np.stack(ref.good_mask_history))


def test_facade_argument_errors(data, sim, server):
    with pytest.raises(ValueError, match="needs `data`"):
        run(None, sim, server)
    with pytest.raises(TypeError, match="unexpected keyword"):
        run(None, sim, server, data=data, seq=16)
    with pytest.raises(ValueError, match="workload_kwargs"):
        run(object(), sim, server, workload_kwargs={"rank": 2})


# ---------------------------------------------------------------------------
# 2. KernelPlan: one resolved config for four historical knobs
# ---------------------------------------------------------------------------


def test_kernel_plan_is_frozen_and_validated():
    plan = KernelPlan(mode="interpret", launch="chained", layout="tree")
    with pytest.raises(Exception):
        plan.mode = "jnp"  # frozen
    with pytest.raises(ValueError):
        KernelPlan(mode="warp")
    with pytest.raises(ValueError):
        KernelPlan(launch="exploded")
    with pytest.raises(ValueError):
        KernelPlan(layout="diagonal")


def test_resolve_precedence_config_pin_beats_env(monkeypatch):
    # 1. an explicit config mode string pins the mode
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    assert resolve_kernel_plan("interpret").mode == "interpret"
    # 2. with config on auto, an env pin elevates use_kernels=True
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    assert resolve_kernel_plan(True).mode == "interpret"
    # the explicit "auto" string defers to the backend at dispatch (the env
    # pin then resolves the True), never an explicit demand
    assert resolve_kernel_plan("auto").mode is True
    # matching pins agree quietly
    assert resolve_kernel_plan("interpret").mode == "interpret"
    # 3. no pins: the bool passes through for runtime auto-detection
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    assert resolve_kernel_plan(True).mode is True
    assert resolve_kernel_plan(False).mode is False


def test_resolve_conflicting_explicit_requests_raise(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "jnp")
    with pytest.raises(ValueError, match="REPRO_KERNELS"):
        resolve_kernel_plan("interpret")


def test_server_config_legacy_knobs_warn_and_map(recwarn):
    cfg = ServerConfig(num_clients=4, use_kernels="interpret", agg_layout="tree")
    with pytest.deprecated_call():
        plan = resolve_server_plan(cfg)
    assert plan == KernelPlan(mode="interpret", launch="fused", layout="tree")

    # the new spelling resolves silently
    cfg2 = ServerConfig(
        num_clients=4, kernel_plan=KernelPlan(mode="interpret", layout="tree")
    )
    recwarn.clear()
    assert resolve_server_plan(cfg2) == plan
    assert not any(
        issubclass(w.category, DeprecationWarning) for w in recwarn.list
    )


def test_server_config_conflicting_knobs_raise():
    cfg = ServerConfig(
        num_clients=4,
        kernel_plan=KernelPlan(layout="packed"),
        agg_layout="tree",
    )
    with pytest.raises(ValueError, match="conflicts"):
        resolve_server_plan(cfg)


def test_simulate_threads_plan_layouts_bit_identically(data, sim, server):
    """kernel_plan layouts route through make_rule_options and the engines:
    tree and packed layouts must agree bit for bit (the fused engine's
    layout contract), now spelled through the ONE knob."""
    import dataclasses as dc

    res_p = simulate(
        data, sim,
        dc.replace(server, kernel_plan=KernelPlan(layout="packed")),
    )
    res_t = simulate(
        data, sim,
        dc.replace(server, kernel_plan=KernelPlan(layout="tree")),
    )
    assert res_p.test_error == res_t.test_error
    assert np.array_equal(res_p.blocked_round, res_t.blocked_round)
