"""A run with the timed path broken underneath must come out not correct.
Each fault is planted in the program for one tiny CPU run through the
harness (the chip check skipped), once for each fault the cell can have:
a step that returns its state unchanged, half the batch left out (the mean
taken over the rest), the exchange between chips left out, and an answer
altered where it is produced."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench.tests.harness import run_tiny

ROOT = Path(__file__).resolve().parents[2]


def _clear_program_caches():
    from repro.fed import client, engine
    from repro.serve import service

    for fn in (engine._make_fused_segment_cached, engine._make_fused_sim_cached,
               service._make_agg_step):
        fn.cache_clear()
    jax.clear_caches()
    del client


# -- sim: the fused round ----------------------------------------------------

def _sim_state_unchanged(monkeypatch):
    from repro.fed import engine

    body = engine._round_body

    def frozen(*a, **k):
        carry = a[10]
        _, out = body(*a, **k)
        return carry, out

    monkeypatch.setattr(engine, "_round_body", frozen)


def _sim_half_batch(monkeypatch):
    from repro.fed import workload

    sgd = workload.local_sgd

    def half(loss_fn, params, batches, key, **kw):
        b = batches["x"].shape[1]
        return sgd(loss_fn, params, {k: v[:, : b // 2] for k, v in batches.items()},
                   key, **kw)

    monkeypatch.setattr(workload, "local_sgd", half)


def _sim_answer_altered(monkeypatch):
    from repro.fed import server

    dispatch = server.dispatch_rule

    def altered(*a, **k):
        res = dispatch(*a, **k)
        return res._replace(aggregate=res.aggregate.at[0].add(1.0))

    monkeypatch.setattr(server, "dispatch_rule", altered)


@pytest.mark.parametrize("plant", [_sim_state_unchanged, _sim_half_batch,
                                   _sim_answer_altered])
def test_sim_fault_is_not_correct(tmp_path, monkeypatch, plant):
    _clear_program_caches()
    plant(monkeypatch)
    try:
        res = run_tiny("afa_mnist_k100.sim", "tiny_sim", "tiny_experiments", tmp_path)
    finally:
        monkeypatch.undo()
        _clear_program_caches()
    assert res["correct"] is False, res["checks"]


# -- serve: the aggregation service --------------------------------------------

def _serve_state_unchanged(monkeypatch):
    from repro.serve import service

    step = service.server_step_versioned

    def frozen(state, *a, **k):
        _, res = step(state, *a, **k)
        return state, res

    monkeypatch.setattr(service, "server_step_versioned", frozen)


def _serve_half_batch(monkeypatch):
    from repro.serve import service

    step = service.server_step_versioned

    def half(state, proposals, n_k, mask0, *a, **k):
        K = mask0.shape[0]
        return step(state, proposals, n_k, mask0 & (jnp.arange(K) < K // 2), *a, **k)

    monkeypatch.setattr(service, "server_step_versioned", half)


def _serve_answer_altered(monkeypatch):
    from repro.serve import service

    step = service.server_step_versioned

    def altered(*a, **k):
        state, res = step(*a, **k)
        return state, res._replace(aggregate=res.aggregate.at[0].add(1.0))

    monkeypatch.setattr(service, "server_step_versioned", altered)


@pytest.mark.parametrize("plant", [_serve_state_unchanged, _serve_half_batch,
                                   _serve_answer_altered])
def test_serve_fault_is_not_correct(tmp_path, monkeypatch, plant):
    _clear_program_caches()
    plant(monkeypatch)
    try:
        res = run_tiny("afa_mnist_k100.serve", "tiny_serve", "tiny_closed_loop",
                       tmp_path)
    finally:
        monkeypatch.undo()
        _clear_program_caches()
    assert res["correct"] is False, res["checks"]


# -- four chips: the exchange between chips left out ----------------------------

NO_EXCHANGE = """
import json, sys, tempfile
from pathlib import Path
sys.path[:0] = [{root!r}, {src!r}]
import jax
from repro.core import afa
psum = jax.lax.psum
# the sharded screen's (D,) exchanges: each chip keeps its own partial
from types import SimpleNamespace
afa.jax = SimpleNamespace(**vars(jax))
afa.jax.lax = SimpleNamespace(**dict(vars(jax.lax), psum=lambda x, axis, **k:
    x if getattr(x, "ndim", 0) == 1 and x.shape[0] > 3 else psum(x, axis, **k)))
from bench.tests.harness import run_tiny
res = run_tiny("afa_mnist_k400.sim_4chip", "tiny_sharded", "tiny_experiments",
               Path(tempfile.mkdtemp()), chips=4)
print(json.dumps(res))
"""


def test_sharded_without_exchange_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", NO_EXCHANGE.format(root=str(ROOT), src=str(ROOT / "src"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is False, res["checks"]
