"""The local update over trainer rows only (``engine._trainer_rows``): the
rows whose result a round throws away — byzantine rows under an update-level
attack, pad rows of a compacted bucket — do not train, and the trajectory is
bit-identical to the full-row program in which every row trains."""

import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import make_mnist_like
from repro.fed import ServerConfig, SimConfig
from repro.fed.engine import make_fused_segment, trainer_count
from repro.fed.server import init_server_state, make_rule_options, resolve_server_plan
from repro.fed import simulator
from repro.fed.simulator import first_segment, fused_inputs, simulate
from repro.utils import spans

K = 10


@pytest.fixture(scope="module")
def data():
    return make_mnist_like(n_train=600, n_test=100, dim=64)


def _sim(scenario, segment_rounds, seed=3):
    """40% byzantine at K = 10: 6 rows train; under ``byzantine`` AFA blocks
    3 of the 4 mid-run and the segmented run compacts to a bucket of 8 rows,
    one of them padding."""
    return SimConfig(num_clients=K, bad_frac=0.4, scenario=scenario, rounds=12,
                     local_epochs=1, batch_size=30, hidden=(16,), seed=seed,
                     engine="fused", segment_rounds=segment_rounds, compact=True)


def _server():
    return ServerConfig(rule="afa", num_clients=K)


def _full_rows(data, sim, server):
    """The full-row program on the identity layout, one segment of every
    round: every row trains and non-trainers are selected back."""
    inp = fused_inputs(data, sim)
    seg_fn = make_fused_segment(
        inp.workload, inp.engine_cfg, rule=server.rule,
        opts=make_rule_options(server, K), delta_block=server.delta_block,
        num_clients_total=K, seg_len=sim.rounds, batch_s=inp.batch_s,
        batch_b=inp.batch_b, agg_layout=resolve_server_plan(server).layout,
    )
    params, state, traj = seg_fn(
        inp.params0, init_server_state(K, server.alpha0, server.beta0),
        jnp.uint32(sim.seed), inp.data, jnp.asarray(inp.bad_mask),
        jnp.arange(K, dtype=jnp.uint32), jnp.int32(0),
    )
    return SimpleNamespace(
        test_error=list(np.asarray(traj.test_error, np.float64) * 100.0),
        good_mask_history=list(np.asarray(traj.good_mask)),
        blocked_round=np.asarray(state.rounds_blocked),
        similarity_history=list(np.asarray(traj.similarities)),
        params=params,
    )


def _full_rows_segmented(data, sim, server, monkeypatch):
    """The segmented driver on the same layouts, each staged with every row
    of its bucket training: the full-row segment program."""
    with monkeypatch.context() as m:
        m.setattr(simulator, "_train_rows", lambda setup, kept, bucket, mesh: bucket)
        return simulate(data, sim, server)


def _run(data, sim, server):
    """``simulate`` and the ``fed.segment.stage`` records it left."""
    n0 = max((r.span_id for r in spans.records()), default=0)
    res = simulate(data, sim, server)
    return res, [r for r in spans.records()
                 if r.span_id > n0 and r.name == "fed.segment.stage"]


def _assert_bit_identical(res, ref):
    """Per round test error, kept set, blocked set and similarities, and
    the final params, bit for bit."""
    np.testing.assert_array_equal(np.asarray(res.test_error), np.asarray(ref.test_error))
    np.testing.assert_array_equal(np.stack(res.good_mask_history),
                                  np.stack(ref.good_mask_history))
    np.testing.assert_array_equal(res.blocked_round, ref.blocked_round)
    np.testing.assert_array_equal(np.stack(res.similarity_history),
                                  np.stack(ref.similarity_history))
    for a, b in zip(jax.tree_util.tree_leaves(res.params),
                    jax.tree_util.tree_leaves(ref.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("segment_rounds", [0, 4], ids=["one_shot", "segmented"])
@pytest.mark.parametrize("scenario", ["byzantine", "alie", "ipm", "flipping"])
def test_trainer_rows_bit_identical_to_full_rows(
    data, monkeypatch, scenario, segment_rounds
):
    sim, server = _sim(scenario, segment_rounds), _server()
    res, stages = _run(data, sim, server)
    if not segment_rounds:
        _assert_bit_identical(res, _full_rows(data, sim, server))
    else:
        _assert_bit_identical(res, _full_rows_segmented(data, sim, server, monkeypatch))
        first = stages[0].attrs
        assert (first["bucket"], first["rows"]) == (K, K)
        # flipping clients train on poisoned data: every row trains
        assert first["train_rows"] == (K if scenario == "flipping" else 6)


def test_padded_bucket_trains_only_its_clients(data, monkeypatch):
    """The byzantine run compacts to 7 clients in a bucket of 8, one of
    them byzantine and never blocked: only the 6 honest rows train, neither
    the pad row nor the byzantine one, and the trajectory stays the
    full-row one."""
    sim, server = _sim("byzantine", 4), _server()
    res, stages = _run(data, sim, server)
    padded = [s.attrs for s in stages if s.attrs["bucket"] > s.attrs["rows"]]
    assert [(s["bucket"], s["rows"], s["train_rows"]) for s in padded] == [(8, 7, 6)]
    _assert_bit_identical(res, _full_rows_segmented(data, sim, server, monkeypatch))


def test_trainer_count_follows_the_scenario():
    bad = np.array([True, True, False, False, False])
    live = np.array([True, True, True, True, False])
    assert trainer_count("byzantine", bad) == 3
    assert trainer_count("alie", bad, live) == 2
    for scenario in ("clean", "flipping", "noisy"):
        assert trainer_count(scenario, bad) == 5
        assert trainer_count(scenario, bad, live) == 4


def _batched_dots(text):
    """Leading dimension of every batched ``dot_general`` in StableHLO."""
    return [int(m) for m in re.findall(
        r"stablehlo\.dot_general[^\n]*batching_dims[^\n]*: \(tensor<(\d+)x", text)]


def _segment(scenario, bad_frac):
    """The first segment program at K = 100, its arguments, and the
    experiment's fused inputs and server."""
    data = make_mnist_like(n_train=2000, n_test=50, dim=16)
    sim = SimConfig(num_clients=100, bad_frac=bad_frac, scenario=scenario,
                    rounds=4, local_epochs=1, batch_size=10, hidden=(8,),
                    engine="fused", segment_rounds=4)
    server = ServerConfig(rule="afa", num_clients=100)
    seg_fn, args = first_segment(data, sim, server)
    return seg_fn, args, fused_inputs(data, sim), server


def test_local_update_runs_over_the_trainer_rows_at_k100():
    """K = 100, 30% byzantine: the local update's vmapped matmuls have batch
    dimension 70, the trainer rows, and the one minibatch gather reads the
    100-row stack by (row, sample) with no copy of the stack's rows."""
    seg_fn, args, _, _ = _segment("byzantine", 0.3)
    text = seg_fn.lower(*args).as_text()
    dots = _batched_dots(text)
    assert dots and set(dots) == {70}, dots
    gathers = re.findall(r'"stablehlo\.gather"[^\n]*', text)
    stack = [g for g in gathers if "tensor<100x20x16xui32>" in g]
    assert len(stack) == 1 and "-> tensor<70x2x10x16xui32>" in stack[0], stack


def test_clean_full_cohort_is_the_full_row_program():
    """Where every row trains (``clean``, no padding) the program is the
    full-row one, op for op: no trainer list, gather or scatter."""
    seg_fn, args, inp, server = _segment("clean", 0.0)
    full = make_fused_segment(
        inp.workload, inp.engine_cfg, rule=server.rule,
        opts=make_rule_options(server, 100), delta_block=server.delta_block,
        num_clients_total=100, seg_len=4, batch_s=inp.batch_s,
        batch_b=inp.batch_b, agg_layout=resolve_server_plan(server).layout,
    )
    text = seg_fn.lower(*args).as_text()
    assert set(_batched_dots(text)) == {100}
    assert text == full.lower(*args).as_text()
