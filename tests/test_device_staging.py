"""Device staging of the fused engines' client stacks: the shard index
functions behind ``iid_shards`` / ``dirichlet_shards``, the row-map gather
from a device-resident pool (equal, bit for bit, to the host
``compact_stack(padded_stack(...))`` it replaces), and the single-entry
cache that keeps a dataset's pool on the device between experiments."""

import gc

import numpy as np
import pytest

from repro.data import (
    compact_stack,
    dirichlet_shard_indices,
    dirichlet_shards,
    iid_shard_indices,
    iid_shards,
    make_mnist_like,
    padded_stack,
    shard_compact_plan,
)
from repro.fed import ServerConfig, SimConfig
from repro.fed import simulator
from repro.fed.api import run
from repro.fed.simulator import _compact_inputs, _Setup
from repro.utils import spans

# ------------------------- shard indices -------------------------------------

# the first three rows of each of 4 clients' shards of 50 rows (labels
# ``arange(50) % 3``), as the split has drawn them since before it returned
# indices: a change in how the splits consume their RNG shows here
PINNED = {
    ("iid", 0): [[18, 23, 36], [22, 10, 45], [28, 0, 8], [13, 12, 7]],
    ("iid", 1): [[15, 25, 37], [24, 0, 20], [44, 1, 11], [2, 12, 10]],
    ("iid", 2**31 + 5): [[13, 34, 16], [21, 15, 27], [1, 24, 25], [19, 43, 33]],
    ("dirichlet", 0): [[40, 25, 7], [26, 2, 29], [0, 36, 39], [32, 49, 16]],
    ("dirichlet", 1): [[24, 30, 12], [8, 2, 20], [28, 22, 25], [35, 41, 7]],
    ("dirichlet", 2**31 + 5): [[7], [19, 22, 4], [17, 2, 14], [33, 24, 9]],
}


@pytest.mark.parametrize("split, seed", sorted(PINNED))
def test_shard_indices_reproduce_the_shards(split, seed):
    x = np.arange(50, dtype=np.float32)[:, None]
    y = np.arange(50) % 3
    if split == "iid":
        rows = iid_shard_indices(len(x), 4, seed=seed)
        shards = iid_shards(x, y, 4, seed=seed)
    else:
        rows = dirichlet_shard_indices(y, 4, alpha=0.5, seed=seed)
        shards = dirichlet_shards(x, y, 4, alpha=0.5, seed=seed)
    assert [r[:3].tolist() for r in rows] == PINNED[split, seed]
    # every row is some client's (a client left empty draws one more)
    assert set(np.concatenate(rows).tolist()) == set(range(50))
    assert len(shards) == len(rows) == 4
    for (xs, ys), r in zip(shards, rows):
        np.testing.assert_array_equal(xs, x[r])
        np.testing.assert_array_equal(ys, y[r])


# ------------------------- staging by a row map ------------------------------


@pytest.fixture(scope="module")
def data():
    return make_mnist_like(n_train=600, n_test=50, dim=16)


K = 8
LAYOUTS = {
    # every client, the one-shot engine's layout and the first segment's
    "identity": (np.arange(K), K),
    # a bucket shrunk past blocked clients, padded at its tail
    "shrunk": (np.asarray([1, 3, 4, 6]), 8),
    # a client-sharded layout: -1 slots at each of 2 shard blocks' tails
    "sharded": (np.asarray([0, 2, 3, -1, 5, 6, -1, -1]), 8),
    # the plan the sharded engine makes for 5 live clients over 4 shards
    "sharded_plan": (shard_compact_plan(np.asarray([0, 2, 3, 5, 6]), 4, 2)[0], 8),
}


def _host_reference(setup, kept, bucket):
    """The stacks as the host built them: pad, compact, then the masks."""
    x, y, lengths = compact_stack(*padded_stack(setup.poisoned), kept,
                                  pad_to=bucket)
    live = kept >= 0
    n_k = np.zeros((bucket,), np.float32)
    bad = np.zeros((bucket,), bool)
    ids = np.zeros((bucket,), np.uint32)
    n_k[: len(kept)][live] = setup.n_k[kept[live]]
    bad[: len(kept)][live] = setup.bad_mask[kept[live]]
    ids[: len(kept)][live] = kept[live]
    return x, y, lengths, n_k, bad, ids


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("scenario", ["byzantine", "flipping", "noisy"])
@pytest.mark.parametrize("split", ["iid", "dirichlet"])
def test_device_staging_equals_host_stacks(data, split, scenario, layout):
    kept, bucket = LAYOUTS[layout]
    setup = _Setup(data, SimConfig(num_clients=K, bad_frac=0.4,
                                   scenario=scenario, sharding=split, seed=4))
    if split == "dirichlet":
        assert len({len(x) for x, _ in setup.poisoned}) > 1  # unequal shards
    fdata, bad, ids = _compact_inputs(setup, kept, bucket)
    got = (fdata.x, fdata.y, fdata.lengths, fdata.n_k, bad, ids)
    for g, want in zip(got, _host_reference(setup, kept, bucket)):
        g = np.asarray(g)
        assert g.dtype == want.dtype and g.shape == want.shape
        # bit for bit: compare the raw bytes, so -0.0 and NaN payloads count
        assert g.tobytes() == want.tobytes()


def test_compact_inputs_refuse_to_truncate(data):
    setup = _Setup(data, SimConfig(num_clients=K, scenario="byzantine"))
    with pytest.raises(ValueError, match="truncate"):
        _compact_inputs(setup, np.arange(5), 4)


# ------------------------- the dataset pool ----------------------------------


def _sim(scenario="byzantine", seed=3):
    """40% byzantine at K = 8, so the bucket shrinks and stages twice."""
    return SimConfig(num_clients=K, bad_frac=0.4, scenario=scenario,
                     rounds=8, local_epochs=1, batch_size=30, hidden=(8,),
                     seed=seed, engine="fused", segment_rounds=4, compact=True)


def _stage_pools(data, sim):
    """The ``pool`` attribute of each staging of one ``api.run``."""
    n0 = max((r.span_id for r in spans.records()), default=0)
    run(None, sim, ServerConfig(rule="afa", num_clients=K), data=data)
    return [r.attrs["pool"] for r in spans.records()
            if r.span_id > n0 and r.name == "fed.segment.stage"]


def test_second_experiment_on_a_dataset_finds_its_pool():
    data = make_mnist_like(n_train=400, n_test=40, dim=12, seed=1)
    first = _stage_pools(data, _sim(seed=3))
    assert first[0] == "upload" and set(first[1:]) <= {"hit"}
    assert _stage_pools(data, _sim(seed=4)) == ["hit"] * len(first)


@pytest.mark.parametrize("case", ["other_dataset", "noisy", "flipping"])
def test_pool_uploads_for_new_rows(case):
    data = make_mnist_like(n_train=400, n_test=40, dim=12, seed=2)
    _stage_pools(data, _sim())
    if case == "other_dataset":
        other = make_mnist_like(n_train=400, n_test=40, dim=12, seed=2)
        pools = _stage_pools(other, _sim())
    else:  # poisoned shards are not rows of the dataset: their own pool
        pools = _stage_pools(data, _sim(scenario=case))
    assert pools[0] == "upload" and set(pools[1:]) <= {"hit"}
    # ... and a poisoning run leaves the dataset's pool where it was
    assert _stage_pools(data, _sim(seed=6))[0] == (
        "upload" if case == "other_dataset" else "hit")


def test_pool_cache_holds_one_dataset_and_lets_it_go():
    a = make_mnist_like(n_train=400, n_test=40, dim=12, seed=5)
    b = make_mnist_like(n_train=400, n_test=40, dim=12, seed=6)
    _stage_pools(a, _sim())
    _stage_pools(b, _sim())
    entry = simulator._dataset_pool[0]
    assert entry[0]() is b.x_train and entry[1]() is b.y_train
    assert _stage_pools(a, _sim())[0] == "upload"  # one entry: b replaced a
    del a
    gc.collect()
    assert simulator._dataset_pool[0] is None
