"""Paper-scale federated simulator: K clients x T rounds over a synthetic
dataset, with clean / byzantine / flipping / noisy / alie / ipm scenarios —
reproduces the paper's Tables 1-2 and the convergence figures.

Four round engines (DESIGN.md §2), selected by ``SimConfig.engine``:

  * ``batched`` (default) — the device-resident round: one jit call per round
    vmaps ``local_sgd`` over a stacked client axis, applies the update-level
    attacks as stacked-pytree transforms on device, and aggregates through
    the registry tree dispatch.  Proposals never round-trip through host
    numpy, but the loop over rounds (and the minibatch draws) stay on host.
  * ``looped`` — the reference path: one jit dispatch per client per round.
    Aggregation goes through the same registry tree dispatch, so the engines
    differ only in the client layer.  Kept for equivalence testing and as the
    baseline of ``benchmarks/round_engine.py``.
  * ``fused`` — the whole T-round simulation as ONE jit: ``lax.scan`` over
    rounds with ``(params, ServerState)`` as carry, minibatch indices drawn
    on device with ``jax.random`` from padded ``(K, n_max, ...)`` shard
    stacks, and the per-round trajectory emitted as scan outputs.  O(1)
    host↔device syncs per simulation instead of O(T); ``run_sweep`` vmaps it
    over a seed axis.  With ``SimConfig.segment_rounds > 0`` the scan is cut
    into S-round segments and (``compact=True``) blocked clients are
    compacted out of the stacked layout between segments — power-of-two
    buckets, original-id-keyed RNG streams — producing a bit-identical
    trajectory while paying FLOPs only for live clients (DESIGN.md §2).
  * ``fused_eager`` — the fused round body run eagerly one round at a time:
    the bit-equivalence reference for the fused scan
    (``tests/test_fused_engine.py``).

Aggregation representation (``ServerConfig.agg_layout``, DESIGN.md §3): by
default every engine packs the stacked proposal pytree into one contiguous
``(K, D)`` buffer per round and runs the rules' matrix forms on it
("packed"); "tree" packs inside the dispatch instead (bit-identical), and
"leaf" keeps the legacy per-leaf path as the benchmark reference.

All four engines key per-client RNG as ``fold_in(fold_in(PRNGKey(seed),
CLIENT_STREAM), round * K + k)`` and the attack noise as
``fold_in(PRNGKey(seed), round)``.  ``batched`` and ``looped`` additionally
draw minibatch indices from the same host numpy stream, so on fixed seeds
they produce matching per-round trajectories (test error, ``good_mask``
history); see ``tests/test_round_engine.py``.  The fused engines draw
minibatch indices from a ``jax.random`` stream instead (there is no host RNG
inside a scan), so fused trajectories are equivalent in distribution — not
bitwise — to the host engines'; the batched engine stays the reference
implementation of the round itself.

Byzantine clients skip training entirely and send w_t + N(0, 20^2 I) (the
paper's update-level fault); flipping/noisy clients poison their *shard* and
train honestly on it.
"""

from __future__ import annotations

import dataclasses
import functools
import time
import warnings
import weakref
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.attacks import (
    UPDATE_ATTACK_SCENARIOS,
    apply_update_attack,
    flip_labels,
    noisy_features,
)
from repro.data import (
    SyntheticClassification,
    dirichlet_shard_indices,
    iid_shard_indices,
    pow2_bucket,
    shard_compact_plan,
)
from repro.data.sharding import stack_dtype
from repro.fed.engine import (
    EngineConfig,
    FusedData,
    FusedTrajectory,
    attack_key,
    client_keys,
    make_fused_segment,
    make_fused_sim,
    make_train_attack_step,
    place_on_client_mesh,
    sweep_fused_sim,
    trainer_count,
)
from repro.fed.server import (
    FedServer,
    ServerConfig,
    gather_server_state,
    init_server_state,
    make_rule_options,
    resolve_server_plan,
    scatter_server_state,
)
from repro.fed.workload import DnnWorkload
from repro.utils.spans import span
from repro.utils.trees import tree_stack


@dataclasses.dataclass
class SimConfig:
    num_clients: int = 10
    bad_frac: float = 0.3
    scenario: str = "clean"      # clean | byzantine | flipping | noisy | alie | ipm
    rounds: int = 30
    local_epochs: int = 10
    batch_size: int = 200
    lr: float = 0.1
    momentum: float = 0.9
    dropout: bool = True
    byzantine_scale: float = 20.0
    seed: int = 0
    hidden: tuple = (512, 256)
    sharding: str = "iid"        # iid | dirichlet (non-IID label skew)
    dirichlet_alpha: float = 0.5
    engine: str = "batched"      # batched | looped | fused | fused_eager
    # fused engine only: > 0 cuts the one-shot scan into segments of this
    # many rounds, with host-side compaction of blocked clients between
    # segments when ``compact`` is set (0 = single scan, no compaction)
    segment_rounds: int = 0
    compact: bool = True
    # fused engine only: > 0 runs the scan client-sharded under shard_map
    # over a ``client`` mesh axis of this many devices (DESIGN.md §4) —
    # data stacks, server state, and the packed proposal buffer split
    # K / client_shards rows per device, AFA screens hierarchically, and
    # (with segment_rounds) compaction is per shard.  1 is a valid value:
    # a one-shard mesh runs the unsharded code inside shard_map, bit for
    # bit (the parity tests use it).  0 = no mesh, today's path.
    client_shards: int = 0


@dataclasses.dataclass
class SimResult:
    test_error: list            # per round
    train_time: float           # mean per round: local training (+ attacks)
    agg_time: float             # mean per round: server aggregation
    blocked_round: np.ndarray   # (K,) round at which blocked (-1 = never)
    bad_clients: np.ndarray     # indices
    good_mask_history: list
    detection_rate: float       # fraction of bad clients blocked by the end
    mean_rounds_to_block: float
    round_time: float = 0.0     # mean per round: batch draw + train +
                                # aggregate + eval dispatch (host engines eval
                                # in-loop, symmetric with the fused scan)
    round_times: list = dataclasses.field(default_factory=list)  # raw per-round
    params: object = None       # global parameters after the last round
    # fused engines: AFA's final-iteration cosine similarities, one (K,)
    # row per round (zeros for rules without them; empty elsewhere)
    similarity_history: list = dataclasses.field(default_factory=list)


class _Setup:
    """Shared (engine-independent) experiment state."""

    def __init__(self, data: SyntheticClassification, sim: SimConfig,
                 workload=None):
        self.rng = np.random.default_rng(sim.seed)
        self.sim = sim
        K = sim.num_clients
        n_bad = int(round(sim.bad_frac * K))
        self.bad = np.arange(n_bad)  # deterministic: first n_bad clients are bad
        self.bad_mask = np.zeros(K, bool)
        self.bad_mask[self.bad] = True

        if sim.sharding == "dirichlet":
            self.shard_rows = dirichlet_shard_indices(
                data.y_train, K, alpha=sim.dirichlet_alpha, seed=sim.seed
            )
        else:
            self.shard_rows = iid_shard_indices(len(data.x_train), K, seed=sim.seed)
        self.data = data
        binary = data.num_classes == 2
        # data-level poisoning
        self.poisoned = []
        rewritten = False
        for k, rows in enumerate(self.shard_rows):
            shard = x, y = data.x_train[rows], data.y_train[rows]
            if self.bad_mask[k] and sim.scenario == "flipping":
                x, y = flip_labels(x, y)
            elif self.bad_mask[k] and sim.scenario == "noisy":
                x, y = noisy_features(x, y, self.rng, binary=binary)
            rewritten |= x is not shard[0] or y is not shard[1]
            self.poisoned.append((x, y))
        # shards that are rows of ``data`` untouched: the fused engines then
        # stage them from a device-resident copy of the dataset (_pool)
        self.rows_of_data = not rewritten

        out_units = 1 if binary else data.num_classes
        self.sizes = (data.dim, *sim.hidden, out_units)
        # the classification simulator drives the paper-DNN workload by
        # default (the facade may inject a compatible override); all engines
        # below consume it only through the ClientWorkload protocol
        self.workload = (
            workload if workload is not None else DnnWorkload(self.sizes)
        )
        self.params0 = self.workload.init_params(jax.random.PRNGKey(sim.seed))
        self.n_k = np.asarray([len(x) for x, _ in self.poisoned], np.float32)
        self.x_test = jnp.asarray(data.x_test)
        self.y_test = jnp.asarray(data.y_test.astype(np.int32))
        self.err_fn = jax.jit(self.workload.eval_metric)

        # uniform per-round minibatch geometry (both engines; stacking needs
        # one (S, b) for every client).  Keyed to the MEAN shard so skewed
        # (dirichlet) splits don't under-train large clients; sampling is with
        # replacement, so b may exceed a small shard's length.  For equal
        # shards this reduces to the per-client geometry.
        lens = [len(x) for x, _ in self.poisoned]
        self.batch_b = min(sim.batch_size, max(lens))
        self.batch_s = sim.local_epochs * max(
            int(np.mean(lens)) // sim.batch_size, 1
        )

    def trainers(self, selected) -> list:
        """Selected clients that actually run local SGD this round, in
        ascending order (update-level attackers send forged updates instead)."""
        skip_bad = self.sim.scenario in UPDATE_ATTACK_SCENARIOS
        return [int(k) for k in selected if not (skip_bad and self.bad_mask[k])]

    def draw_indices(self, trainers: list) -> dict:
        """Consume the shared numpy stream — identically in both engines."""
        out = {}
        for k in trainers:
            x, _ = self.poisoned[k]
            out[k] = self.rng.integers(0, len(x), size=(self.batch_s, self.batch_b))
        return out

    def engine_config(self) -> EngineConfig:
        s = self.sim
        return EngineConfig(
            scenario=s.scenario, lr=s.lr, momentum=s.momentum, dropout=s.dropout,
            byzantine_scale=s.byzantine_scale,
        )

    def result(self, blocked_round: np.ndarray, test_error, good_hist,
               t_train, t_agg, round_times, params=None,
               sim_hist=()) -> SimResult:
        sim, bad = self.sim, self.bad
        rate, mean_rounds = detection_stats(blocked_round, bad)
        return SimResult(
            test_error=test_error,
            train_time=t_train / sim.rounds,
            agg_time=t_agg / sim.rounds,
            blocked_round=blocked_round,
            bad_clients=bad,
            good_mask_history=good_hist,
            detection_rate=rate,
            mean_rounds_to_block=mean_rounds,
            round_time=float(np.mean(round_times)) if round_times else 0.0,
            round_times=list(round_times),
            params=params,
            similarity_history=list(sim_hist),
        )


def detection_stats(blocked_round: np.ndarray, bad: np.ndarray):
    """(detection rate, mean rounds-to-block) over the bad-client set.

    ``blocked_round`` is 1-indexed (a client blocked during the first round
    carries 1, so round-1 blocks count as detected; -1 = never blocked).
    Both stats are NaN when there are no bad clients; the mean is NaN when
    none were blocked.
    """
    blocked_round = np.asarray(blocked_round)
    bad = np.asarray(bad, dtype=np.int64)
    if len(bad) == 0:
        return float("nan"), float("nan")
    det = blocked_round[bad] > 0
    rate = float(det.mean())
    mean_rounds = float(blocked_round[bad][det].mean()) if det.any() else float("nan")
    return rate, mean_rounds


def run_simulation(
    data: SyntheticClassification,
    sim: SimConfig,
    server_cfg: ServerConfig,
    *,
    eval_every: int = 1,
) -> SimResult:
    """DEPRECATED — call :func:`repro.fed.api.run` instead.

    Thin shim over :func:`simulate` (bit-identical trajectory), kept so
    existing callers keep working with a warning.
    """
    warnings.warn(
        "run_simulation is deprecated; use repro.fed.api.run(workload, sim, "
        "server, data=data) instead",
        DeprecationWarning,
        stacklevel=2,
    )
    return simulate(data, sim, server_cfg, eval_every=eval_every)


def simulate(
    data: SyntheticClassification,
    sim: SimConfig,
    server_cfg: ServerConfig,
    *,
    eval_every: int = 1,
    workload=None,
) -> SimResult:
    """The classification-simulator implementation behind
    ``repro.fed.api.run`` — route ``sim.engine`` to its round engine."""
    with span("fed.setup") as attrs:
        setup = _Setup(data, sim, workload=workload)
        attrs["h2d_bytes"] = int(setup.x_test.nbytes + setup.y_test.nbytes)
    if sim.client_shards > 0 and sim.engine != "fused":
        raise ValueError(
            f"client_shards requires engine='fused' (got {sim.engine!r})"
        )
    if sim.engine == "batched":
        return _run_batched(setup, server_cfg, eval_every)
    if sim.engine == "looped":
        return _run_looped(setup, server_cfg, eval_every)
    if sim.engine == "fused":
        if sim.segment_rounds > 0:
            return _run_fused_segmented(setup, server_cfg, eval_every)
        return _run_fused(setup, server_cfg, eval_every)
    if sim.engine == "fused_eager":
        return _run_fused(setup, server_cfg, eval_every, eager=True)
    raise ValueError(
        f"unknown engine {sim.engine!r} (batched | looped | fused | fused_eager)"
    )


# ---------------------------------------------------------------------------
# batched engine — device-resident round (DESIGN.md §2)
# ---------------------------------------------------------------------------


def _run_batched(setup: _Setup, server_cfg: ServerConfig, eval_every: int) -> SimResult:
    sim = setup.sim
    K = sim.num_clients
    server = FedServer(server_cfg)
    params = setup.params0
    step = make_train_attack_step(setup.workload, setup.engine_config())
    dim = setup.poisoned[0][0].shape[1]
    S, b = setup.batch_s, setup.batch_b
    bad_j = jnp.asarray(setup.bad_mask)

    test_error, good_hist, round_times = [], [], []
    t_train = t_agg = 0.0
    for rnd in range(sim.rounds):
        t_start = time.perf_counter()
        selected = server.select()
        trainers = setup.trainers(selected)
        idx = setup.draw_indices(trainers)

        xb = np.zeros((K, S, b, dim), np.float32)
        yb = np.zeros((K, S, b), np.int32)
        for k, ix in idx.items():
            x, y = setup.poisoned[k]
            xb[k] = x[ix]
            yb[k] = y[ix].astype(np.int32)
        batch = {"x": jnp.asarray(xb), "y": jnp.asarray(yb)}
        train_mask = np.zeros(K, bool)
        train_mask[trainers] = True
        mask0 = server.participation_mask(selected)
        benign = mask0 & ~setup.bad_mask

        t0 = time.perf_counter()
        proposals = step(
            params, batch, client_keys(sim.seed, rnd, K),
            jnp.asarray(train_mask), bad_j & jnp.asarray(mask0),
            jnp.asarray(benign), attack_key(sim.seed, rnd),
        )
        jax.block_until_ready(proposals)
        t_train += time.perf_counter() - t0

        t0 = time.perf_counter()
        agg, info = server.aggregate_tree(proposals, setup.n_k, selected)
        if not info["all_blocked"]:  # zero update: keep previous params
            params = agg
        jax.block_until_ready(params)
        t_agg += time.perf_counter() - t0
        good_hist.append(info.get("good_mask"))

        if rnd % eval_every == 0 or rnd == sim.rounds - 1:
            test_error.append(
                float(setup.err_fn(params, setup.x_test, setup.y_test)) * 100.0
            )
        # includes the eval dispatch, symmetric with the fused scan (which
        # evaluates every round in-scan) so engine benchmarks compare like
        # for like at eval_every=1
        round_times.append(time.perf_counter() - t_start)

    return setup.result(
        server.rounds_blocked, test_error, good_hist, t_train, t_agg, round_times,
        params,
    )


# ---------------------------------------------------------------------------
# looped engine — per-client dispatch reference
# ---------------------------------------------------------------------------


def _run_looped(setup: _Setup, server_cfg: ServerConfig, eval_every: int) -> SimResult:
    sim = setup.sim
    K = sim.num_clients
    server = FedServer(server_cfg)
    params = setup.params0
    ec = setup.engine_config()
    bad_j = jnp.asarray(setup.bad_mask)

    test_error, good_hist, round_times = [], [], []
    t_train = t_agg = 0.0
    for rnd in range(sim.rounds):
        t_start = time.perf_counter()
        selected = server.select()
        trainers = setup.trainers(selected)
        idx = setup.draw_indices(trainers)
        mask0 = server.participation_mask(selected)
        benign = mask0 & ~setup.bad_mask

        t0 = time.perf_counter()
        keys = client_keys(sim.seed, rnd, K)  # shared per-client key scheme
        per_client = [params] * K  # non-trainers hold w_t (masked out later)
        for k in trainers:
            x, y = setup.poisoned[k]
            batches = {
                "x": jnp.asarray(x[idx[k]]),
                "y": jnp.asarray(y[idx[k]].astype(np.int32)),
            }
            per_client[k] = setup.workload.local_update(
                ec, params, batches, keys[k]
            )
        stacked = tree_stack(per_client)
        stacked = apply_update_attack(
            sim.scenario, stacked, params, bad_j & jnp.asarray(mask0),
            jnp.asarray(benign), attack_key(sim.seed, rnd),
            byzantine_scale=ec.byzantine_scale, z_max=ec.alie_z_max, eps=ec.ipm_eps,
        )
        jax.block_until_ready(stacked)
        t_train += time.perf_counter() - t0

        # same registry tree dispatch as the batched engine, so the two
        # engines differ only in the client layer (per-client jit vs vmap)
        t0 = time.perf_counter()
        agg, info = server.aggregate_tree(stacked, setup.n_k, selected)
        if not info["all_blocked"]:  # zero update: keep previous params
            params = agg
        jax.block_until_ready(params)
        t_agg += time.perf_counter() - t0
        good_hist.append(info.get("good_mask"))

        if rnd % eval_every == 0 or rnd == sim.rounds - 1:
            test_error.append(
                float(setup.err_fn(params, setup.x_test, setup.y_test)) * 100.0
            )
        round_times.append(time.perf_counter() - t_start)

    return setup.result(
        server.rounds_blocked, test_error, good_hist, t_train, t_agg, round_times,
        params,
    )


# ---------------------------------------------------------------------------
# fused engine — the whole simulation as one lax.scan jit (DESIGN.md §2)
# ---------------------------------------------------------------------------


class _Pool(NamedTuple):
    """The device rows an experiment's client stacks are gathered from."""

    x: jax.Array          # (N, *feat) in the stack dtype
    y: jax.Array          # (N, *lab) int32
    rows: np.ndarray      # (K, n_max) int32: client k's pool rows, -1 past n_k
    lengths: np.ndarray   # (K,) int32 shard lengths


# the device copy of the dataset last staged from, while that dataset lives:
# (weak refs to its x_train and y_train, the client mesh or None, (x, y) on
# the device)
_dataset_pool: list = [None]


def _forget_dataset_pool(ref) -> None:
    entry = _dataset_pool[0]
    if entry is not None and (ref is entry[0] or ref is entry[1]):
        _dataset_pool[0] = None


def _upload(x, y, mesh):
    """``(x, y)`` on the device in the dtypes ``padded_stack`` gives, and
    the bytes sent (every replica counts).  The device arrays are copies
    that own their memory: a CPU client may alias a host array it is handed,
    which would keep a cached dataset alive for as long as its pool."""
    arrays = _jit_on(_copies, mesh, split=False)(
        np.asarray(x, stack_dtype(x)), np.asarray(y, np.int32))
    sent = sum(s.data.nbytes for a in arrays for s in a.addressable_shards)
    return arrays, sent


def _copies(x, y):
    return jnp.copy(x), jnp.copy(y)


@functools.lru_cache(maxsize=None)
def _jit_on(fn, mesh, *, split: bool):
    """``fn`` jitted.  Client-sharded, its two outputs land on the client
    mesh: split over the client axis (``split``) or replicated."""
    if mesh is None:
        return jax.jit(fn)
    from repro.launch.mesh import client_axis

    spec = jax.sharding.PartitionSpec(client_axis(mesh) if split else None)
    out = jax.sharding.NamedSharding(mesh, spec)
    return jax.jit(fn, out_shardings=(out, out))


def _row_map(row_lists) -> np.ndarray:
    out = np.full((len(row_lists), max(len(r) for r in row_lists)), -1, np.int32)
    for k, r in enumerate(row_lists):
        out[k, : len(r)] = r
    return out


def _pool(setup: _Setup, mesh) -> tuple[_Pool, int]:
    """The pool this experiment stages from, and the bytes uploaded for it
    now (0 where it was already on the device).

    Shards that are rows of the dataset (``setup.rows_of_data``) are
    gathered from the dataset's own training rows, kept on the device in a
    single-entry cache keyed by the identity of ``x_train`` and
    ``y_train``: the next experiment on the same dataset finds them there.
    The entry holds weak references and goes with the dataset.  Shards a
    poisoning scenario rewrote are concatenated into this experiment's own
    pool, uploaded once.  Client-sharded, a pool is replicated over the
    client mesh."""
    own = getattr(setup, "_own_pool", None)
    if own is not None:
        return own, 0
    if setup.rows_of_data:
        x, y = setup.data.x_train, setup.data.y_train
        entry = _dataset_pool[0]
        if (entry is not None and entry[0]() is x and entry[1]() is y
                and entry[2] == mesh):
            arrays, sent = entry[3], 0
        else:
            arrays, sent = _upload(x, y, mesh)
            _dataset_pool[0] = (
                weakref.ref(x, _forget_dataset_pool),
                weakref.ref(y, _forget_dataset_pool), mesh, arrays)
        row_lists = setup.shard_rows
    else:
        xs = [x for x, _ in setup.poisoned]
        arrays, sent = _upload(
            np.concatenate(xs).astype(stack_dtype(xs[0]), copy=False),
            np.concatenate([y for _, y in setup.poisoned]), mesh)
        ends = np.cumsum([len(x) for x in xs])
        row_lists = [np.arange(e - len(x), e) for e, x in zip(ends, xs)]
    pool = _Pool(*arrays, _row_map(row_lists),
                 np.asarray([len(r) for r in row_lists], np.int32))
    if not setup.rows_of_data:
        setup._own_pool = pool
    return pool, sent


def _take_rows(pool, rows):
    """``pool[rows]``, with a zero row wherever ``rows`` is -1."""
    live = (rows >= 0).reshape(rows.shape + (1,) * (pool.ndim - 1))
    return jnp.where(live, pool[jnp.maximum(rows, 0)], 0)


def _stage_rows(pool_x, pool_y, rows):
    return _take_rows(pool_x, rows), _take_rows(pool_y, rows)


def _fused_data(setup: _Setup, mesh=None) -> FusedData:
    """The one-shot fused engine's inputs: every client, the identity map."""
    K = setup.sim.num_clients
    return _compact_inputs(setup, np.arange(K), K, mesh)[0]


def _client_mesh(sim: SimConfig):
    """The (client,) device mesh of a sharded run, or None (DESIGN.md §4)."""
    if sim.client_shards <= 0:
        return None
    from repro.launch.mesh import make_client_mesh

    return make_client_mesh(sim.client_shards)


def _client_opts_kwargs(mesh) -> dict:
    """make_rule_options kwargs marking the options for a client mesh."""
    if mesh is None:
        return {}
    from repro.launch.mesh import client_axis

    axis = client_axis(mesh)
    return {"client_axis": axis, "client_shards": int(mesh.shape[axis])}


def _make_setup_sim(setup: _Setup, server_cfg: ServerConfig, mesh=None):
    """Fused scan + round body for this experiment's static configuration."""
    sim = setup.sim
    return make_fused_sim(
        setup.workload, setup.engine_config(),
        rule=server_cfg.rule,
        opts=make_rule_options(
            server_cfg, sim.num_clients, **_client_opts_kwargs(mesh)
        ),
        delta_block=server_cfg.delta_block,
        num_clients=sim.num_clients,
        num_rounds=sim.rounds,
        batch_s=setup.batch_s,
        batch_b=setup.batch_b,
        bad_mask=setup.bad_mask,
        alpha0=server_cfg.alpha0,
        beta0=server_cfg.beta0,
        agg_layout=resolve_server_plan(server_cfg).layout,
        client_mesh=mesh,
    )


class FusedInputs(NamedTuple):
    """Everything an EXTERNAL driver of the fused round pipeline needs — the
    serving tier (``repro.serve``) builds its proposal pool and aggregation
    service from this instead of re-deriving shard/batch geometry."""

    workload: object           # ClientWorkload (hashable frozen dataclass)
    engine_cfg: EngineConfig
    data: FusedData            # padded device stacks + n_k + test set
    bad_mask: np.ndarray       # (K,) bool — ground-truth byzantine ids
    batch_s: int               # per-round local steps
    batch_b: int               # minibatch width
    params0: object            # workload.init_params(PRNGKey(sim.seed))


def fused_inputs(
    data: SyntheticClassification, sim: SimConfig, *, workload=None
) -> FusedInputs:
    """Build the fused-engine inputs for this experiment WITHOUT running it —
    the exact same ``_Setup`` the engines use, so an external driver that
    replays rounds through these inputs reproduces the fused trajectory."""
    setup = _Setup(data, sim, workload=workload)
    return FusedInputs(
        workload=setup.workload,
        engine_cfg=setup.engine_config(),
        data=_fused_data(setup),
        bad_mask=setup.bad_mask,
        batch_s=setup.batch_s,
        batch_b=setup.batch_b,
        params0=setup.params0,
    )


def _run_fused(
    setup: _Setup, server_cfg: ServerConfig, eval_every: int, *, eager: bool = False
) -> SimResult:
    sim = setup.sim
    mesh = _client_mesh(sim)
    if eager and mesh is not None:
        raise ValueError("fused_eager has no client-sharded form; use engine='fused'")
    data = _fused_data(setup, mesh)
    scan_fn, round_fn = _make_setup_sim(setup, server_cfg, mesh)

    t_start = time.perf_counter()
    if eager:
        # bit-equivalence reference: the identical round body, one jit
        # dispatch per round instead of one scan over all of them
        step = round_fn
        carry = (
            setup.params0,
            init_server_state(sim.num_clients, server_cfg.alpha0, server_cfg.beta0),
        )
        outs = []
        for rnd in range(sim.rounds):
            carry, out = step(carry, jnp.int32(rnd), jnp.uint32(sim.seed), data)
            outs.append(out)
        params, state = carry
        traj = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)
    else:
        params, state, traj = scan_fn(setup.params0, jnp.uint32(sim.seed), data)
    jax.block_until_ready(traj)
    total = time.perf_counter() - t_start

    errs = np.asarray(traj.test_error, np.float64) * 100.0
    test_error = [
        float(errs[r]) for r in range(sim.rounds)
        if r % eval_every == 0 or r == sim.rounds - 1
    ]
    good_hist = [gm for gm in np.asarray(traj.good_mask)]
    per_round = total / max(sim.rounds, 1)
    # one device program covers all T rounds: per-phase host timings do not
    # exist, so only round_time is populated (uniformly spread)
    return setup.result(
        np.asarray(state.rounds_blocked), test_error, good_hist,
        0.0, 0.0, [per_round] * sim.rounds,
        params, list(np.asarray(traj.similarities)),
    )


# ---------------------------------------------------------------------------
# segmented fused engine — inter-segment compaction of blocked clients
# ---------------------------------------------------------------------------


def _compact_inputs(setup: _Setup, kept: np.ndarray, bucket: int, mesh=None,
                    stage: dict | None = None):
    """Stage the kept clients' device inputs in a ``bucket``-row layout.

    ``kept`` is the index map of still-live original client ids (ascending);
    pad rows — the tail up to ``bucket``, plus any ``-1`` slots the per-shard
    plan interleaved at shard-block tails — carry zero shards of length 1,
    zero ``n_k``, benign ``bad`` and id 0 — all inert, since their
    server-state rows are blocked.

    The client stacks are gathered on the device from the resident pool
    (:func:`_pool`) by a ``(bucket, n_max)`` row map built here, ``-1`` in
    every pad slot: the same values, bit for bit, as
    ``compact_stack(*padded_stack(setup.poisoned), kept, pad_to=bucket)``.
    ``stage``, a span's attributes, gets ``pool`` (``"hit"`` or
    ``"upload"``) and ``h2d_bytes``: the map, the masks and any pool upload.
    """
    kept = np.asarray(kept)
    if bucket < len(kept):
        raise ValueError(
            f"bucket={bucket} is smaller than the {len(kept)} kept client "
            f"rows; refusing to truncate live clients"
        )
    live = kept >= 0
    pool, sent = _pool(setup, mesh)
    rows = np.full((bucket, pool.rows.shape[1]), -1, np.int32)
    rows[: len(kept)][live] = pool.rows[kept[live]]
    # client-sharded, split as place_on_client_mesh places the stacks: each
    # device gathers its own rows from its replica of the pool
    x_c, y_c = _jit_on(_stage_rows, mesh, split=True)(pool.x, pool.y, rows)
    len_c = np.ones((bucket,), np.int32)
    len_c[: len(kept)][live] = pool.lengths[kept[live]]
    n_k_c = np.zeros((bucket,), np.float32)
    n_k_c[: len(kept)][live] = setup.n_k[kept[live]]
    bad_c = np.zeros((bucket,), bool)
    bad_c[: len(kept)][live] = setup.bad_mask[kept[live]]
    ids_c = np.zeros((bucket,), np.uint32)
    ids_c[: len(kept)][live] = kept[live]
    if stage is not None:
        stage["pool"] = "hit" if sent == 0 else "upload"
        stage["h2d_bytes"] = sent + sum(
            a.nbytes for a in (rows, len_c, n_k_c, bad_c, ids_c))
    data = FusedData(
        x=x_c,
        y=y_c,
        lengths=jnp.asarray(len_c),
        n_k=jnp.asarray(n_k_c),
        x_test=setup.x_test,
        y_test=setup.y_test,
    )
    return data, jnp.asarray(bad_c), jnp.asarray(ids_c)


def _segment_fn(setup: _Setup, server_cfg: ServerConfig, seg_len: int,
                mesh=None, bucket_rows: int | None = None,
                train_rows: int | None = None):
    """Segment scan for this experiment's static configuration (cached in
    ``make_fused_segment`` — one trace per (bucket shape, seg_len,
    train_rows))."""
    sim = setup.sim
    return make_fused_segment(
        setup.workload, setup.engine_config(),
        rule=server_cfg.rule,
        opts=make_rule_options(
            server_cfg, sim.num_clients, **_client_opts_kwargs(mesh)
        ),
        delta_block=server_cfg.delta_block,
        num_clients_total=sim.num_clients,
        seg_len=seg_len,
        batch_s=setup.batch_s,
        batch_b=setup.batch_b,
        agg_layout=resolve_server_plan(server_cfg).layout,
        client_mesh=mesh,
        bucket_rows=bucket_rows,
        train_rows=train_rows,
    )


def _train_rows(setup: _Setup, kept: np.ndarray, bucket: int, mesh) -> int:
    """How many rows of the ``(kept, bucket)`` layout the local update runs
    over: its clients that can train (:func:`~repro.fed.engine.trainer_count`;
    pad slots never do).  Client-sharded, every row of the bucket trains."""
    if mesh is not None:
        return bucket
    live = kept >= 0
    return trainer_count(setup.sim.scenario,
                         setup.bad_mask[np.where(live, kept, 0)], live)


def _segment_layout(live: np.ndarray, K: int, n_shards: int, mesh):
    """``(kept, bucket)`` of a segment: the index map of the clients a
    segment carries and its row count.  Unsharded, the live ids fill a pow2
    bucket; client-sharded, compaction is per shard — equal pow2 blocks with
    ``-1`` pads at block tails (``data/sharding.shard_compact_plan``)."""
    if mesh is None:
        return live, pow2_bucket(len(live), K)
    kept, rows = shard_compact_plan(live, n_shards, K // n_shards)
    return kept, rows * n_shards


def _segment_inputs(setup: _Setup, params, state_full, kept, bucket: int, mesh,
                    stage: dict | None = None):
    """A segment's inputs for the ``(kept, bucket)`` layout: the compacted
    data stacks, masks and server state, with params.  Client-sharded, all
    of them are committed to the client mesh
    (:func:`~repro.fed.engine.place_on_client_mesh`).  ``stage`` is as in
    :func:`_compact_inputs`."""
    data_c, bad_c, ids_c = _compact_inputs(setup, kept, bucket, mesh, stage)
    state_c = gather_server_state(state_full, kept, bucket)
    if mesh is None:
        return params, state_c, data_c, bad_c, ids_c
    return place_on_client_mesh(mesh, params, state_c, data_c, bad_c, ids_c)


def first_segment(
    data: SyntheticClassification, sim: SimConfig, server_cfg: ServerConfig
):
    """The program the segmented fused engine runs first, and its arguments.

    Returns ``(segment_fn, args)``: ``segment_fn(*args)`` is the first
    segment call that :func:`simulate` makes for ``sim`` (``engine="fused"``,
    ``segment_rounds > 0``) — the same cached jit and the same argument
    shapes and placement.  A caller can compile it ahead of the run
    (``segment_fn.lower(*args).compile()``), inspect the compiled program,
    and the run then reuses the executable.
    """
    if sim.engine != "fused" or sim.segment_rounds <= 0:
        raise ValueError("first_segment needs engine='fused' and segment_rounds > 0")
    setup = _Setup(data, sim)
    K = sim.num_clients
    mesh = _client_mesh(sim)
    n_shards = max(sim.client_shards, 1) if mesh is not None else 1
    kept, bucket = _segment_layout(np.arange(K), K, n_shards, mesh)
    params, state_c, data_c, bad_c, ids_c = _segment_inputs(
        setup, setup.params0,
        init_server_state(K, server_cfg.alpha0, server_cfg.beta0),
        kept, bucket, mesh,
    )
    seg_fn = _segment_fn(
        setup, server_cfg, min(sim.segment_rounds, sim.rounds), mesh,
        None if mesh is None else bucket // n_shards,
        _train_rows(setup, kept, bucket, mesh),
    )
    return seg_fn, (
        params, state_c, jnp.uint32(sim.seed), data_c, bad_c, ids_c,
        jnp.int32(0),
    )


def _run_fused_segmented(
    setup: _Setup, server_cfg: ServerConfig, eval_every: int
) -> SimResult:
    """The fused simulation as S-round scan segments with host-side
    compaction in between (DESIGN.md §2).

    Between segments the host reads the blocked set (the only device→host
    sync, O(T / S) of them), gathers the still-live clients' ``n_k`` /
    reputation posteriors / attack masks into a dense power-of-two bucket
    via the ``kept`` index map, and re-embeds the compacted ``ServerState``
    into the full-K layout afterwards.  The shard stacks are gathered on
    the device from a resident pool of training rows by a row map the host
    builds from ``kept`` (:func:`_compact_inputs`): no stack is copied on
    the host, and the dataset's rows go to the device once, not once per
    experiment.  Because every
    per-client RNG stream is keyed by original client id and dropped rows
    were mask-zeroed in every reduction, the stitched trajectory is
    bit-identical to the one-shot fused scan — but post-blocking segments pay
    client FLOPs only for ~K_live rows.

    Client-sharded (``sim.client_shards > 0``): compaction is PER SHARD —
    the live ids redistribute contiguously over equal power-of-two shard
    blocks (``data/sharding.shard_compact_plan``), pad slots (``kept ==
    -1``) interleave at shard-block tails, and the segment runs under
    shard_map over the client mesh.  Multi-shard trajectories agree with
    the single-device run numerically (the (D,) psum re-associates one
    summation); a one-shard mesh is bit-identical.
    """
    sim = setup.sim
    K, T, S = sim.num_clients, sim.rounds, sim.segment_rounds
    mesh = _client_mesh(sim)
    n_shards = max(sim.client_shards, 1) if mesh is not None else 1
    seed = jnp.uint32(sim.seed)

    test_error = np.zeros((T,), np.float64)
    good = np.zeros((T, K), bool)
    sims = np.zeros((T, K), np.float32)
    round_times = np.zeros((T,), np.float64)

    params = setup.params0
    # full-K container: holds the frozen state of clients dropped at earlier
    # compactions; the live rows' state lives in ``state_c`` and is scattered
    # back only at bucket boundaries (and once at the end) — the steady-state
    # per-segment host work is a single K_bucket-bool sync
    state_full = init_server_state(K, server_cfg.alpha0, server_cfg.beta0)
    state_c = state_full
    data_c, bad_c, ids_c = None, None, None
    kept = np.arange(K)
    bucket = train_rows = None

    seg_start = 0
    while seg_start < T:
        t0 = time.perf_counter()
        seg_len = min(S, T - seg_start)
        with span("fed.segment", seg_start=seg_start, seg_len=seg_len) as seg:
            with span("fed.segment.layout"):
                if sim.compact:
                    blocked_c = np.asarray(state_c.reputation.blocked)[: len(kept)]
                    # pad slots (kept == -1, sharded layout) are blocked and drop out
                    live = kept[~blocked_c & (kept >= 0)]
                else:
                    live = np.arange(K)
                new_kept, new_bucket = _segment_layout(live, K, n_shards, mesh)
            if bucket != new_bucket:
                # bucket boundary crossed: preserve the rows being dropped, then
                # compact to the smaller layout (the first iteration lands here
                # too, with the identity map at bucket = K and nothing to save)
                with span("fed.segment.stage", bucket=int(new_bucket)) as stage:
                    if bucket is not None:
                        state_full = scatter_server_state(state_full, state_c, kept)
                    bucket, kept = new_bucket, new_kept
                    params, state_c, data_c, bad_c, ids_c = _segment_inputs(
                        setup, params, state_full, kept, bucket, mesh, stage
                    )
                    train_rows = _train_rows(setup, kept, bucket, mesh)
                    stage.update(rows=int((kept >= 0).sum()),
                                 train_rows=train_rows)
            seg.update(bucket=int(bucket), live=len(live))
            with span("fed.segment.call"):
                seg_fn = _segment_fn(
                    setup, server_cfg, seg_len, mesh,
                    None if mesh is None else bucket // n_shards, train_rows,
                )
                params, state_c, traj = seg_fn(
                    params, state_c, seed, data_c, bad_c, ids_c,
                    jnp.int32(seg_start),
                )
            with span("fed.segment.wait"):
                jax.block_until_ready(traj)

            # stitch the (seg_len, bucket) segment outputs into full-K rows via
            # the index map; dropped clients keep the default good_mask = False
            # (they are blocked, exactly what the one-shot scan emits for them)
            with span("fed.segment.stitch"):
                end = seg_start + seg_len
                valid = kept >= 0
                test_error[seg_start:end] = np.asarray(traj.test_error, np.float64)
                good[seg_start:end, kept[valid]] = (
                    np.asarray(traj.good_mask)[:, np.nonzero(valid)[0]]
                )
                sims[seg_start:end, kept[valid]] = (
                    np.asarray(traj.similarities)[:, np.nonzero(valid)[0]]
                )
        round_times[seg_start:end] = (time.perf_counter() - t0) / seg_len
        seg_start = end

    with span("fed.result"):
        state_full = scatter_server_state(state_full, state_c, kept)
        errs = test_error * 100.0
        test_error_list = [
            float(errs[r]) for r in range(T) if r % eval_every == 0 or r == T - 1
        ]
        good_hist = [gm for gm in good]
        return setup.result(
            np.asarray(state_full.rounds_blocked), test_error_list, good_hist,
            0.0, 0.0, list(round_times), params, list(sims),
        )


@dataclasses.dataclass
class SweepResult:
    """Per-seed trajectories/detection stats of a vmapped fused sweep."""

    seeds: np.ndarray                # (n,)
    test_error: np.ndarray           # (n, T) percent, every round
    good_mask_history: np.ndarray    # (n, T, K) bool
    blocked_round: np.ndarray        # (n, K) 1-indexed, -1 = never
    bad_clients: np.ndarray          # (n_bad,) indices (fixed across seeds)
    detection_rate: np.ndarray       # (n,)
    mean_rounds_to_block: np.ndarray # (n,)


def run_sweep(
    data: SyntheticClassification,
    sim: SimConfig,
    server_cfg: ServerConfig,
    seeds,
) -> SweepResult:
    """DEPRECATED — call :func:`repro.fed.api.run` with ``seeds=`` instead.

    Thin shim over :func:`sweep` (bit-identical trajectories), kept so
    existing callers keep working with a warning.
    """
    warnings.warn(
        "run_sweep is deprecated; use repro.fed.api.run(workload, sim, "
        "server, data=data, seeds=seeds) instead",
        DeprecationWarning,
        stacklevel=2,
    )
    return sweep(data, sim, server_cfg, seeds)


def sweep(
    data: SyntheticClassification,
    sim: SimConfig,
    server_cfg: ServerConfig,
    seeds,
) -> SweepResult:
    """Run the fused simulation for every seed as ONE vmapped device program.

    The shard split (and data-level poisoning) is built once from
    ``sim.seed`` and shared across the sweep; each sweep seed drives the
    model init, the device minibatch stream, and the attack-noise stream.
    Replaces the Python-loop-over-seeds grid with a single jit dispatch —
    the entry point for adaptive-attack and prior-sensitivity sweeps.

    With ``sim.segment_rounds > 0`` the sweep runs segmented, compacting on
    the UNION of live clients across seeds between segments (a client stays
    resident while any seed still has it unblocked — per-seed masks handle
    the rest, so each seed's trajectory stays bit-identical to its
    unsegmented run).
    """
    setup = _Setup(data, sim)
    if sim.client_shards > 0:
        raise ValueError(
            "run_sweep is not wired for the client-sharded engine; "
            "set client_shards=0 for sweeps"
        )
    if sim.segment_rounds > 0:
        return _run_sweep_segmented(setup, server_cfg, seeds)
    fdata = _fused_data(setup)
    scan_fn, _ = _make_setup_sim(setup, server_cfg)
    _, state, traj = sweep_fused_sim(scan_fn, setup.workload, seeds, fdata)
    jax.block_until_ready(traj)

    return _sweep_result(setup, seeds, np.asarray(state.rounds_blocked),
                         np.asarray(traj.test_error, np.float64),
                         np.asarray(traj.good_mask))


def _sweep_result(setup, seeds, blocked_round, test_error, good_mask):
    stats = [detection_stats(br, setup.bad) for br in blocked_round]
    return SweepResult(
        seeds=np.asarray(seeds),
        test_error=test_error * 100.0,
        good_mask_history=good_mask,
        blocked_round=blocked_round,
        bad_clients=setup.bad,
        detection_rate=np.asarray([r for r, _ in stats]),
        mean_rounds_to_block=np.asarray([m for _, m in stats]),
    )


def _run_sweep_segmented(
    setup: _Setup, server_cfg: ServerConfig, seeds
) -> SweepResult:
    """Segmented + compacted seed sweep: the per-segment scan is vmapped over
    the seed axis, and compaction drops a client only once it is blocked in
    EVERY seed (union of live sets — the index map must be shared across the
    vmapped program, whose shapes are common to all seeds)."""
    sim = setup.sim
    K, T, S = sim.num_clients, sim.rounds, sim.segment_rounds
    n = len(seeds)
    seeds_u32 = jnp.asarray(np.asarray(seeds, np.uint32))

    params = jax.vmap(
        lambda s: setup.workload.init_params(jax.random.PRNGKey(s))
    )(seeds_u32)
    state0 = init_server_state(K, server_cfg.alpha0, server_cfg.beta0)
    state_full = jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l[None], (n,) + l.shape), state0
    )
    state_c = state_full
    data_c, bad_c, ids_c = None, None, None
    kept = np.arange(K)
    bucket = None

    test_error = np.zeros((n, T), np.float64)
    good = np.zeros((n, T, K), bool)

    seg_start = 0
    while seg_start < T:
        seg_len = min(S, T - seg_start)
        if sim.compact:
            # (n, K_bucket) -> live iff unblocked in ANY seed
            blocked_c = np.asarray(state_c.reputation.blocked)[:, : len(kept)]
            live = kept[~blocked_c.all(axis=0)]
        else:
            live = np.arange(K)
        new_bucket = pow2_bucket(len(live), K)
        if bucket != new_bucket:
            if bucket is not None:
                state_full = scatter_server_state(state_full, state_c, kept)
            bucket, kept = new_bucket, live
            data_c, bad_c, ids_c = _compact_inputs(setup, kept, bucket)
            state_c = gather_server_state(state_full, kept, bucket)
        seg_fn = _segment_fn(setup, server_cfg, seg_len,
                             train_rows=_train_rows(setup, kept, bucket, None))
        params, state_c, traj = jax.vmap(
            seg_fn, in_axes=(0, 0, 0, None, None, None, None)
        )(params, state_c, seeds_u32, data_c, bad_c, ids_c, jnp.int32(seg_start))
        jax.block_until_ready(traj)

        end = seg_start + seg_len
        test_error[:, seg_start:end] = np.asarray(traj.test_error, np.float64)
        good[:, seg_start:end, kept] = np.asarray(traj.good_mask)[:, :, : len(kept)]
        seg_start = end

    state_full = scatter_server_state(state_full, state_c, kept)
    return _sweep_result(
        setup, seeds, np.asarray(state_full.rounds_blocked), test_error, good
    )
