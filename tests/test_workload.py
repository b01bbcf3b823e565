"""ClientWorkload protocol tests (DESIGN.md §Workload).

The load-bearing property: routing the paper DNN through the workload seam
changes NOTHING — ``DnnWorkload``'s fused trajectory is bit-identical to an
independent reference that spells out the pre-refactor round body directly
(``local_sgd(dnn_loss, ...)``, identity proposal space, ``pack_spec(params)``)
with no workload layer in sight, across every registered rule and the
update-level attack matrix, including rounds where blocking fires.

The LoRA side: the adapter codec round-trips through the packed aggregation
buffer exactly, adapter-shaped trees respect the dispatch retrace budget,
and the tiny end-to-end federated LLM simulation blocks its byzantine
clients while aggregating < 5% of the model's parameters.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attacks import UPDATE_ATTACK_SCENARIOS, apply_update_attack
from repro.core import RuleOptions
from repro.core.baselines import RULES, _dispatch_tree_jit, dispatch_rule
from repro.fed.client import local_sgd
from repro.fed.api import run
from repro.fed.dnn import dnn_error, dnn_loss, init_dnn
from repro.fed.engine import (
    _BATCH_STREAM,
    EngineConfig,
    FusedData,
    client_keys_traced,
    make_fused_segment,
    make_fused_sim,
)
from repro.fed.server import (
    ServerConfig,
    init_server_state,
    make_rule_options,
    server_step,
)
from repro.fed.simulator import SimConfig
from repro.fed.workload import (
    ADAPTER_CODEC,
    DnnWorkload,
    TransformerLoraWorkload,
    get_workload,
    init_lora_adapters,
)
from repro.utils.trees import (
    pack_spec,
    pack_stack,
    tree_broadcast_clients,
    tree_select_rows,
    unpack_stack,
)

# reference geometry — small enough that every (rule, scenario) case compiles
# and runs in a couple of seconds on CPU
K, N, DIM, OUT = 5, 20, 10, 3
ROUNDS, BATCH_S, BATCH_B = 8, 2, 4
SIZES = (DIM, 6, OUT)
SEED = 7
# Beta(1,1) start: at this seed AFA keeps every client in round 0 and
# screens byzantine client 0 out from round 1 on; six bad rounds after the
# good one push betainc(2, 7, 0.5) past 0.95, so blocking FIRES inside the
# 8-round window and the bit-identity property covers the blocked regime,
# not just the screening one
ALPHA0 = BETA0 = 1.0


def _fused_data(seed: int = 0) -> FusedData:
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(K, N, DIM)).astype(np.float32)
    y = rng.integers(0, OUT, size=(K, N)).astype(np.int32)
    xt = rng.normal(size=(16, DIM)).astype(np.float32)
    yt = rng.integers(0, OUT, size=(16,)).astype(np.int32)
    return FusedData(
        x=jnp.asarray(x), y=jnp.asarray(y),
        lengths=jnp.full((K,), N, jnp.int32),
        n_k=jnp.full((K,), N, jnp.float32),
        x_test=jnp.asarray(xt), y_test=jnp.asarray(yt),
    )


def _bad_mask() -> np.ndarray:
    bad = np.zeros((K,), bool)
    bad[:2] = True
    return bad


# ---------------------------------------------------------------------------
# 1. local_update is literally local_sgd
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dropout", [False, True])
def test_dnn_local_update_is_local_sgd(dropout):
    """DnnWorkload.local_update == local_sgd(dnn_loss, ...) bit for bit: the
    protocol hop adds no arithmetic."""
    wl = DnnWorkload(SIZES)
    cfg = EngineConfig(lr=0.1, momentum=0.9, dropout=dropout)
    for seed in (0, 1, 2):
        key = jax.random.PRNGKey(seed)
        kp, kb, kt = jax.random.split(key, 3)
        params = init_dnn(kp, SIZES)
        batches = {
            "x": jax.random.normal(kb, (BATCH_S, BATCH_B, DIM)),
            "y": jax.random.randint(kb, (BATCH_S, BATCH_B), 0, OUT),
        }
        got = wl.local_update(cfg, params, batches, kt)
        want = local_sgd(
            dnn_loss, params, batches, kt,
            lr=cfg.lr, momentum=cfg.momentum, dropout=dropout,
        )
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# 2. fused trajectory through the protocol == pre-refactor round body
# ---------------------------------------------------------------------------


def _reference_scan(cfg: EngineConfig, rule: str, opts: RuleOptions,
                    delta_block: float, bad: np.ndarray):
    """The PRE-REFACTOR fused simulation, spelled out with the DNN hard-wired
    exactly as the engine had it before the workload seam existed: vmapped
    ``local_sgd(dnn_loss, ...)``, proposals in full-parameter space,
    ``pack_spec(params)`` as the aggregation layout, ``dnn_error`` on the
    carry.  Independent of ``repro.fed.workload`` by construction."""
    bad_j = jnp.asarray(bad)
    ids = jnp.arange(K, dtype=jnp.uint32)
    skip_bad = cfg.scenario in UPDATE_ATTACK_SCENARIOS

    def body(carry, rnd, seed, data: FusedData):
        params, state = carry
        mask0 = ~state.reputation.blocked
        train_mask = mask0 & ~bad_j if skip_bad else mask0

        base = jax.random.PRNGKey(seed)
        offsets = jnp.asarray(rnd).astype(jnp.uint32) * jnp.uint32(K) + ids
        bbase = jax.random.fold_in(base, _BATCH_STREAM)
        bkeys = jax.vmap(lambda o: jax.random.fold_in(bbase, o))(offsets)
        idx = jax.vmap(
            lambda k, n: jax.random.randint(k, (BATCH_S, BATCH_B), 0, n)
        )(bkeys, data.lengths)
        batch = {
            "x": jax.vmap(lambda xs, ix: xs[ix])(data.x, idx),
            "y": jax.vmap(lambda ys, ix: ys[ix])(data.y, idx),
        }

        def train_one(cbatch, ckey):
            return local_sgd(
                dnn_loss, params, cbatch, ckey,
                lr=cfg.lr, momentum=cfg.momentum, dropout=cfg.dropout,
            )

        proposals = jax.vmap(train_one)(
            batch, client_keys_traced(seed, rnd, ids, K)
        )
        proposals = tree_select_rows(
            train_mask, proposals, tree_broadcast_clients(params, K)
        )
        proposals = apply_update_attack(
            cfg.scenario, proposals, params, bad_j & mask0, mask0 & ~bad_j,
            jax.random.fold_in(base, rnd),
            byzantine_scale=cfg.byzantine_scale, z_max=cfg.alie_z_max,
            eps=cfg.ipm_eps, client_ids=ids,
        )

        pspec = pack_spec(params)
        state, res = server_step(
            state, pack_stack(proposals, pspec), data.n_k, mask0,
            rule=rule, opts=opts, delta_block=delta_block, layout="packed",
        )
        aggregate = unpack_stack(res.aggregate, pspec)
        params = jax.tree_util.tree_map(
            lambda prev, new: jnp.where(res.all_blocked, prev, new),
            params, aggregate,
        )
        err = dnn_error(params, data.x_test, data.y_test)
        return (params, state), (err, res.good_mask, state.reputation.blocked)

    @jax.jit
    def scan_fn(params0, seed, data: FusedData):
        state0 = init_server_state(K, ALPHA0, BETA0)
        (params, state), traj = jax.lax.scan(
            lambda c, r: body(c, r, seed, data),
            (params0, state0),
            jnp.arange(ROUNDS, dtype=jnp.int32),
        )
        return params, state, traj

    return scan_fn


BIT_IDENTITY_CASES = [(r, "byzantine") for r in sorted(RULES)] + [
    ("afa", "alie"), ("afa", "ipm"),
]


@pytest.mark.parametrize("rule,scenario", BIT_IDENTITY_CASES)
def test_dnn_workload_bit_identical_to_prerefactor_round_body(rule, scenario):
    """Every registered rule (under byzantine) plus AFA under alie/ipm: the
    DnnWorkload-through-protocol fused scan reproduces the hard-wired
    reference trajectory BIT FOR BIT — test error, per-round screening
    masks, and the blocked set after every round."""
    cfg = EngineConfig(scenario=scenario, lr=0.1, momentum=0.9, dropout=True)
    scfg = ServerConfig(rule=rule, num_clients=K, num_byzantine=2, trim=1)
    opts = make_rule_options(scfg, K)
    bad = _bad_mask()
    data = _fused_data()

    ref_fn = _reference_scan(cfg, rule, opts, scfg.delta_block, bad)
    scan_fn, _ = make_fused_sim(
        DnnWorkload(SIZES), cfg, rule=rule, opts=opts,
        delta_block=scfg.delta_block, num_clients=K, num_rounds=ROUNDS,
        batch_s=BATCH_S, batch_b=BATCH_B, bad_mask=bad,
        alpha0=ALPHA0, beta0=BETA0, agg_layout="packed",
    )

    params0 = init_dnn(jax.random.PRNGKey(SEED), SIZES)
    r_params, _, (r_err, r_good, r_blocked) = ref_fn(params0, SEED, data)
    w_params, _, traj = scan_fn(params0, SEED, data)

    np.testing.assert_array_equal(np.asarray(traj.test_error), np.asarray(r_err))
    np.testing.assert_array_equal(np.asarray(traj.good_mask), np.asarray(r_good))
    np.testing.assert_array_equal(np.asarray(traj.blocked), np.asarray(r_blocked))
    for a, b in zip(jax.tree_util.tree_leaves(w_params),
                    jax.tree_util.tree_leaves(r_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if rule == "afa" and scenario == "byzantine":
        # the property must cover the blocked regime, not hold vacuously
        # (alie/ipm are evasive by design — no blocking guarantee there)
        assert np.asarray(traj.blocked)[-1].any(), "blocking never fired"


def test_dnn_workload_segmented_bit_equals_one_shot():
    """The segmented fused engine through the protocol (the entry point the
    simulator's compaction drives) matches the one-shot scan bit for bit,
    across a segment boundary that lands mid-blocking."""
    cfg = EngineConfig(scenario="byzantine", lr=0.1, momentum=0.9, dropout=True)
    scfg = ServerConfig(rule="afa", num_clients=K, num_byzantine=2, trim=1)
    opts = make_rule_options(scfg, K)
    bad = _bad_mask()
    data = _fused_data()
    wl = DnnWorkload(SIZES)

    scan_fn, _ = make_fused_sim(
        wl, cfg, rule="afa", opts=opts, delta_block=scfg.delta_block,
        num_clients=K, num_rounds=ROUNDS, batch_s=BATCH_S, batch_b=BATCH_B,
        bad_mask=bad, alpha0=ALPHA0, beta0=BETA0,
    )
    seg_fn = make_fused_segment(
        wl, cfg, rule="afa", opts=opts, delta_block=scfg.delta_block,
        num_clients_total=K, seg_len=ROUNDS // 2,
        batch_s=BATCH_S, batch_b=BATCH_B,
    )

    params0 = wl.init_params(jax.random.PRNGKey(SEED))
    _, _, traj = scan_fn(params0, SEED, data)

    params, state = params0, init_server_state(K, ALPHA0, BETA0)
    ids = jnp.arange(K, dtype=jnp.uint32)
    pieces = []
    for start in (0, ROUNDS // 2):
        params, state, seg_traj = seg_fn(
            params, state, SEED, data, jnp.asarray(bad), ids, start
        )
        pieces.append(seg_traj)

    for field in ("test_error", "good_mask", "blocked"):
        got = np.concatenate([np.asarray(getattr(p, field)) for p in pieces])
        np.testing.assert_array_equal(got, np.asarray(getattr(traj, field)))


# ---------------------------------------------------------------------------
# 3. LoRA adapter codec: packed-buffer round trip
# ---------------------------------------------------------------------------


def _toy_adapter_stack(seed: int = 0):
    """K stacked adapter proposals over a fake 2-layer attention stack."""
    layers = {
        "attn": {
            "wq": jnp.zeros((2, 8, 8), jnp.float32),
            "wo": jnp.zeros((2, 8, 8), jnp.float32),
        },
        "mlp": {"w1": jnp.zeros((2, 8, 16), jnp.float32)},
    }
    adapters0 = init_lora_adapters(
        jax.random.PRNGKey(seed), layers, ("wq", "wo"), rank=2
    )
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), K)
    stacked = jax.vmap(
        lambda k: jax.tree_util.tree_map(
            lambda leaf: leaf + 0.1 * jax.random.normal(
                jax.random.fold_in(k, leaf.size), leaf.shape
            ),
            adapters0,
        )
    )(keys)
    params = {"base": {"layers": layers}, "adapters": adapters0}
    return params, adapters0, stacked


@pytest.mark.parametrize("rule", ["fa", "afa", "comed"])
def test_lora_roundtrip_packed_equals_tree_dispatch(rule):
    """pack_stack -> matrix dispatch -> unpack_stack -> codec.apply equals
    the tree-form dispatch applied directly to the adapter pytree — the
    (K, D_adapter) buffer is a faithful wire format for LoRA proposals."""
    from repro.core.baselines import dispatch_rule_tree

    params, adapters0, stacked = _toy_adapter_stack()
    n_k = jnp.full((K,), 4.0, jnp.float32)
    p_k = jnp.full((K,), 0.5, jnp.float32)
    mask = jnp.ones((K,), bool)
    opts = RuleOptions()

    pspec = pack_spec(adapters0)
    res_m = dispatch_rule(rule, pack_stack(stacked, pspec), n_k, p_k, mask, opts)
    packed_params = ADAPTER_CODEC.apply(params, unpack_stack(res_m.aggregate, pspec))

    res_t = dispatch_rule_tree(rule, stacked, n_k, p_k, mask, opts)
    tree_params = ADAPTER_CODEC.apply(params, res_t.aggregate)

    # the frozen base passes through apply untouched (same objects)
    assert packed_params["base"] is params["base"]
    for a, b in zip(jax.tree_util.tree_leaves(packed_params),
                    jax.tree_util.tree_leaves(tree_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if rule == "afa":
        np.testing.assert_array_equal(
            np.asarray(res_m.good_mask), np.asarray(res_t.good_mask)
        )


def test_adapter_codec_projection_inverts_apply():
    """proposal_of(apply(params, agg)) == agg and apply never touches the
    base: the codec is a section/retraction pair on the adapter sub-tree."""
    params, adapters0, _ = _toy_adapter_stack()
    agg = jax.tree_util.tree_map(lambda leaf: leaf + 1.0, adapters0)
    new_params = ADAPTER_CODEC.apply(params, agg)
    assert new_params["base"] is params["base"]
    got = ADAPTER_CODEC.proposal_of(new_params)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(agg)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(agg)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# 4. adapter-shaped trees respect the dispatch retrace budget
# ---------------------------------------------------------------------------


def test_adapter_tree_dispatch_retrace_bound():
    """Tree dispatch over adapter-shaped stacks retraces once per client
    bucket, never per call — LoRA aggregation inherits the DNN path's
    O(log K) compile budget (repro.analysis contract)."""
    from repro.analysis import audit_jit_cache

    _, adapters0, _ = _toy_adapter_stack()
    opts = RuleOptions()
    calls = []
    for rows in (4, 8):
        stacked = tree_broadcast_clients(adapters0, rows)
        n_k = jnp.full((rows,), 4.0, jnp.float32)
        p_k = jnp.full((rows,), 0.5, jnp.float32)
        mask = jnp.ones((rows,), bool)
        calls.append((
            (stacked, n_k, p_k, mask),
            {"name": "afa", "opts": opts, "layout": "packed"},
        ))
    findings = audit_jit_cache(
        _dispatch_tree_jit, calls, bound=len(calls),
        target="workload.adapter_dispatch",
    )
    bad = [f for f in findings if getattr(f, "severity", "info") != "info"]
    assert not bad, bad


# ---------------------------------------------------------------------------
# 5. end-to-end: federated LLM fine-tuning blocks byzantine clients
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _tiny_lora_workload() -> TransformerLoraWorkload:
    from repro.models import ModelConfig

    cfg = ModelConfig(
        name="t-lora", family="dense", num_layers=2, d_model=32,
        vocab_size=64, num_heads=4, num_kv_heads=2, d_ff=64,
        block_q=16, block_k=16,
    )
    return get_workload("lora", model_cfg=cfg, rank=2)


def test_lora_simulation_blocks_byzantine_on_adapter_buffer():
    """6 clients / 2 byzantine on the tiny transformer: AFA screens the
    attackers out every round and blocks them within the horizon, operating
    on an adapter buffer < 5% of the model's parameters."""
    sim = SimConfig(num_clients=6, bad_frac=2 / 6, scenario="byzantine",
                    rounds=8, local_epochs=2, batch_size=2, seed=0, lr=0.2)
    res = run(_tiny_lora_workload(), sim, samples_per_client=8, seq=16,
              n_test=8)
    blocked = res["blocked"][-1]
    assert blocked[:2].all(), f"byzantine clients not blocked: {blocked}"
    assert not blocked[2:].any(), f"benign client blocked: {blocked}"
    assert (res["rounds_blocked"][:2] > 0).all()
    # screening excludes the attackers from round 0 on
    assert (res["good_frac"] <= 4.0 / 6.0 + 1e-6).all()
    assert res["adapter_fraction"] < 0.05, res["adapter_fraction"]
    err = res["test_error"]
    assert np.isfinite(err).all() and (err >= 0).all() and (err <= 1).all()


def test_lora_proposal_dims_and_delta_spec():
    """delta_spec is the adapter layout: proposal_dim counts exactly the
    A/B leaves and the packed row length matches it."""
    wl = _tiny_lora_workload()
    params = wl.init_params(jax.random.PRNGKey(0))
    d_adapter = wl.proposal_dim(params)
    d_total = wl.param_dim(params)
    assert 0 < d_adapter < d_total
    want = sum(
        int(np.prod(leaf.shape))
        for leaf in jax.tree_util.tree_leaves(params["adapters"])
    )
    assert d_adapter == want
    spec = wl.delta_spec(params)
    packed = pack_stack(tree_broadcast_clients(params["adapters"], 3), spec)
    assert packed.shape == (3, d_adapter)
