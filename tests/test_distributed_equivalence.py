"""Multi-device equivalence: the sharded federated round on a 2x2 CPU mesh
produces the same aggregate and reputation as the single-device reference.

Runs in a subprocess (the forced device count must not leak into the suite).
"""

import os
import subprocess
import sys

import jax

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core import AFAConfig
from repro.core.reputation import init_reputation
from repro.fed.distributed import FedRoundConfig, make_fed_round
from repro.launch.mesh import make_test_mesh, data_axes
from repro.launch.sharding import shard_params_tree, batch_pspec
from repro.models import ModelConfig, build_model
from jax.sharding import NamedSharding, PartitionSpec as P

cfg = ModelConfig(name="eq", family="dense", num_layers=2, d_model=32, vocab_size=64,
                  num_heads=4, num_kv_heads=2, d_ff=64, block_q=16, block_k=16,
                  fed_mode="vmap", fed_clients=2)
model = build_model(cfg)
K = 2
rng = np.random.default_rng(0)
batch = {
    "tokens": jnp.asarray(rng.integers(0, 64, (K, 2, 4, 16)), jnp.int32),
    "labels": jnp.asarray(rng.integers(0, 64, (K, 2, 4, 16)), jnp.int32),
}
params = model.init(jax.random.PRNGKey(0))
rep = init_reputation(K)
n_k = jnp.ones((K,), jnp.float32)

# ---- single-device reference (plain jit, no mesh) --------------------------
fr_ref = jax.jit(make_fed_round(model, FedRoundConfig(num_clients=K, local_steps=2, lr=0.05)))
agg_ref, rep_ref, _ = fr_ref(params, rep, n_k, batch)
agg_ref = jax.tree_util.tree_map(np.asarray, agg_ref)

# ---- sharded execution on the 2x2 mesh --------------------------------------
mesh = make_test_mesh(data=2, model=2)
from repro.launch.steps import make_train_step
step = make_train_step(model, mesh, local_steps=2, lr=0.05)
with mesh:
    # place args with the dry-run shardings
    pspecs = shard_params_tree(jax.eval_shape(lambda: params), mesh)
    params_s = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s.sharding), params, pspecs)
    batch_s = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, NamedSharding(mesh, batch_pspec(x.shape, mesh, client_axis=True, per_client_batch=True))),
        batch)
    agg_sh, rep_sh, _ = jax.jit(step)(params_s, rep, n_k, batch_s)
for a, b in zip(jax.tree_util.tree_leaves(agg_ref), jax.tree_util.tree_leaves(agg_sh)):
    np.testing.assert_allclose(a, np.asarray(b), rtol=2e-4, atol=2e-5)
np.testing.assert_array_equal(np.asarray(rep_ref.alpha), np.asarray(rep_sh.alpha))
print("EQUIVALENT")
"""


def test_sharded_fed_round_matches_single_device():
    assert len(jax.devices()) == 1
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, timeout=900,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "EQUIVALENT" in out.stdout


# ---------------------------------------------------------------------------
# client-sharded fused engine: trajectory parity under a 4-way client mesh
# ---------------------------------------------------------------------------

FUSED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.data import make_spambase_like
from repro.fed.api import run as fed_run
from repro.fed.simulator import SimConfig
from repro.fed.server import ServerConfig

K = 20
data = make_spambase_like(n_train=640, n_test=200, dim=24, seed=0)


def run(shards, seg=0):
    # bad_frac = 0.4: all 8 attackers get blocked, shrinking the live set to
    # 12 and the per-shard power-of-two bucket from 5 to 4 rows — the -1
    # padded per-shard compaction runs mid-simulation
    sim = SimConfig(
        num_clients=K, bad_frac=0.4, scenario="byzantine", rounds=16,
        local_epochs=1, batch_size=16, hidden=(8,), engine="fused",
        segment_rounds=seg, compact=seg > 0, client_shards=shards, seed=0,
    )
    cfg = ServerConfig(rule="afa", num_clients=K)
    return fed_run(None, sim, cfg, data=data)


ref = run(0)                 # today's single-device one-shot fused scan
blocked = np.asarray(ref.blocked_round)
assert (blocked > 0).sum() >= 8, f"attack did not block: {blocked}"

# shard count 1 must degenerate to the unsharded code path bit for bit
one = run(1)
assert np.array_equal(ref.test_error, one.test_error), "1-shard error drifted"
assert np.array_equal(
    np.stack(ref.good_mask_history), np.stack(one.good_mask_history)
)
assert np.array_equal(ref.blocked_round, one.blocked_round)
print("ONE_SHARD_BIT_IDENTICAL")

# 4-way client mesh, segmented with per-shard compaction: numerically equal
# trajectories (the (D,) psum re-associates one summation; every discrete
# outcome — screening masks, blocking rounds — must match exactly)
import jax
segment_compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda name, *_, fun_name=None, **__: segment_compiles.append(fun_name)
    if name == "/jax/core/compile/backend_compile_duration"
    and fun_name == "jit(segment_fn)" else None
)
four = run(4, seg=4)
# one program per bucket layout (5 rows a shard, then 4): the first
# segment's inputs sit on the mesh like every later segment's
assert len(segment_compiles) == 2, segment_compiles
np.testing.assert_allclose(
    np.asarray(ref.test_error), np.asarray(four.test_error),
    rtol=1e-4, atol=1e-4,
)
assert np.array_equal(
    np.stack(ref.good_mask_history), np.stack(four.good_mask_history)
), "4-shard screening masks drifted"
assert np.array_equal(ref.blocked_round, four.blocked_round)
print("FOUR_SHARD_EQUIVALENT")
"""


# ---------------------------------------------------------------------------
# sharded cross-client attacks: alie/ipm under a client mesh, one psum each
# ---------------------------------------------------------------------------

ATTACK_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.analysis import collective_uses
from repro.attacks import apply_update_attack
from repro.launch.mesh import client_axis, make_client_mesh

K = 16
rng = np.random.default_rng(3)
proposals = {
    "w": jnp.asarray(rng.normal(size=(K, 33, 2)).astype(np.float32)),
    "b": jnp.asarray(rng.normal(size=(K, 7)).astype(np.float32)),
}
w_prev = {
    "w": jnp.zeros((33, 2), jnp.float32), "b": jnp.zeros((7,), jnp.float32)
}
bad = np.zeros((K,), bool); bad[:5] = True
bad = jnp.asarray(bad)
benign = ~bad
key = jax.random.PRNGKey(0)
mesh = make_client_mesh(4)
axis = client_axis(mesh)
row = {"w": P(axis), "b": P(axis)}
rep = {"w": P(), "b": P()}

for scenario in ("alie", "ipm"):
    ref = apply_update_attack(scenario, proposals, w_prev, bad, benign, key)

    def attacked(props, prev, bad_rows, benign_rows):
        return apply_update_attack(
            scenario, props, prev, bad_rows, benign_rows, key, axis_name=axis
        )

    sharded = jax.shard_map(
        attacked, mesh=mesh,
        in_specs=(row, rep, P(axis), P(axis)), out_specs=row,
        check_vma=False,
    )
    got = sharded(proposals, w_prev, bad, benign)
    for a, b in zip(
        jax.tree_util.tree_leaves(ref), jax.tree_util.tree_leaves(got)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )
    # the cross-shard moments contract: ONE fused psum per attack, no other
    # collective anywhere in the attacked shard body
    uses = collective_uses(sharded, proposals, w_prev, bad, benign)
    assert [u.primitive for u in uses] == ["psum"], uses
    print(scenario.upper() + "_SHARDED_ONE_PSUM")
"""


def test_sharded_attacks_match_and_use_one_psum():
    """alie/ipm on a 4-way client mesh match the single-device transforms
    (one-pass vs two-pass moments: allclose) and globalize their benign
    moments with exactly ONE fused psum per attack."""
    assert len(jax.devices()) == 1
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", ATTACK_SCRIPT], capture_output=True, text=True,
        env=env, timeout=900,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ALIE_SHARDED_ONE_PSUM" in out.stdout
    assert "IPM_SHARDED_ONE_PSUM" in out.stdout


FUSED_ATTACK_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.data import make_spambase_like
from repro.fed.api import run as fed_run
from repro.fed.simulator import SimConfig
from repro.fed.server import ServerConfig

K = 16
data = make_spambase_like(n_train=480, n_test=160, dim=24, seed=0)


def run(shards, scenario):
    sim = SimConfig(
        num_clients=K, bad_frac=0.25, scenario=scenario, rounds=8,
        local_epochs=1, batch_size=16, hidden=(8,), engine="fused",
        client_shards=shards, seed=0,
    )
    return fed_run(None, sim, ServerConfig(rule="afa", num_clients=K),
                   data=data)


for scenario in ("alie", "ipm"):
    ref = run(0, scenario)
    four = run(4, scenario)
    np.testing.assert_allclose(
        np.asarray(ref.test_error), np.asarray(four.test_error),
        rtol=1e-4, atol=1e-4,
    )
    assert np.array_equal(
        np.stack(ref.good_mask_history), np.stack(four.good_mask_history)
    ), scenario + " screening masks drifted"
    assert np.array_equal(ref.blocked_round, four.blocked_round), scenario
    print(scenario.upper() + "_FUSED_SHARDED_EQUIVALENT")
"""


def test_client_sharded_attack_matrix_trajectory_parity():
    """The full fused trajectory under alie/ipm (previously a ValueError for
    client_shards > 1) matches the single-device engine on a 4-way client
    mesh: the sharded engine now runs the complete attack matrix."""
    assert len(jax.devices()) == 1
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", FUSED_ATTACK_SCRIPT], capture_output=True,
        text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ALIE_FUSED_SHARDED_EQUIVALENT" in out.stdout
    assert "IPM_FUSED_SHARDED_EQUIVALENT" in out.stdout


def test_client_sharded_fused_trajectory_parity():
    """Fused-scan run under a 4-way client mesh (hierarchical two-stage AFA
    + per-shard compaction) agrees numerically with the single-device
    engine; a 1-shard mesh is bit-identical.  Includes blocking + bucket
    shrink rounds."""
    assert len(jax.devices()) == 1
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", FUSED_SCRIPT], capture_output=True, text=True,
        env=env, timeout=900,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ONE_SHARD_BIT_IDENTICAL" in out.stdout
    assert "FOUR_SHARD_EQUIVALENT" in out.stdout


# ---------------------------------------------------------------------------
# client-sharded staging: the replicated pool and the per-device gather
# ---------------------------------------------------------------------------

STAGING_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.data import compact_stack, make_spambase_like, padded_stack
from repro.fed import FusedData, simulator
from repro.fed.server import ServerConfig
from repro.fed.simulator import SimConfig, simulate
from repro.launch.mesh import client_axis
from repro.utils import spans

K = 20
data = make_spambase_like(n_train=640, n_test=200, dim=24, seed=0)
sim = SimConfig(
    num_clients=K, bad_frac=0.4, scenario="byzantine", rounds=12,
    local_epochs=1, batch_size=16, hidden=(8,), engine="fused",
    segment_rounds=4, compact=True, client_shards=4, seed=0,
)  # all 8 attackers get blocked: 5 rows a shard, then 4
cfg = ServerConfig(rule="afa", num_clients=K)


def host_compact(setup, kept, bucket, mesh=None, stage=None):
    # the stacks as the host built and sent them before device staging
    x, y, lengths = compact_stack(*padded_stack(setup.poisoned), kept,
                                  pad_to=bucket)
    live = kept >= 0
    n_k = np.zeros((bucket,), np.float32)
    bad = np.zeros((bucket,), bool)
    ids = np.zeros((bucket,), np.uint32)
    n_k[: len(kept)][live] = setup.n_k[kept[live]]
    bad[: len(kept)][live] = setup.bad_mask[kept[live]]
    ids[: len(kept)][live] = kept[live]
    fdata = FusedData(jnp.asarray(x), jnp.asarray(y), jnp.asarray(lengths),
                      jnp.asarray(n_k), setup.x_test, setup.y_test)
    return fdata, jnp.asarray(bad), jnp.asarray(ids)


n0 = max((r.span_id for r in spans.records()), default=0)
staged = simulate(data, sim, cfg)
stages = [r for r in spans.records()
          if r.span_id > n0 and r.name == "fed.segment.stage"]
assert [r.attrs["bucket"] for r in stages] == [20, 16], stages
assert [r.attrs["pool"] for r in stages] == ["upload", "hit"], stages

# the pool is the dataset, replicated on all four devices
pool_x = simulator._dataset_pool[0][3][0]
assert pool_x.sharding.is_fully_replicated
assert len(pool_x.sharding.device_set) == 4
assert pool_x.shape == data.x_train.shape

# a compacted layout, -1 slots at shard-block tails: the gathered stacks
# sit on the client mesh as place_on_client_mesh puts them, equal bit for
# bit to the host's
setup = simulator._Setup(data, sim)
mesh = simulator._client_mesh(sim)
live = np.asarray([1, 2, 3, 7, 8, 10, 15])
kept, bucket = simulator._segment_layout(live, K, 4, mesh)
assert (kept == -1).any()
got = simulator._compact_inputs(setup, kept, bucket, mesh)
want = host_compact(setup, kept, bucket)
for g, w in zip((got[0].x, got[0].y), (want[0].x, want[0].y)):
    assert g.sharding.spec == P(client_axis(mesh)), g.sharding
    assert len(g.sharding.device_set) == 4
    assert np.asarray(g).tobytes() == np.asarray(w).tobytes()

# the same sharded run on the host-built stacks: today's trajectory
simulator._compact_inputs = host_compact
host = simulate(data, sim, cfg)
assert np.array_equal(staged.test_error, host.test_error)
assert np.array_equal(np.stack(staged.good_mask_history),
                      np.stack(host.good_mask_history))
assert np.array_equal(staged.blocked_round, host.blocked_round)
for a, b in zip(jax.tree_util.tree_leaves(staged.params),
                jax.tree_util.tree_leaves(host.params)):
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
print("SHARDED_POOL_STAGING_BIT_IDENTICAL")
"""


def test_client_sharded_staging_gathers_from_a_replicated_pool():
    """A 4-shard segmented run stages its client stacks by gathering, on
    each device, from the dataset replicated over the client mesh, and runs
    the trajectory the host-built stacks give, bit for bit."""
    assert len(jax.devices()) == 1
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", STAGING_SCRIPT], capture_output=True,
        text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHARDED_POOL_STAGING_BIT_IDENTICAL" in out.stdout
