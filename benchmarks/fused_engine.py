"""Fused-vs-batched engine comparison plus the segmented-compaction scenario.

The batched engine is one jit per round plus O(T) host work (numpy batch
draws, reputation sync, Python loop control); the fused engine is ONE jit for
the whole T-round simulation (`lax.scan`, device-side batch draws, in-scan
server step).  This benchmark times full simulations under both engines at
K in {10, 50, 200} and reports per-round wall-clock.

The ``compaction`` scenario exercises the segmented fused engine
(``SimConfig.segment_rounds`` + ``compact``): K in {50, 200} with 40%
byzantine clients over T = 60 rounds — AFA blocks the attackers within the
first segment, after which the compacted engine runs its scan on a
power-of-two bucket of the survivors.  Reported: post-blocking per-round
wall-clock of the compacted engine vs the one-shot fused scan (which keeps
paying full-K FLOPs forever), along with the bucket it settled at.  The
scenario also ASSERTS that the compacted trajectory equals the one-shot
fused trajectory bit for bit — compaction must be a pure layout change.

The ``packed`` scenario measures the aggregation hot path alone: one
registry dispatch on a stacked K=200 proposal tree, legacy per-leaf layout
(AFA's native tree form) vs the packed ``(K, D)`` path (one ``pack_stack``
-> matrix rule -> one unpack).  It also ASSERTS that the fused trajectory
under ``agg_layout="packed"`` (pack once per round in the scan body) is
BIT-IDENTICAL to ``agg_layout="tree"`` (pack inside the dispatch) — the
packed threading must be a pure layout change.

The ``kernel`` scenario measures the fused AFA screening kernel (ONE Pallas
launch per aggregation: gram + VMEM-resident screening loop + weighted sum,
``kernels/afa_screen.py``) against the chained per-op kernel launches and
the jnp oracle at K in {50, 200, 512}, D = 2048.  It ASSERTS the launch
counts by jaxpr inspection (fused = 1, chained >= 2, jnp = 0) and — on the
interpret route — that the fused result is BIT-identical (f32) to the jnp
gram reference.

The ``client_scaling`` scenario measures the client-sharded fused engine
(DESIGN.md §4: ``shard_map`` over the dedicated ``client`` mesh axis,
hierarchical two-stage AFA, per-shard power-of-two compaction) against the
single-device one-shot fused scan at K in {10^3, 10^4, 10^5} on an 8-way
host-device mesh (``--xla_force_host_platform_device_count=8`` — on the CPU
spawned as a CPU-only subprocess when the current process has fewer
devices; an accelerator host with fewer chips refuses).  Reported:
steady-state post-blocking rounds/sec for both routes and their ratio.
Honesty note: forced host devices SERIALIZE on the physical cores, so any
replicated work executes once per shard with no wall-clock parallelism
(which is why the O(K log K) screening stats run on shard 0 only — see
``core/afa._afa_aggregate_sharded``) — the measured sharded win comes
purely from per-shard compaction paying FLOPs only for live rows, and
UNDERSTATES what a real multi-chip mesh (parallel shards) would show.  The scenario also asserts
the sharded trajectory numerically equals the single-device one (test error
allclose at 1e-4; blocking rounds exactly equal at K <= 10^4 — the (D,)
psum re-associates one summation, so borderline screening verdicts can
flip at very large K and mask agreement is recorded, not asserted, there).

Emits ``BENCH_fused_engine.json`` at the repo root (machine-readable record
for the acceptance gates: >= 2x fused-vs-batched at K = 50, >= 1.5x
post-blocking compaction speedup at K = 200, and >= 1.3x packed-vs-leaf
aggregation speedup at K = 200, all on CPU) in addition to the usual CSV
rows.  ``benchmarks/check_regression.py`` gates CI on these speedups against
the committed ``BENCH_baseline.json``.  ``--tiny`` runs a seconds-scale
subset for the CI smoke job (including the compaction and packed-layout
bit-exactness asserts at K = 10; the packed dispatch timing stays at K=200 —
it involves no training and is cheap).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os

import numpy as np

from repro.data import make_mnist_like
from repro.fed import ServerConfig, SimConfig
from repro.fed import run as fed_run
from repro.kernels.policy import KernelPlan

OUT_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_fused_engine.json")

# Small-model workload: the fused engine's target regime (ISSUE/DESIGN §2) —
# per-round dispatch + host overhead dominates device compute, which is
# exactly what fusing the T rounds into one scan removes.  At bigger models
# both engines converge to the same device time (see BENCH_round_engine.json
# for the model-scale round itself).
DIM = 32
HIDDEN = (16,)
BATCH = 32
PER_CLIENT = 100  # samples per shard
REPEATS = 3


def _measure(data, K: int, engine: str, rounds: int) -> float:
    """Best median per-round wall time (s) over REPEATS timed runs, after a
    full-length compile warmup.

    All runs use the same T so the fused scan (whose trip count is baked
    into the jit) hits its compile cache on the timed runs; best-of-repeats
    suppresses scheduler noise on small containers.
    """
    base = dict(
        num_clients=K, scenario="clean", rounds=rounds, local_epochs=1,
        batch_size=BATCH, hidden=HIDDEN, dropout=False, seed=0, engine=engine,
    )
    cfg = ServerConfig(rule="afa", num_clients=K)
    fed_run(None, SimConfig(**base), cfg, data=data)  # warmup/compile
    best = float("inf")
    for _ in range(REPEATS):
        res = fed_run(None, SimConfig(**base), cfg, data=data)
        ts = sorted(res.round_times)
        best = min(best, ts[len(ts) // 2])
    return best


# compaction scenario geometry: 40% byzantine, blocked by AFA within the
# first segment (min_rounds_to_block = 5 < SEGMENT), so segments >= 2 run on
# the compacted bucket of survivors
COMPACT_BAD_FRAC = 0.4
COMPACT_SEGMENT = 10


def _compact_sim(K: int, rounds: int, **kw) -> SimConfig:
    return SimConfig(
        num_clients=K, bad_frac=COMPACT_BAD_FRAC, scenario="byzantine",
        rounds=rounds, local_epochs=1, batch_size=BATCH, hidden=HIDDEN,
        dropout=False, seed=0, engine="fused", **kw,
    )


def _assert_bit_exact(base, seg, K: int) -> None:
    """Compaction must be a pure layout change: identical trajectories."""
    np.testing.assert_array_equal(
        np.asarray(base.test_error), np.asarray(seg.test_error),
        err_msg=f"compaction changed test_error at K={K}",
    )
    np.testing.assert_array_equal(
        np.stack(base.good_mask_history), np.stack(seg.good_mask_history),
        err_msg=f"compaction changed good_mask at K={K}",
    )
    np.testing.assert_array_equal(
        base.blocked_round, seg.blocked_round,
        err_msg=f"compaction changed blocking at K={K}",
    )


def run_compaction(tiny: bool = False) -> tuple[list[dict], list[dict]]:
    """Post-blocking per-round speedup of the segmented+compacted fused
    engine over the one-shot fused scan, plus the bit-exactness assert.

    AFA blocks the byzantine 40% inside segment 0, so the bucket shrinks at
    the segment 0 -> 1 boundary and segment 1 carries the one-time compaction
    transition (host gather + device puts, amortized O(log K) times per run);
    T >= 3 * SEGMENT keeps the measured LAST segment in the steady state.
    """
    ks, rounds = ([10], 30) if tiny else ([50, 200], 60)
    rows, record = [], []
    for K in ks:
        data = make_mnist_like(n_train=K * PER_CLIENT, n_test=200, dim=DIM)
        cfg = ServerConfig(rule="afa", num_clients=K)
        base_sim = _compact_sim(K, rounds)
        seg_sim = _compact_sim(
            K, rounds, segment_rounds=COMPACT_SEGMENT, compact=True
        )

        # correctness first (also the compile warmup): pure layout change
        base = fed_run(None, base_sim, cfg, data=data)
        seg = fed_run(None, seg_sim, cfg, data=data)
        _assert_bit_exact(base, seg, K)
        n_blocked = int((seg.blocked_round > 0).sum())

        # timing: post-blocking rounds only.  The one-shot scan has uniform
        # per-round cost; the segmented engine's steady state is segments
        # >= 2 (segment 1 pays the one-time compaction transition).  Best-of
        # estimators throughout — per-round cost is scheduler-noisy on small
        # CPU containers (2 cores here), and min over repeated fixed-shape
        # runs is the standard denoiser (cf. timeit).
        t_base = t_seg = float("inf")
        n_segs = rounds // COMPACT_SEGMENT
        for _ in range(REPEATS):
            b = fed_run(None, dataclasses.replace(base_sim), cfg, data=data)
            s = fed_run(None, dataclasses.replace(seg_sim), cfg, data=data)
            ts_b = sorted(b.round_times)
            t_base = min(t_base, ts_b[len(ts_b) // 2])
            steady = [
                float(np.mean(s.round_times[i * COMPACT_SEGMENT:(i + 1) * COMPACT_SEGMENT]))
                for i in range(2, n_segs)
            ]
            t_seg = min(t_seg, min(steady))
        speedup = t_base / max(t_seg, 1e-9)
        from repro.data import pow2_bucket

        bucket = pow2_bucket(K - n_blocked, K)
        rows.append({
            "name": f"fused_engine/compaction/K{K}/post_block_speedup",
            "us_per_call": round(t_seg * 1e6, 1),
            "derived": f"compacted={speedup:.2f}x_vs_fused_bucket{bucket}",
        })
        record.append({
            "K": K,
            "bad_frac": COMPACT_BAD_FRAC,
            "rounds": rounds,
            "segment_rounds": COMPACT_SEGMENT,
            "blocked_clients": n_blocked,
            "bucket_after_blocking": bucket,
            "fused_round_s": round(t_base, 6),
            "compacted_post_block_round_s": round(t_seg, 6),
            "post_block_speedup": round(speedup, 2),
            "bit_exact": True,
        })
    return rows, record


# packed-scenario geometry: dispatch timing always at the acceptance point
# K = 200 (a single registry dispatch on the tiny bench model — no training,
# cheap even for CI); the layout bit-exactness assert runs a short fused sim
PACKED_K = 200
PACKED_LIVE_FRAC = 0.9  # ~10% of clients masked out, as after some blocking


def run_packed(tiny: bool = False) -> tuple[list[dict], list[dict]]:
    """Per-round aggregation speedup of the packed (K, D) path over the
    legacy per-leaf dispatch, plus the packed-layout bit-exactness assert.

    Timing compares ONE tree dispatch (the per-round aggregation unit) of
    the paper's rule (AFA, iterative variant) on a stacked K = 200 proposal
    tree shaped like the bench model: ``layout="leaf"`` walks AFA's native
    per-leaf contractions, ``layout="packed"`` packs once and runs the
    matrix form on the contiguous buffer.  Best-of-REPEATS medians, like the
    engine scenarios.
    """
    import jax.numpy as jnp

    from benchmarks.common import timeit
    from repro.core import RuleOptions, dispatch_rule_tree
    from repro.utils.trees import pack_spec

    rng = np.random.default_rng(0)
    K = PACKED_K
    sizes = (DIM, *HIDDEN, 1)
    stacked = {}
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        stacked[f"w{i}"] = jnp.asarray(rng.normal(size=(K, a, b)).astype(np.float32))
        stacked[f"b{i}"] = jnp.asarray(rng.normal(size=(K, b)).astype(np.float32))
    D = pack_spec(stacked, stacked=True).dim
    n_k = jnp.full((K,), float(PER_CLIENT), jnp.float32)
    p_k = jnp.full((K,), 0.5, jnp.float32)
    mask = jnp.asarray(rng.uniform(size=K) < PACKED_LIVE_FRAC)
    opts = RuleOptions()

    t_leaf = t_packed = float("inf")
    for _ in range(REPEATS):
        t_leaf = min(t_leaf, timeit(
            lambda: dispatch_rule_tree("afa", stacked, n_k, p_k, mask, opts,
                                       layout="leaf"), warmup=1, iters=10))
        t_packed = min(t_packed, timeit(
            lambda: dispatch_rule_tree("afa", stacked, n_k, p_k, mask, opts,
                                       layout="packed"), warmup=1, iters=10))
    speedup = t_leaf / max(t_packed, 1e-9)

    # layout bit-exactness: pack-once-per-round in the scan body ("packed")
    # vs pack-inside-dispatch ("tree") is a pure layout change — identical
    # fused trajectories, bit for bit, on a byzantine workload with blocking
    K_sim, rounds = 10, (8 if tiny else 12)
    data = make_mnist_like(n_train=K_sim * PER_CLIENT, n_test=200, dim=DIM)
    sim = SimConfig(
        num_clients=K_sim, bad_frac=COMPACT_BAD_FRAC, scenario="byzantine",
        rounds=rounds, local_epochs=1, batch_size=BATCH, hidden=HIDDEN,
        dropout=False, seed=0, engine="fused",
    )
    res_p = fed_run(None, sim, ServerConfig(
        rule="afa", num_clients=K_sim,
        kernel_plan=KernelPlan(layout="packed")), data=data)
    res_t = fed_run(None, dataclasses.replace(sim), ServerConfig(
        rule="afa", num_clients=K_sim,
        kernel_plan=KernelPlan(layout="tree")), data=data)
    _assert_bit_exact(res_p, res_t, K_sim)

    rows = [
        {"name": f"fused_engine/packed/K{K}/afa_leaf", "us_per_call": round(t_leaf * 1e6, 1), "derived": ""},
        {"name": f"fused_engine/packed/K{K}/afa_packed", "us_per_call": round(t_packed * 1e6, 1), "derived": ""},
        {"name": f"fused_engine/packed/K{K}/agg_speedup", "us_per_call": "", "derived": f"packed={speedup:.2f}x_vs_leaf_D{D}"},
    ]
    record = [{
        "K": K,
        "D": D,
        "rule": "afa",
        "live_frac": PACKED_LIVE_FRAC,
        "leaf_agg_s": round(t_leaf, 6),
        "packed_agg_s": round(t_packed, 6),
        "agg_speedup": round(speedup, 2),
        "bit_exact": True,
    }]
    return rows, record


# client-scaling geometry: huge-K federated population, tiny model — the
# client-sharded engine's target regime.  32 samples/client at batch 8 gives
# 4 local SGD steps per round, enough per-shard compute for the sharded
# route's fixed per-round costs to amortize.  40% byzantine: AFA blocks the
# attackers inside segment 0, after which the 8 shards each compact to a
# power-of-two row bucket (K=10^4 -> 8*1024 rows = 0.82x FLOPs, K=10^5 ->
# 8*8192 = 0.66x; K=10^3's live count pads back to the full cap — the curve
# shows WHERE sharding starts paying, not that it always does).
CS_SHARDS = 8
CS_DIM = 16
CS_HIDDEN = (8,)
CS_BATCH = 8
CS_PER_CLIENT = 32
CS_ROUNDS = 16
CS_SEGMENT = 4
CS_BAD_FRAC = 0.4
CS_REPEATS = 2


def _cs_sim(K: int, **kw) -> SimConfig:
    return SimConfig(
        num_clients=K, bad_frac=CS_BAD_FRAC, scenario="byzantine",
        rounds=CS_ROUNDS, local_epochs=1, batch_size=CS_BATCH,
        hidden=CS_HIDDEN, dropout=False, seed=0, engine="fused", **kw,
    )


def _client_scaling_core(tiny: bool) -> tuple[list[dict], list[dict]]:
    """The in-process client-scaling measurement; requires >= CS_SHARDS jax
    devices (on the CPU, the public entry point ``run_client_scaling``
    spawns this in a CPU-only subprocess with forced host devices when the
    current process has too few)."""
    import jax

    assert jax.device_count() >= CS_SHARDS, jax.device_count()
    ks = [160] if tiny else [1_000, 10_000, 100_000]
    rows, record = [], []
    for K in ks:
        data = make_mnist_like(n_train=K * CS_PER_CLIENT, n_test=200, dim=CS_DIM)
        cfg = ServerConfig(rule="afa", num_clients=K)
        base_sim = _cs_sim(K)
        shard_sim = _cs_sim(
            K, segment_rounds=CS_SEGMENT, compact=True, client_shards=CS_SHARDS
        )

        # correctness first (also the compile warmup): the sharded segmented
        # trajectory must match the single-device one-shot scan
        base = fed_run(None, base_sim, cfg, data=data)
        shard = fed_run(None, shard_sim, cfg, data=data)
        np.testing.assert_allclose(
            np.asarray(base.test_error), np.asarray(shard.test_error),
            rtol=1e-4, atol=1e-4,
            err_msg=f"sharded test_error drifted at K={K}",
        )
        masks_equal = bool(np.array_equal(
            np.stack(base.good_mask_history), np.stack(shard.good_mask_history)
        ))
        blocked_equal = bool(np.array_equal(base.blocked_round, shard.blocked_round))
        if K <= 10_000:
            assert blocked_equal, f"sharded blocking diverged at K={K}"
        if tiny:
            assert masks_equal, "sharded screening masks diverged at tiny K"
        n_blocked = int((shard.blocked_round > 0).sum())

        # timing: steady-state post-blocking rounds.  The one-shot scan has
        # uniform per-round cost (median round); the sharded segmented
        # engine's steady state is segments >= 2 (segment 1 pays the
        # one-time per-shard compaction transition).  Best-of-CS_REPEATS.
        t_base = t_shard = float("inf")
        n_segs = CS_ROUNDS // CS_SEGMENT
        for _ in range(CS_REPEATS):
            b = fed_run(None, dataclasses.replace(base_sim), cfg, data=data)
            s = fed_run(None, dataclasses.replace(shard_sim), cfg, data=data)
            ts_b = sorted(b.round_times)
            t_base = min(t_base, ts_b[len(ts_b) // 2])
            steady = [
                float(np.mean(s.round_times[i * CS_SEGMENT:(i + 1) * CS_SEGMENT]))
                for i in range(2, n_segs)
            ]
            t_shard = min(t_shard, min(steady))
        speedup = t_base / max(t_shard, 1e-9)
        from repro.data import pow2_bucket, shard_compact_plan

        live = np.nonzero(np.asarray(shard.blocked_round) <= 0)[0]
        _, rows_per_shard = shard_compact_plan(live, CS_SHARDS, K // CS_SHARDS)
        bucket = rows_per_shard * CS_SHARDS
        rows.append({
            "name": f"fused_engine/client_scaling/K{K}/sharded_speedup",
            "us_per_call": round(t_shard * 1e6, 1),
            "derived": f"sharded={speedup:.2f}x_vs_1dev_bucket{bucket}",
        })
        record.append({
            "K": K,
            "shards": CS_SHARDS,
            "bad_frac": CS_BAD_FRAC,
            "rounds": CS_ROUNDS,
            "segment_rounds": CS_SEGMENT,
            "blocked_clients": n_blocked,
            "bucket_after_blocking": int(bucket),
            "single_device_round_s": round(t_base, 6),
            "sharded_post_block_round_s": round(t_shard, 6),
            "single_device_rounds_per_s": round(1.0 / max(t_base, 1e-9), 2),
            "sharded_rounds_per_s": round(1.0 / max(t_shard, 1e-9), 2),
            "post_block_speedup": round(speedup, 2),
            "test_error_allclose": True,
            "blocked_round_equal": blocked_equal,
            "good_mask_equal": masks_equal,
        })
    return rows, record


_CS_MARK = "CLIENT_SCALING_JSON:"


def run_client_scaling(tiny: bool = False) -> tuple[list[dict], list[dict]]:
    """Client-sharded engine vs single-device one-shot scan (see module
    docstring).  Runs in-process when enough devices exist (the CI
    multi-device job sets ``--xla_force_host_platform_device_count=8``).
    On the CPU backend with fewer devices it re-execs this file as a
    CPU-only worker subprocess with forced host devices; on an accelerator
    it refuses, since this process already holds the chips a child would
    need."""
    import jax

    if jax.device_count() >= CS_SHARDS:
        return _client_scaling_core(tiny)
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"client scaling needs {CS_SHARDS} devices in one process, but "
            f"this {jax.default_backend()} host has {jax.device_count()}; "
            "it runs on a host with that many chips, or on the CPU with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={CS_SHARDS}"
        )
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={CS_SHARDS}".strip()
    )
    cmd = [sys.executable, os.path.abspath(__file__), "--client-scaling-worker"]
    if tiny:
        cmd.append("--tiny")
    out = subprocess.run(cmd, capture_output=True, text=True, env=env)
    if out.returncode != 0:
        raise RuntimeError(
            f"client-scaling worker failed:\n{out.stdout}\n{out.stderr}"
        )
    payload = next(
        line for line in out.stdout.splitlines() if line.startswith(_CS_MARK)
    )
    doc = json.loads(payload[len(_CS_MARK):])
    return doc["rows"], doc["record"]


# fed_llm scenario geometry: the transformer LoRA workload through the fused
# engine (fed.workload) — 6 clients, 2 byzantine.  Two numbers: rounds/sec of
# the whole scanned LLM simulation (one fused jit, adapter-delta proposals),
# and the aggregation-buffer win of low-rank proposals: one AFA dispatch on
# the packed (K, D_adapter) buffer vs the same dispatch on the (K, D_full)
# buffer a full-parameter workload would ship.  The scenario also asserts the
# robustness outcome (both attackers blocked within the horizon) so the
# timing can never go green on a broken simulation.
LLM_CLIENTS = 6
LLM_BYZANTINE = 2


def _llm_workload(tiny: bool):
    from repro.fed.workload import get_workload

    if tiny:
        from repro.models import ModelConfig

        cfg = ModelConfig(
            name="bench-lora", family="dense", num_layers=2, d_model=32,
            vocab_size=64, num_heads=4, num_kv_heads=2, d_ff=64,
            block_q=16, block_k=16,
        )
        return get_workload("lora", model_cfg=cfg, rank=2)
    return get_workload("lora", arch="smollm-135m", reduced=True, rank=4)


def run_fed_llm(tiny: bool = False) -> tuple[list[dict], list[dict]]:
    """Federated LLM fine-tuning on low-rank deltas: fused-scan rounds/sec
    plus the adapter-vs-full-parameter aggregation speedup (see the section
    comment above)."""
    import time

    import jax
    import jax.numpy as jnp

    from benchmarks.common import timeit
    from repro.core import RuleOptions, dispatch_rule
    from repro.fed.workload import make_llm_fused_data
    from repro.utils.trees import pack_spec, pack_stack, tree_broadcast_clients

    K, byz = LLM_CLIENTS, LLM_BYZANTINE
    rounds = 6 if tiny else 8
    seq, samples = (16, 8) if tiny else (32, 16)
    workload = _llm_workload(tiny)
    data = make_llm_fused_data(
        workload.model_cfg, clients=K, samples_per_client=samples, seq=seq,
        n_test=8,
    )
    sim = SimConfig(
        num_clients=K, bad_frac=byz / K, scenario="byzantine", rounds=rounds,
        local_epochs=2, batch_size=2, seed=0, lr=0.2,
    )

    # correctness first (also the compile warmup): AFA must block both
    # attackers on the adapter buffer
    res = fed_run(workload, sim, data=data, seq=seq)
    blocked = res["blocked"][-1]
    assert blocked[:byz].all(), f"byzantine clients not blocked: {blocked}"
    assert not blocked[byz:].any(), f"benign client blocked: {blocked}"

    t_sim = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        fed_run(workload, sim, data=data, seq=seq)
        t_sim = min(t_sim, time.perf_counter() - t0)
    rounds_per_s = rounds / max(t_sim, 1e-9)

    # aggregation-buffer win: identical AFA dispatch, adapter rows vs the
    # full-parameter rows a whole-model workload would propose
    params = workload.init_params(jax.random.PRNGKey(0))
    adapters = workload.codec.proposal_of(params)
    rng = np.random.default_rng(0)

    def proposal_buffer(tree):
        u = pack_stack(tree_broadcast_clients(tree, K), pack_spec(tree))
        u = u + jnp.asarray(rng.normal(size=u.shape).astype(np.float32))
        return u.at[:byz].multiply(25.0)  # outliers: screening iterates

    u_full = proposal_buffer(params)
    u_adapter = proposal_buffer(adapters)
    n_k = jnp.full((K,), float(samples), jnp.float32)
    p_k = jnp.full((K,), 0.5, jnp.float32)
    mask = jnp.ones((K,), bool)
    opts = RuleOptions()
    t_full = t_adapter = float("inf")
    for _ in range(REPEATS):
        t_full = min(t_full, timeit(
            lambda: dispatch_rule("afa", u_full, n_k, p_k, mask, opts),
            warmup=1, iters=5))
        t_adapter = min(t_adapter, timeit(
            lambda: dispatch_rule("afa", u_adapter, n_k, p_k, mask, opts),
            warmup=1, iters=5))
    agg_speedup = t_full / max(t_adapter, 1e-9)
    d_adapter, d_full = u_adapter.shape[1], u_full.shape[1]

    rows = [
        {"name": f"fused_engine/fed_llm/K{K}/rounds_per_s",
         "us_per_call": round(t_sim / rounds * 1e6, 1),
         "derived": f"{rounds_per_s:.2f}rounds_per_s"},
        {"name": f"fused_engine/fed_llm/K{K}/agg_full",
         "us_per_call": round(t_full * 1e6, 1), "derived": f"D{d_full}"},
        {"name": f"fused_engine/fed_llm/K{K}/agg_adapter",
         "us_per_call": round(t_adapter * 1e6, 1), "derived": f"D{d_adapter}"},
        {"name": f"fused_engine/fed_llm/K{K}/agg_speedup",
         "us_per_call": "",
         "derived": f"adapter={agg_speedup:.2f}x_vs_full"},
    ]
    record = [{
        "K": K,
        "byzantine": byz,
        "rank": int(workload.rank),
        "rounds": rounds,
        "adapter_dim": int(d_adapter),
        "param_dim": int(d_full),
        "adapter_fraction": round(d_adapter / d_full, 4),
        "sim_s": round(t_sim, 6),
        "rounds_per_s": round(rounds_per_s, 2),
        "full_agg_s": round(t_full, 6),
        "adapter_agg_s": round(t_adapter, 6),
        "agg_speedup": round(agg_speedup, 2),
        "attackers_blocked": True,
    }]
    return rows, record


# kernel-scenario geometry: the aggregation hot path alone, AFA gram variant
# on a synthetic (K, D) stack with planted outliers so the screening loop
# actually iterates.  Three routes: jnp oracle, chained kernels (PR-4:
# separate gram + weighted-sum launches), fused mega-kernel (ONE launch).
KERNEL_D = 2048
KERNEL_ROUNDS = 8




def run_kernel(tiny: bool = False) -> tuple[list[dict], list[dict]]:
    """Fused-screening-kernel speedups: ONE Pallas launch per aggregation
    (afa_screen) vs the chained per-op kernel launches vs the jnp oracle.

    Also asserts the tentpole's structural claims: the fused route binds
    EXACTLY one pallas_call in its jaxpr (the chained route >= 2, the jnp
    route 0), and — on the interpret route — the fused aggregate / mask /
    rounds / similarities are BIT-identical (f32) to the jnp gram reference.
    On CPU CI the kernel mode is pinned to ``interpret`` (compiled Mosaic
    needs a TPU), so the recorded speedups gate the interpreter route's
    relative cost; on a real accelerator the same scenario records the
    compiled launch wins.
    """
    import jax.numpy as jnp

    from benchmarks.common import timeit
    from repro.core.afa import AFAConfig, afa_aggregate
    from repro.kernels.policy import resolve_kernel_mode

    mode = resolve_kernel_mode(True)
    if mode == "jnp":  # auto off-TPU (GPU included): the interpreter IS the kernel route
        mode = "interpret"
    ks = [50] if tiny else [50, 200, 512]
    rows, record = [], []
    for K in ks:
        rng = np.random.default_rng(K)
        u = jnp.asarray(rng.normal(size=(K, KERNEL_D)).astype(np.float32))
        u = u.at[: max(K // 10, 1)].multiply(25.0)  # outliers -> screening iterates
        n_k = jnp.asarray(rng.integers(1, 50, size=K).astype(np.float32))
        p_k = jnp.asarray(rng.uniform(0.2, 0.8, size=K).astype(np.float32))
        cfgs = {
            "jnp": AFAConfig(variant="gram", use_kernels=False,
                             max_rounds=KERNEL_ROUNDS),
            "chained": AFAConfig(variant="gram", use_kernels=mode,
                                 kernel_launch="chained", max_rounds=KERNEL_ROUNDS),
            "fused": AFAConfig(variant="gram", use_kernels=mode,
                               kernel_launch="fused", max_rounds=KERNEL_ROUNDS),
        }
        res = {name: afa_aggregate(u, n_k, p_k, config=c)
               for name, c in cfgs.items()}
        if mode == "interpret":
            # exact-shape one-pass kernel: bit-identical to the jnp oracle
            np.testing.assert_array_equal(
                np.asarray(res["fused"].aggregate), np.asarray(res["jnp"].aggregate),
                err_msg=f"fused kernel not bit-identical to jnp oracle at K={K}")
            np.testing.assert_array_equal(
                np.asarray(res["fused"].good_mask), np.asarray(res["jnp"].good_mask))
            np.testing.assert_array_equal(
                np.asarray(res["fused"].similarities),
                np.asarray(res["jnp"].similarities))
            assert int(res["fused"].rounds) == int(res["jnp"].rounds)
        from repro.analysis import LaunchBudget, count_pallas_launches
        from repro.analysis.launches import assert_launch_budget

        budgets = {"jnp": LaunchBudget(exact=0),
                   "chained": LaunchBudget(min=2),
                   "fused": LaunchBudget(exact=1)}
        launches = {}
        for name, c in cfgs.items():
            route = lambda u_, n_, p_, c=c: afa_aggregate(u_, n_, p_, config=c)
            assert_launch_budget(route, u, n_k, p_k, budget=budgets[name],
                                 target=f"afa[{name}]")
            launches[name] = count_pallas_launches(route, u, n_k, p_k)
        times = {}
        for name, c in cfgs.items():
            t = float("inf")
            for _ in range(REPEATS):
                t = min(t, timeit(
                    lambda c=c: afa_aggregate(u, n_k, p_k, config=c),
                    warmup=1, iters=5))
            times[name] = t
        vs_chained = times["chained"] / max(times["fused"], 1e-9)
        vs_jnp = times["jnp"] / max(times["fused"], 1e-9)
        for name in ("jnp", "chained", "fused"):
            rows.append({
                "name": f"fused_engine/kernel/K{K}/{name}",
                "us_per_call": round(times[name] * 1e6, 1),
                "derived": f"launches={launches[name]}",
            })
        rows.append({
            "name": f"fused_engine/kernel/K{K}/speedup",
            "us_per_call": "",
            "derived": f"fused={vs_chained:.2f}x_vs_chained_{vs_jnp:.2f}x_vs_jnp",
        })
        record.append({
            "K": K,
            "D": KERNEL_D,
            "mode": mode,
            "rounds_run": int(res["fused"].rounds),
            "launches_fused": launches["fused"],
            "launches_chained": launches["chained"],
            "jnp_s": round(times["jnp"], 6),
            "chained_s": round(times["chained"], 6),
            "fused_s": round(times["fused"], 6),
            "fused_vs_chained": round(vs_chained, 2),
            "fused_vs_jnp": round(vs_jnp, 2),
            "bit_exact": mode == "interpret",
        })
    return rows, record


def run(quick: bool = False, tiny: bool = False,
        client_scaling_only: bool = False) -> list[dict]:
    if client_scaling_only:
        cs_rows, cs_record = run_client_scaling(tiny=tiny)
        with open(OUT_JSON, "w") as f:
            json.dump({
                "workload": {
                    "dim": CS_DIM, "hidden": list(CS_HIDDEN), "batch": CS_BATCH,
                    "per_client": CS_PER_CLIENT, "scenario": "byzantine",
                    "rule": "afa", "rounds_timed": CS_ROUNDS,
                    "repeats": CS_REPEATS,
                },
                "client_scaling": cs_record,
            }, f, indent=2)
        return cs_rows
    if tiny:
        ks, rounds = [10], 8
    elif quick:
        ks, rounds = [10, 50], 30
    else:
        ks, rounds = [10, 50, 200], 30
    rows, record = [], []
    for K in ks:
        data = make_mnist_like(n_train=K * PER_CLIENT, n_test=200, dim=DIM)
        t_batched = _measure(data, K, "batched", rounds)
        t_fused = _measure(data, K, "fused", rounds)
        speedup = t_batched / max(t_fused, 1e-9)
        for name, t in [("batched", t_batched), ("fused", t_fused)]:
            rows.append({
                "name": f"fused_engine/K{K}/{name}",
                "us_per_call": round(t * 1e6, 1),
                "derived": "",
            })
        rows.append({
            "name": f"fused_engine/K{K}/speedup",
            "us_per_call": "",
            "derived": f"fused={speedup:.1f}x_vs_batched",
        })
        record.append({
            "K": K,
            "batched_round_s": round(t_batched, 6),
            "fused_round_s": round(t_fused, 6),
            "speedup": round(speedup, 2),
        })
    compact_rows, compact_record = run_compaction(tiny=tiny)
    rows.extend(compact_rows)
    packed_rows, packed_record = run_packed(tiny=tiny)
    rows.extend(packed_rows)
    kernel_rows, kernel_record = run_kernel(tiny=tiny)
    rows.extend(kernel_rows)
    llm_rows, llm_record = run_fed_llm(tiny=tiny)
    rows.extend(llm_rows)
    cs_rows, cs_record = run_client_scaling(tiny=tiny)
    rows.extend(cs_rows)
    with open(OUT_JSON, "w") as f:
        json.dump({
            "workload": {
                "dim": DIM, "hidden": list(HIDDEN), "batch": BATCH,
                "per_client": PER_CLIENT, "scenario": "clean", "rule": "afa",
                "rounds_timed": rounds, "repeats": REPEATS,
            },
            "results": record,
            "compaction": compact_record,
            "packed": packed_record,
            "kernel": kernel_record,
            "fed_llm": llm_record,
            "client_scaling": cs_record,
        }, f, indent=2)
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="K in {10, 50} only")
    ap.add_argument("--tiny", action="store_true",
                    help="seconds-scale CI smoke: K=10, T=8")
    ap.add_argument("--client-scaling", action="store_true",
                    help="run ONLY the client-sharded scaling scenario")
    ap.add_argument("--client-scaling-worker", action="store_true",
                    help=argparse.SUPPRESS)  # internal: forced-device subprocess
    args = ap.parse_args()
    if args.client_scaling_worker:
        cs_rows, cs_record = _client_scaling_core(tiny=args.tiny)
        print(_CS_MARK + json.dumps({"rows": cs_rows, "record": cs_record}))
    else:
        emit(run(quick=args.quick, tiny=args.tiny,
                 client_scaling_only=args.client_scaling))
