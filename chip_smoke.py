#!/usr/bin/env python3
"""Smoke run of the federated AFA round on a TPU, through ``repro.fed.api.run``.

    python chip_smoke.py            # one chip: phases a-d
    python chip_smoke.py --chips 4  # four chips: the client-sharded phase only

One chip, on the paper's deployment (DNN 784x512x256x10 at its published
widths, MNIST-scale data generated from ``--seed``, K=100 clients, 30%
byzantine, fused segmented scan with compaction):

  a. AFA's gram variant with the kernel plan pinned to ``pallas``: the fused
     ``afa_screen`` kernel screens and aggregates each round;
  b. the paper's iterative variant on the chained ``weighted_sum`` /
     ``cosine_sim`` kernels;
  c. the same run on the ``jnp`` route, the reference a and b are held to
     (``compare_runs``): equal blocked rounds, total updates within
     ``UPDATE_REL_TOL`` of each other, per-round kept sets that differ
     only at their edge, test error within ``TEST_ERROR_TOL`` points,
     detection rate 1.0;
  d. one aggregation call per kernel-backed rule (afa gram, comed,
     trimmed-mean, multi-krum through gram) on a packed (K, D) buffer, on
     ``pallas`` and on ``jnp``: both routes keep the same rows, no
     byzantine one, and each route's aggregate is within ``AGG_REL_TOL`` of
     the rule computed on the host in f64 over those rows.

``--chips 4`` runs phase a's deployment client-sharded over four chips
(iterative variant, the one sharded AFA implements) against the same seed
on one chip, holds it to that run as ``compare_runs`` does, and checks that
the client data stacks and the server state span all four chips and that
the sharded program's per-chip working set is at most
``SHARDED_BYTES_SHARE`` of the one-chip program's.

Where two runs keep different clients in a round, each such client must
have crossed the screening cut: in the run that kept it, its similarity's
z-score over the kept set (the statistic AFA's tail test cuts at ``xi`` >=
2) is within ``EDGE_Z`` of the kept set's extreme, and in the other run it
lies outside the kept range.  The smoke prints these clients.

Every compiled program that should hold a Pallas kernel must show
``tpu_custom_call`` in its compiled text.  Each phase prints one JSON line;
the last line is ``{"ok": true, "device": {...}}``.  Without a TPU, or away
from the repository's ``src/``, the script exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

SEED = 0
K = 100                  # clients
BAD_FRAC = 0.3           # byzantine share (clients 0..29)
N_TRAIN, N_TEST = 60_000, 10_000
HIDDEN = (512, 256)      # paper DNN 784x512x256x10 (configs/paper_mnist_dnn.py)
ROUNDS, SEGMENT = 12, 4  # a byzantine client is blocked after 6 flagged rounds
KERNEL_MODE = "pallas"
CHIPS_SHARDED = 4
# every aggregation contraction runs at f32 (Precision.HIGHEST); kernels
# and jnp reduce in other orders, so routes agree within bounds, not bitwise
TEST_ERROR_TOL = 2.0     # percentage points, every round
AGG_REL_TOL = 1e-5       # ||agg - agg_host_f64|| / ||agg_host_f64||
# ||params_got - params_ref|| / ||params_ref - params_0||: the two runs'
# total updates agree within this share.  On a v5e, kernel against jnp
# routes read 5.2e-4 and 1.1e-3 with one and four edge flips; one client
# shard left out of the sharded aggregate reads 0.21 (CPU, K=32)
UPDATE_REL_TOL = 1e-2
# a client kept by one run only sits within this many z-scores of the kept
# set's edge in the run that kept it (v5e readings: 0 to 0.037)
EDGE_Z = 0.5
SHARDED_BYTES_SHARE = 0.5
MAX_DIFFS_SHOWN = 8


class SmokeFailure(RuntimeError):
    pass


def _require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _import_repo():
    """Import ``repro`` from the ``src/`` next to this file, and only there."""
    src = ROOT / "src"
    if not (src / "repro" / "fed" / "api.py").is_file():
        raise SmokeFailure(f"no repro package under {src}: run from a checkout")
    sys.path.insert(0, str(src))
    import repro.fed.api

    _require(
        Path(repro.fed.api.__file__).resolve().is_relative_to(src),
        f"imported repro from {repro.fed.api.__file__}, not from {src}",
    )


def _process_peak_bytes(device) -> int:
    """The device's ``peak_bytes_in_use``: a high-water mark over the whole
    process so far, not over one phase."""
    return int(device.memory_stats()["peak_bytes_in_use"])


def _program_bytes(compiled) -> int:
    """Per-device bytes a compiled program holds: arguments, outputs and
    temporaries (``memory_analysis``)."""
    mem = compiled.memory_analysis()
    return int(mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes)


def _check_kernels(text: str, expected: bool, what: str) -> None:
    has = "tpu_custom_call" in text
    _require(
        has == expected,
        f"{what}: tpu_custom_call {'missing from' if expected else 'found in'} "
        "the compiled program",
    )


class _CompileCounter:
    """Counts backend compiles and the seconds they took; a program loaded
    from the persistent compilation cache counts too, at its load time."""

    def __init__(self):
        import jax

        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration


def _deployment(client_shards: int, variant: str, mode: str, seed: int):
    from repro.fed.server import ServerConfig
    from repro.fed.simulator import SimConfig
    from repro.kernels.policy import KernelPlan

    sim = SimConfig(
        num_clients=K, bad_frac=BAD_FRAC, scenario="byzantine", rounds=ROUNDS,
        engine="fused", segment_rounds=SEGMENT, compact=True, seed=seed,
        hidden=HIDDEN, client_shards=client_shards,
    )
    server = ServerConfig(
        rule="afa", num_clients=K, afa_variant=variant,
        kernel_plan=KernelPlan(mode=mode),
    )
    return sim, server


def run_deployment(phase: str, data, counter, *, variant: str, mode: str,
                   seed: int, client_shards: int = 0):
    """Compile the segment program ahead of the run, check it for kernels,
    then run the deployment twice through ``api.run`` (cold, then warm).
    Returns the cold run's result and what the compiled program showed."""
    import jax
    import numpy as np

    from repro.fed.api import run
    from repro.fed.simulator import first_segment

    sim, server = _deployment(client_shards, variant, mode, seed)
    seg_fn, args = first_segment(data, sim, server)
    t0 = time.perf_counter()
    compiled = seg_fn.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    _check_kernels(compiled.as_text(), mode != "jnp", f"phase {phase} segment")
    program = dict(
        data_devices=len(args[3].x.sharding.device_set),
        state_devices=len(compiled.output_shardings[1].reputation.blocked.device_set),
        program_bytes=_program_bytes(compiled),
        params0=_flat(args[0]),
    )
    del seg_fn, args, compiled

    n0, s0 = counter.n, counter.seconds
    t0 = time.perf_counter()
    cold = run(None, sim, server, data=data)
    cold_s = time.perf_counter() - t0
    cold_compiles, cold_compile_s = counter.n - n0, counter.seconds - s0
    warm = run(None, sim, server, data=data)
    n_bad = int(round(BAD_FRAC * K))

    _require(
        np.array_equal(warm.blocked_round, cold.blocked_round)
        and np.array_equal(_flat(warm.params), _flat(cold.params)),
        f"phase {phase}: warm and cold runs differ",
    )
    _require(cold.detection_rate == 1.0,
             f"phase {phase}: detection rate {cold.detection_rate} != 1.0")
    _emit(
        phase, variant=variant, kernel_mode=mode, client_shards=client_shards,
        device_kind=jax.devices()[0].device_kind,
        segment_compile_s=compile_s,
        cold_run_s=cold_s, cold_run_backend_compiles=cold_compiles,
        cold_run_backend_compile_s=cold_compile_s,
        round_s=float(sum(warm.round_times) / len(warm.round_times)),
        round_times_s=warm.round_times,
        test_error_final=cold.test_error[-1],
        blocked_round_max=int(cold.blocked_round.max()),
        detection_rate=cold.detection_rate,
        benign_blocked=int((cold.blocked_round[n_bad:] > 0).sum()),
        program_bytes=program["program_bytes"],
        process_peak_bytes_in_use=_process_peak_bytes(jax.devices()[0]),
    )
    return cold, program


def _flat(tree):
    import jax
    import numpy as np

    return np.concatenate([
        np.asarray(leaf, np.float64).ravel()
        for leaf in jax.tree_util.tree_leaves(jax.device_get(tree))
    ])


def _z_scores(sims, kept):
    """Similarities as z-scores over the kept set: its median and the
    tie-floored spread that AFA's tail test scales ``xi`` by."""
    import numpy as np

    from repro.core.afa import SIM_TIE_RTOL

    s = np.asarray(sims, np.float64)
    kept = np.asarray(kept, bool)
    med = float(np.median(s[kept]))
    sd = max(float(s[kept].std()), SIM_TIE_RTOL * abs(med))
    return (s - med) / sd


def _flip(k: int, keep, drop) -> dict:
    """Client ``k``, kept by one run and dropped by the other; ``keep`` and
    ``drop`` are each run's ``(similarities, kept mask)``.  A client that
    crossed the screening cut is the kept set's extreme in the run that
    kept it (``edge_z`` near 0) and lies outside the kept range in the run
    that dropped it (a dropped client always does: the tail test cuts from
    the ends)."""
    import numpy as np

    out = {}
    for name, (sims, kept) in (("keep", keep), ("drop", drop)):
        kept = np.asarray(kept, bool)
        z = _z_scores(sims, kept)
        lo, hi = float(z[kept].min()), float(z[kept].max())
        out[name] = dict(z=float(z[k]), kept_z_range=[lo, hi])
    z, (lo, hi) = out["keep"]["z"], out["keep"]["kept_z_range"]
    out["edge_z"] = min(z - lo, hi - z)
    z, (lo, hi) = out["drop"]["z"], out["drop"]["kept_z_range"]
    out["outside"] = bool(z < lo or z > hi)
    return out


def _flips(sims_a, kept_a, sims_b, kept_b) -> list[dict]:
    """Every client kept by exactly one of two runs in a round."""
    import numpy as np

    flips = []
    for k in np.nonzero(np.asarray(kept_a) != np.asarray(kept_b))[0]:
        a_kept = bool(kept_a[k])
        a, b = (sims_a, kept_a), (sims_b, kept_b)
        flips.append(dict(client=int(k), kept_by="got" if a_kept else "ref",
                          **_flip(int(k), *((a, b) if a_kept else (b, a)))))
    return flips


def compare_runs(phase: str, got, ref, what: str, params0) -> dict:
    """Hold a run to a reference run: the same clients blocked in the same
    rounds, total updates within ``UPDATE_REL_TOL`` of each other, test
    error within ``TEST_ERROR_TOL``, and kept sets that differ only at
    their edge.  Another reduction order may move a client that sits at the
    screening cut across it: such a client is the kept set's extreme, within
    ``EDGE_Z`` of it, in the run that kept it."""
    import numpy as np

    p_got, p_ref = _flat(got.params), _flat(ref.params)
    update_rel = float(
        np.linalg.norm(p_got - p_ref) / np.linalg.norm(p_ref - params0)
    )
    g_got = np.asarray(got.good_mask_history, bool)
    g_ref = np.asarray(ref.good_mask_history, bool)
    flips = [
        dict(round=r, **f)
        for r in np.nonzero((g_got != g_ref).any(axis=1))[0].tolist()
        for f in _flips(got.similarity_history[r], g_got[r],
                        ref.similarity_history[r], g_ref[r])
    ]
    off_edge = [f for f in flips if f["edge_z"] > EDGE_Z or not f["outside"]]
    blocked_equal = bool(np.array_equal(got.blocked_round, ref.blocked_round))
    diff = np.abs(np.asarray(got.test_error) - np.asarray(ref.test_error))
    fields = dict(
        vs=what, blocked_round_equal=blocked_equal,
        update_rel_diff=update_rel, update_rel_tol=UPDATE_REL_TOL,
        good_mask_entries_differing=len(flips),
        good_mask_rounds_differing=sorted({f["round"] for f in flips}),
        edge_z_max=max((f["edge_z"] for f in flips), default=None),
        edge_z_tol=EDGE_Z,
        flips=(off_edge or flips)[:MAX_DIFFS_SHOWN],
        test_error_max_abs_diff=float(diff.max()),
        test_error_tol=TEST_ERROR_TOL,
    )
    # print before the checks, so a failed comparison still reports
    _emit(phase, **fields)
    _require(
        blocked_equal,
        f"phase {phase}: blocked_round differs from {what}: "
        f"{got.blocked_round.tolist()} vs {ref.blocked_round.tolist()}",
    )
    _require(update_rel <= UPDATE_REL_TOL,
             f"phase {phase}: update differs from {what} by {update_rel} "
             f"(relative) > {UPDATE_REL_TOL}")
    _require(not off_edge,
             f"phase {phase}: {len(off_edge)} client(s) kept by one run "
             "only, away from the kept set's edge")
    _require(float(diff.max()) <= TEST_ERROR_TOL,
             f"phase {phase}: test error differs from {what} by "
             f"{diff.max():.3f} points > {TEST_ERROR_TOL}")
    return fields


def packed_buffer(seed: int, D: int, n_bad: int):
    """A packed (K, D) proposal buffer: benign rows around one base
    direction, each at its own noise scale, so that their similarities and
    distances spread far beyond f32 resolution and the rows a rule keeps
    are decided by the data, not by a reduction order; byzantine rows
    (the first ``n_bad``) are large noise."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        kb, ks, kn, kz = jax.random.split(key, 4)
        base = 0.05 * jax.random.normal(kb, (D,), jnp.float32)
        scale = jax.random.uniform(ks, (K, 1), jnp.float32, 0.01, 0.03)
        u = base + scale * jax.random.normal(kn, (K, D), jnp.float32)
        return u.at[:n_bad].set(20.0 * jax.random.normal(kz, (n_bad, D)))

    return make(jax.random.PRNGKey(seed))


def phase_rules(seed: int) -> list[dict]:
    """One aggregation call per kernel-backed rule, on pallas and on jnp:
    both keep the same rows, and each is held to the rule computed on the
    host over them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import AFAConfig, RuleOptions, dispatch_rule

    sizes = (784, *HIDDEN, 10)
    D = sum(a * b + b for a, b in zip(sizes[:-1], sizes[1:]))
    n_bad, n_dead = int(round(BAD_FRAC * K)), 5

    u = packed_buffer(seed, D, n_bad)
    n_k = jnp.full((K,), float(N_TRAIN // K), jnp.float32)
    p_k = jnp.full((K,), 0.5, jnp.float32)
    mask = jnp.arange(K) < K - n_dead
    live = K - n_dead

    def opts(rule, mode):
        return RuleOptions(
            num_byzantine=n_bad, trim=n_bad,
            num_selected=max(live - n_bad - 2, 1) if rule == "mkrum" else None,
            use_kernels=mode,
            afa=AFAConfig(variant="gram", use_kernels=mode),
        )

    u_host = np.asarray(u, np.float64)
    live_rows = u_host[np.asarray(mask)]
    trimmed = np.sort(live_rows, axis=0)[n_bad:live - n_bad]

    def reference(rule, good):
        """The rule on the host in f64, over the rows the route kept: afa
        and multi-krum average their kept rows (equal n_k and p_k)."""
        if rule == "comed":
            return np.median(live_rows, axis=0)
        if rule == "trimmed_mean":
            return trimmed.mean(axis=0)
        return u_host[good].mean(axis=0)

    rows = []
    for rule in ("afa", "comed", "trimmed_mean", "mkrum"):
        row = dict(rule=rule, K=K, D=D, agg_rel_tol=AGG_REL_TOL)
        goods, sims = {}, {}
        for mode in (KERNEL_MODE, "jnp"):
            o = opts(rule, mode)
            fn = jax.jit(lambda u, n, p, m, o=o, rule=rule: dispatch_rule(rule, u, n, p, m, o))
            t0 = time.perf_counter()
            compiled = fn.lower(u, n_k, p_k, mask).compile()
            compile_s = time.perf_counter() - t0
            _check_kernels(compiled.as_text(), mode != "jnp", f"rule {rule} ({mode})")
            res = compiled(u, n_k, p_k, mask)
            jax.block_until_ready(res.aggregate)
            t0 = time.perf_counter()
            res = compiled(u, n_k, p_k, mask)
            jax.block_until_ready(res.aggregate)
            call_s = time.perf_counter() - t0
            agg = np.asarray(res.aggregate, np.float64)
            good = np.asarray(res.good_mask)
            ref = reference(rule, good)
            goods[mode] = good
            sims[mode] = getattr(res, "similarities", None)
            row[mode] = dict(
                compile_s=compile_s, call_s=call_s, kept=int(good.sum()),
                finite=bool(np.isfinite(agg).all()),
                byzantine_kept=int(good[:n_bad].sum()),
                agg_rel_diff=float(np.linalg.norm(agg - ref) / np.linalg.norm(ref)),
            )
        differ = np.nonzero(goods[KERNEL_MODE] != goods["jnp"])[0]
        row["kept_rows_differing"] = int(len(differ))
        if len(differ) and sims[KERNEL_MODE] is not None:
            # "got" is the kernel route, "ref" the jnp route
            row["flips"] = _flips(sims[KERNEL_MODE], goods[KERNEL_MODE],
                                  sims["jnp"], goods["jnp"])[:MAX_DIFFS_SHOWN]
        _emit("d", **row)
        _require(len(differ) == 0, f"rule {rule}: kept rows differ between routes")
        for mode in (KERNEL_MODE, "jnp"):
            r = row[mode]
            _require(r["finite"], f"rule {rule} ({mode}): non-finite aggregate")
            if rule in ("afa", "mkrum"):
                _require(r["byzantine_kept"] == 0,
                         f"rule {rule} ({mode}): kept a byzantine row")
            _require(r["agg_rel_diff"] <= AGG_REL_TOL,
                     f"rule {rule} ({mode}): aggregate rel. diff "
                     f"{r['agg_rel_diff']} > {AGG_REL_TOL}")
        rows.append(row)
    return rows


def _make_data(seed: int):
    from repro.data import make_mnist_like

    t0 = time.perf_counter()
    data = make_mnist_like(seed=seed, n_train=N_TRAIN, n_test=N_TEST)
    return data, time.perf_counter() - t0


def one_chip(seed: int, counter) -> None:
    data, data_s = _make_data(seed)
    _emit("setup", data_s=data_s, n_train=N_TRAIN, n_test=N_TEST, K=K)
    ref, prog = run_deployment("c", data, counter, variant="iterative", mode="jnp", seed=seed)
    a, _ = run_deployment("a", data, counter, variant="gram", mode=KERNEL_MODE, seed=seed)
    compare_runs("a", a, ref, "c (jnp reference)", prog["params0"])
    b, _ = run_deployment("b", data, counter, variant="iterative", mode=KERNEL_MODE, seed=seed)
    compare_runs("b", b, ref, "c (jnp reference)", prog["params0"])
    phase_rules(seed)


def four_chips(seed: int, counter) -> None:
    import jax

    data, data_s = _make_data(seed)
    _emit("setup", data_s=data_s, n_train=N_TRAIN, n_test=N_TEST, K=K)
    sharded, s_prog = run_deployment(
        "sharded", data, counter, variant="iterative", mode=KERNEL_MODE,
        seed=seed, client_shards=CHIPS_SHARDED,
    )
    single, o_prog = run_deployment(
        "single", data, counter, variant="iterative", mode=KERNEL_MODE, seed=seed,
    )
    placement = {k: s_prog[k] for k in ("data_devices", "state_devices")}
    share = s_prog["program_bytes"] / o_prog["program_bytes"]
    _emit("sharded", **placement, program_bytes_share=share,
          per_chip_process_peak_bytes=[
              _process_peak_bytes(d) for d in jax.devices()[:CHIPS_SHARDED]])
    compare_runs("sharded", sharded, single, "one chip", o_prog["params0"])
    _require(
        all(n == CHIPS_SHARDED for n in placement.values()),
        f"data stacks / server state span {placement}, not {CHIPS_SHARDED} devices",
    )
    _require(
        share <= SHARDED_BYTES_SHARE,
        f"the sharded program holds {share:.3f} of the one-chip program's "
        f"bytes per chip, more than {SHARDED_BYTES_SHARE}",
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, CHIPS_SHARDED), default=1,
                    help=f"1: phases a-d on one chip; {CHIPS_SHARDED}: the "
                         "client-sharded phase against one chip")
    ap.add_argument("--seed", type=int, default=SEED)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{devices[0].platform!r}); this smoke runs on the chip only",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    try:
        _import_repo()
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2

    from repro.utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    counter = _CompileCounter()
    t0 = time.perf_counter()
    _emit("start", device_kind=devices[0].device_kind, devices=len(devices),
          chips=args.chips, seed=args.seed, jax=jax.__version__,
          compile_cache=cache_dir)
    if args.chips == 1:
        one_chip(args.seed, counter)
    else:
        four_chips(args.seed, counter)
    _emit("done", wall_s=time.perf_counter() - t0,
          backend_compiles=counter.n, backend_compile_s=counter.seconds)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
