"""Bench regression gate: fail when a recorded speedup regresses vs baseline.

CI runs ``benchmarks/fused_engine.py --tiny`` and then this script against
the committed ``BENCH_baseline.json`` snapshot.  Every *speedup* scenario
present in BOTH files is compared; a current speedup below
``baseline * (1 - tolerance)`` fails the job.  Only the dimensionless
speedups are gated — absolute per-round seconds vary with the runner, the
ratios are what the engine work is supposed to protect.  The default 25%
tolerance absorbs shared-runner noise; scenarios present in only one file
(new benchmarks, retired ones) are reported but never fail.

The committed ``BENCH_baseline.json`` records CONSERVATIVE reference
speedups — each set below the range observed across repeated local ``--tiny``
runs (see its "note" field) — because a ~1ms microbenchmark's run-to-run
spread on shared runners can itself approach the tolerance.  The gate's job
is to catch a layout/dispatch change that erases a speedup class (packed
dropping to ~1x, compaction losing its post-blocking win), not to
relitigate the third significant digit.

Per-scenario thresholds: the ``kernel_*`` scenarios (fused screening kernel
vs chained launches / jnp oracle) get a wider default tolerance — on CPU CI
they time the Pallas *interpreter*, whose per-launch overhead is noisier
than the compiled engines' round times — override with ``--kernel-tolerance``.
The ``client_scaling/*`` scenarios (client-sharded engine vs single-device,
timed over shard_map on forced host devices) get their own wide default via
``--scaling-tolerance`` for the same reason, amplified: forced host devices
serialize on the runner's physical cores, so their per-round times carry
both jit-dispatch and scheduler noise.

Absolute floors: scenarios whose baseline has been rounded down near parity
(runner variance can pin a conservative baseline at ~1.0x, where a
fractional tolerance would only fire *below* parity-minus-tolerance) also
carry an ABSOLUTE floor, independent of the baseline: the ``packed_agg_*``
scenarios fail outright when the packed dispatch drops below 1.0x — the
speedup class collapsing to (or past) parity is exactly what the gate
exists to catch, however noisy the runner.

Usage:  python benchmarks/check_regression.py CURRENT.json BASELINE.json
            [--tolerance 0.25] [--kernel-tolerance 0.5]
"""

from __future__ import annotations

import argparse
import json
import sys

# scenario-name prefix -> CLI option that carries its tolerance; anything
# unlisted uses --tolerance
PREFIX_TOLERANCE_OPTS = {
    "kernel_": "kernel_tolerance",
    # client_scaling times shard_map over FORCED host devices, which
    # serialize on the runner's cores — per-round cost there is the noisiest
    # thing the bench measures, so its gate is deliberately loose: it exists
    # to catch the sharded route collapsing (e.g. losing compaction), not a
    # timing wobble
    "client_scaling/": "scaling_tolerance",
}

# scenario-name prefix -> absolute speedup floor, applied IN ADDITION to the
# baseline-relative tolerance.  The packed dispatch must never lose to the
# leaf layout it replaced: even with its conservative baseline rounded down
# to ~1.0x, dropping below parity fails the gate outright.  The serve tier's
# ingress gate is a FRACTION, not a ratio: >= 95% of byzantine submissions
# arriving after their client was blocked must die at the front door
# (BENCH_serve.json, serve-smoke job) — admission control regressing to
# "accept and re-screen" is a correctness loss, so no runner-noise tolerance
# applies below the floor.
PREFIX_ABS_FLOOR = {"packed_agg/": 1.0, "serve_ingress/": 0.95}


def tolerance_for(name: str, args: argparse.Namespace) -> float:
    for prefix, opt in PREFIX_TOLERANCE_OPTS.items():
        if name.startswith(prefix):
            return getattr(args, opt)
    return args.tolerance


def abs_floor_for(name: str) -> float | None:
    for prefix, floor in PREFIX_ABS_FLOOR.items():
        if name.startswith(prefix):
            return floor
    return None


def collect_speedups(doc: dict) -> dict[str, float]:
    """Flatten every speedup scenario of a BENCH_fused_engine.json doc."""
    out: dict[str, float] = {}
    for r in doc.get("compaction", []):
        out[f"compaction_post_block/K{r['K']}"] = float(r["post_block_speedup"])
    for r in doc.get("packed", []):
        out[f"packed_agg/K{r['K']}/{r.get('rule', 'afa')}"] = float(r["agg_speedup"])
    for r in doc.get("kernel", []):
        out[f"kernel_fused_vs_chained/K{r['K']}"] = float(r["fused_vs_chained"])
        out[f"kernel_fused_vs_jnp/K{r['K']}"] = float(r["fused_vs_jnp"])
    for r in doc.get("fed_llm", []):
        out[f"fed_llm_agg/K{r['K']}"] = float(r["agg_speedup"])
    for r in doc.get("client_scaling", []):
        out[f"client_scaling/K{r['K']}"] = float(r["post_block_speedup"])
    for r in doc.get("serve", []):
        out[f"serve_ingress/K{r['K']}"] = float(r["byz_reject_fraction"])
        out[f"serve_reject_speedup/K{r['K']}"] = float(
            r["ingress_reject_speedup"]
        )
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("current", help="freshly generated BENCH json")
    ap.add_argument("baseline", help="committed baseline BENCH json")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="allowed fractional speedup drop before failing")
    ap.add_argument("--kernel-tolerance", type=float, default=0.5,
                    help="tolerance for the kernel_* scenarios (interpreter "
                         "timings on CPU CI are noisier)")
    ap.add_argument("--scaling-tolerance", type=float, default=0.5,
                    help="tolerance for the client_scaling/* scenarios "
                         "(forced-host-device shard_map timings are the "
                         "noisiest the bench records)")
    args = ap.parse_args(argv)

    with open(args.current) as f:
        cur = collect_speedups(json.load(f))
    with open(args.baseline) as f:
        base = collect_speedups(json.load(f))

    shared = sorted(set(cur) & set(base))
    if not shared:
        print("check_regression: no shared speedup scenarios — nothing gated")
        return 1  # a silently empty gate is a broken gate

    failures = []
    for name in shared:
        tol = tolerance_for(name, args)
        floor = base[name] * (1.0 - tol)
        abs_floor = abs_floor_for(name)
        if abs_floor is not None:
            floor = max(floor, abs_floor)
        status = "OK" if cur[name] >= floor else "REGRESSED"
        extra = f", abs floor {abs_floor:.2f}x" if abs_floor is not None else ""
        print(f"{status:9s} {name}: current {cur[name]:.2f}x vs baseline "
              f"{base[name]:.2f}x (floor {floor:.2f}x, tol {tol:.0%}{extra})")
        if cur[name] < floor:
            failures.append(name)
    for name in sorted(set(cur) - set(base)):
        print(f"NEW       {name}: {cur[name]:.2f}x (no baseline — not gated)")
    for name in sorted(set(base) - set(cur)):
        print(f"MISSING   {name}: in baseline but not in current run")

    if failures:
        print(f"\ncheck_regression: {len(failures)} scenario(s) regressed "
              f"past their tolerance vs baseline: {failures}")
        return 1
    print(f"\ncheck_regression: {len(shared)} shared scenario(s) within "
          f"tolerance of baseline")
    return 0


if __name__ == "__main__":
    sys.exit(main())
