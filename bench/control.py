#!/usr/bin/env python3
"""The control of a cell's ``correct``: the plain reference put in the
program's place, in the next precision below the configuration's, held to
the reference by the cell's own comparison.  Prints one JSON line per seed
with every compared number; each has to read above a limit somewhere for
the comparison to be worth anything.

    python3 bench/control.py --workload afa_mnist_k100.sim --seeds 1 2 3

sim cells: the first experiment of the run's window, trained in bfloat16
(params, activations, momentum) against the float32 reference.  serve
traffic: ``rounds`` closed-loop rounds replayed with three-pass bf16
contractions (``Precision.HIGH``, the step below the configuration's
``HIGHEST``) and, for comparison, one pass (``Precision.DEFAULT``), against
float64.  No serve cell is declared: its comparison does not separate the
three-pass control from the program (PERF.md).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def control_values(plan: dict, seed: int, rounds: int | None = None) -> dict:
    """``{precision: compared numbers}`` of each control run against the
    reference: ``bfloat16`` for sim traffic, ``high`` and ``default`` for
    serve traffic."""
    import numpy as np

    from bench import check, data
    from bench.drivers.serve_closed_loop import make_pool
    from bench.reference import fl_afa, serve_replay

    cfg, traffic = plan["config"], plan["traffic"]
    if traffic["driver"] == "sim_experiments":
        arrays = data.classification(seed, cfg["n_train"], cfg["n_test"],
                                     cfg["model"]["sizes"][0], cfg["classes"],
                                     cfg["class_separation"])
        exp_seed = data.sub_seeds(seed, 1, salt=2)[0]
        ref = fl_afa.run_experiment(arrays, cfg, exp_seed, traffic["rounds"])
        n_bad = int(round(cfg["bad_frac"] * cfg["clients"]))
        return {"bfloat16": check.compare_sim(fl_afa.run_experiment(
                    arrays, cfg, exp_seed, traffic["rounds"], "bfloat16"), ref, n_bad)}
    K = cfg["clients"]
    D = fl_afa.pack(fl_afa.init_params(0, tuple(cfg["model"]["sizes"])),
                    tuple(cfg["model"]["sizes"])).shape[-1]
    pool = make_pool(seed, traffic["pool_rounds"], K, D,
                     int(round(cfg["bad_frac"] * K)), traffic, cfg["byzantine_scale"])
    rng = np.random.default_rng(data.sub_seeds(seed, 1, salt=6)[0])
    orders = [rng.permutation(K) for _ in range(traffic["orders"])]
    srng = np.random.default_rng(data.sub_seeds(seed, 1, salt=8)[0])
    sampled = sorted(set(np.nonzero(srng.random(rounds) < traffic["sample_share"])[0].tolist())
                     | {rounds - 1})
    n_k = np.full(K, float(cfg["samples_per_client"]))
    ref = serve_replay.replay(pool, orders, rounds, n_k, cfg, sampled, "float64")
    return {p: check.compare_serve(serve_replay.replay(
                pool, orders, rounds, n_k, cfg, sampled, p), ref)
            for p in ("high", "default")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from bench.run import BENCH, plan as make_plan

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU found; the control is read on the chip", file=sys.stderr)
        return 1
    p = make_plan(json.loads((ROOT / "BENCHMARK.json").read_text()), args.workload, [BENCH])
    for seed in args.seeds:
        t0 = time.perf_counter()
        values = control_values(p, seed)
        print(json.dumps({"workload": args.workload, "seed": seed, "control": values,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
