#!/usr/bin/env python3
"""The control of the LoRA cells' ``correct``: the plain reference
(``bench/reference/granite_lora.py``) put in the program's place with its
adapter path a precision step below the configuration's (adapters and their
momentum held in bfloat16, their matmuls on bf16 operands), routed by the
float32 reference's own expert choices as the reference is routed by the
program's, and held to the float32 reference by the cell's own comparison.  Prints one JSON line per
seed with every compared number and the cell's verdict on it, which has to
be not correct.

    python3 bench/control_lora.py --workload granite4h_small_lora_k16.sim --seeds 1 2 3

Each seed's experiment is the one the cell's check takes for that
``--seed``: the window's first.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def control_values(p: dict, seed: int, driver_cls, bench_cls) -> dict:
    from bench import data
    from bench.spans import CompileCounter, Spans

    d = driver_cls(bench_cls(p["cell"], p["config"], p["traffic"], seed, Spans(),
                             CompileCounter()))
    d.prepare()
    exp_seed = data.sub_seeds(seed, 10_000, salt=2)[0]
    ref = d.reference(exp_seed)
    ctrl = d.reference(exp_seed, ref["choices"], "bfloat16")
    return d.compare(dict(w1=ctrl["w1"], sims=ctrl["sims"], kept=ctrl["kept"]), ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from bench.check import judge
    from bench.run import BENCH, Bench, _load, plan as make_plan

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU found; the control is read on the chip", file=sys.stderr)
        return 1
    p = make_plan(json.loads((ROOT / "BENCHMARK.json").read_text()), args.workload, [BENCH])
    name = p["traffic"]["driver"]
    driver_cls = _load(BENCH / "drivers" / f"{name}.py", f"bench.drivers.{name}").Driver
    for seed in args.seeds:
        t0 = time.perf_counter()
        values = control_values(p, seed, driver_cls, Bench)
        limits = {k: v for k, v in p["limits"].items() if k in values}
        correct, _ = judge(values, limits)
        print(json.dumps({"workload": args.workload, "seed": seed, "control": values,
                          "correct": correct, "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
