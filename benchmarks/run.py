"""Benchmark harness entry point — one module per paper table/figure plus the
roofline reader.  Prints ``name,us_per_call,derived`` CSV.

  python -m benchmarks.run [--quick] [--only table1,fig3]
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import emit
from repro.utils.compile_cache import use_compile_cache

MODULES = ["table1_robustness", "table2_detection", "fig2_convergence",
           "fig3_aggregation_time", "fused_engine",
           "ablation_xi", "roofline"]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced sizes/rounds")
    ap.add_argument("--only", default=None, help="comma-separated module prefixes")
    args = ap.parse_args()
    use_compile_cache()

    only = args.only.split(",") if args.only else None
    print("name,us_per_call,derived")
    rc = 0
    for mod_name in MODULES:
        if only and not any(mod_name.startswith(o) for o in only):
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            emit(mod.run(quick=args.quick))
            print(f"# {mod_name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            print(f"# {mod_name} FAILED: {e}", file=sys.stderr)
            rc = 1
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
