"""Drive ``bench/run.py``'s ``run_cell`` on the CPU at a test size: the
chip check is skipped, everything else of a run (set-up, window, check
against the reference, metrics) runs.  Cells here carry the names of the
real cells, so their limits are the real ``bench/limits`` files; their
configuration and traffic are the test files under ``fixtures/``."""

from __future__ import annotations

import argparse
import time
from pathlib import Path

from bench import run

FIXTURES = Path(__file__).parent / "fixtures"
PER_LAYER = ["round_mfu", "device_idle_share", "compiles_in_window",
             "experiment_staging_ms", "ingress_ms", "fire_ms", "collective_share",
             "rounds_in_window"]


def spec(cell: str, config: str, traffic: str, chips: int = 1) -> dict:
    return {
        "configs": [{"name": config,
                     "file": f"bench/tests/fixtures/configs/{config}.json"}],
        "workloads": [{"name": cell, "config": config, "traffic": traffic,
                       "chips": chips}],
        "end_to_end": [
            {"name": "rounds_per_s", "unit": "rounds/s"},
            {"name": "setup_s", "unit": "s"},
            {"name": "round_p95_ms", "unit": "ms", "workloads": [cell]},
        ],
        "per_layer": [{"name": n, "unit": "x", "moves": "rounds_per_s",
                       "workloads": [cell]} for n in PER_LAYER],
    }


def run_tiny(cell, config, traffic, tmp_path, *, chips=1, trace=0,
             seconds=1.0, seed=2**31 + 7) -> dict:
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds, trace=trace)
    return run.run_cell(args, dirs=[FIXTURES, run.BENCH],
                        spec=spec(cell, config, traffic, chips),
                        require_tpu=False, trace_dir=str(tmp_path / "trace"),
                        t_start=time.perf_counter(), compile_cache=False)
