"""The per-layer metrics that read the program's own spans: the sim fixture
cell through ``run_cell`` on the CPU, with a spec of its own that lists
them."""

import argparse
import math
import time

from bench import run
from bench.tests.harness import FIXTURES, spec

CELL = "afa_mnist_k100.sim"
SPAN_METRICS = ["experiment_setup_ms", "segment_stage_ms",
                "h2d_mb_per_experiment", "segment_retraces_in_window"]


def test_span_metrics_read_on_the_sim_cell(tmp_path):
    s = spec(CELL, "tiny_sim", "tiny_experiments")
    s["per_layer"] = [{"name": n, "unit": "x", "moves": "rounds_per_s",
                       "workloads": [CELL]} for n in SPAN_METRICS]
    args = argparse.Namespace(workload=CELL, seed=2**31 + 11, seconds=1.0, trace=1)
    res = run.run_cell(args, dirs=[FIXTURES, run.BENCH], spec=s,
                       require_tpu=False, trace_dir=str(tmp_path / "trace"),
                       t_start=time.perf_counter(), compile_cache=False)
    assert res["correct"], res["checks"]
    values = {n: res["metrics"][n]["value"] for n in SPAN_METRICS}
    assert all(math.isfinite(v) for v in values.values()), values
    assert values["experiment_setup_ms"] > 0 and values["segment_stage_ms"] > 0
    assert values["segment_retraces_in_window"] == 0
    # tiny_sim: 8 clients of 20 samples x 16 features staged once (at most
    # one more, smaller, staging), the 64 x 16 test set once
    full = 8 * 20 * (16 * 4 + 4) + 8 * (4 + 4 + 1 + 4) + 64 * (16 * 4 + 4)
    assert full * 1e-6 <= values["h2d_mb_per_experiment"] < 2 * full * 1e-6
    labels = [label for label, _ in res["breakdown"]["idle_gaps"]]
    assert any(label.startswith("fed.") for label in labels), labels

