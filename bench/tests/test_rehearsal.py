"""Every cell end to end on the CPU at a test size (kernels in interpret
mode), through the harness: set-up, window, the check against the plain
reference, and the metrics.  The test configuration, traffic mix and the
``rounds_in_window`` metric are files under ``fixtures/`` the harness finds
by name, as a later cell's would be."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.tests.harness import run_tiny

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("trace", [0, 1])
def test_sim_cell(tmp_path, trace):
    res = run_tiny("afa_mnist_k100.sim", "tiny_sim", "tiny_experiments", tmp_path,
                   trace=trace)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 8 and res["failed"] == 0
    names = set(res["metrics"])
    if trace:
        assert {"round_mfu", "device_idle_share", "compiles_in_window",
                "experiment_staging_ms", "rounds_in_window"} <= names
        assert res["metrics"]["compiles_in_window"]["value"] == 0
        assert res["device"]["busy_s"] > 0 and res["breakdown"]["device_ops"]
    else:
        assert names == {"rounds_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_cell(tmp_path, trace):
    res = run_tiny("afa_mnist_k100.serve", "tiny_serve", "tiny_closed_loop",
                   tmp_path, trace=trace)
    assert res["correct"], res["checks"]
    names = set(res["metrics"])
    if trace:
        assert {"round_mfu", "ingress_ms", "fire_ms", "rounds_in_window"} <= names
    else:
        assert names == {"rounds_per_s", "setup_s", "round_p95_ms"}


SHARDED = """
import json, sys, tempfile
from pathlib import Path
sys.path[:0] = [{root!r}, {src!r}]
from bench.tests.harness import run_tiny
res = run_tiny("afa_mnist_k400.sim_4chip", "tiny_sharded", "tiny_experiments",
               Path(tempfile.mkdtemp()), chips=4, trace=1)
print(json.dumps(res))
"""


def test_sharded_cell_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", SHARDED.format(root=str(ROOT), src=str(ROOT / "src"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"], res["checks"]
    assert res["metrics"]["collective_share"]["value"] > 0


@pytest.mark.parametrize("config", ["afa_mnist_k100", "afa_mnist_k400"])
def test_real_configurations_build_the_program_configs(config):
    """The cells' own configuration files (full size) turn into the
    program's ``SimConfig``/``ServerConfig`` as the drivers build them."""
    import types

    from bench.drivers.sim_experiments import Driver

    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" / "sim_experiments.json").read_text())
    bench = types.SimpleNamespace(config=cfg, traffic=traffic, seed=1)
    sim, server = Driver(bench)._configs(5)
    assert sim.num_clients == server.num_clients == cfg["clients"]
    assert sim.client_shards == cfg["client_shards"]
    assert server.kernel_plan.mode is True
