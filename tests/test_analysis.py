"""Tests for the repro.analysis invariant linter: race detection (including
the seeded known-bad geometry), launch budgets via the analysis API,
host-transfer detection, retrace auditing, the collective budget on a forced
multi-device host (subprocess), and the lint CLI."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    Finding,
    LaunchBudget,
    Report,
    analyze_pallas_races,
    check_launch_budget,
    check_no_host_transfers,
    count_pallas_launches,
    pallas_launch_names,
    pow2_bucket_bound,
)
from repro.analysis.registry import (
    LAUNCH_BUDGETS,
    LINT_MODES,
    known_bad_findings,
    run_lint,
)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
RNG = np.random.default_rng(11)


def _u(K=8, d=256):
    return jnp.asarray(RNG.normal(size=(K, d)).astype(np.float32))


# ------------------------------ grid races -----------------------------------


def test_known_bad_geometry_is_detected_as_error():
    """The acceptance criterion: a multi-grid-step accumulating gram on the
    parallel-grid route MUST be reported as an error."""
    findings = known_bad_findings()
    errors = [f for f in findings if f.severity == "error"]
    assert errors, findings
    assert any("read-modify-write" in f.message for f in errors)
    assert any("_gram_kernel" in f.message for f in errors)


def test_race_unsafe_gram_flagged_only_on_parallel_grids():
    from repro.kernels.gram import gram as raw_gram

    u = _u()
    fn = lambda x: raw_gram(x, block_d=64, interpret=False)  # noqa: E731
    assert any(
        f.severity == "error"
        for f in analyze_pallas_races(fn, u, parallel_grid=True)
    )
    # sequential grid (TPU Mosaic): the same geometry is legal
    assert analyze_pallas_races(fn, u, parallel_grid=False) == []
    # interpreted launches are sequential even on the parallel route
    fn_i = lambda x: raw_gram(x, block_d=64, interpret=True)  # noqa: E731
    assert analyze_pallas_races(fn_i, u, parallel_grid=True) == []


def test_forced_gpu_geometry_is_race_free():
    """ops.py's single-grid-step forcing is what the detector proves: the
    ops-level gram under compiled off-TPU geometry has no multi-step RMW."""
    from repro.kernels.ops import gram as ops_gram

    findings = analyze_pallas_races(
        lambda x: ops_gram(x, interpret=False), _u(), parallel_grid=True
    )
    assert findings == []


def test_per_step_kernels_clean_on_parallel_grids():
    from repro.kernels.ops import coord_median, weighted_sum

    u = _u()
    w = jnp.ones((u.shape[0],), jnp.float32)
    assert analyze_pallas_races(
        lambda a, b: weighted_sum(a, b, interpret=True), w, u,
        parallel_grid=True,
    ) == []
    assert analyze_pallas_races(
        lambda a: coord_median(a, interpret=True), u, parallel_grid=True
    ) == []


def test_lying_declaration_is_an_error_on_every_route():
    """A kernel declared parallel_grid_safe=True whose jaxpr accumulates
    across grid steps is flagged even on a sequential target."""
    from jax.experimental import pallas as pl

    from repro.kernels.meta import KERNEL_GEOMETRY, register_kernel_geometry

    def _lint_lying_kernel(x_ref, o_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += x_ref[...] @ x_ref[...].T

    def launch(x):
        d = x.shape[1]
        return pl.pallas_call(
            _lint_lying_kernel,
            name="_lint_lying_kernel",
            grid=(4,),
            in_specs=[pl.BlockSpec((x.shape[0], d // 4), lambda b: (0, b))],
            out_specs=pl.BlockSpec((x.shape[0], x.shape[0]), lambda b: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((x.shape[0], x.shape[0]), x.dtype),
            interpret=True,
        )(x)

    register_kernel_geometry("_lint_lying_kernel", "per-step", True)
    try:
        findings = analyze_pallas_races(launch, _u(8, 64), parallel_grid=False)
        assert any(
            f.severity == "error" and "parallel_grid_safe=True" in f.message
            for f in findings
        ), findings
    finally:
        KERNEL_GEOMETRY.pop("_lint_lying_kernel", None)


def test_meta_rejects_contradictory_declaration():
    from repro.kernels.meta import register_kernel_geometry

    with pytest.raises(ValueError, match="never be"):
        register_kernel_geometry("_impossible", "cross-step", True)
    with pytest.raises(ValueError, match="invalid"):
        register_kernel_geometry("_impossible", "sometimes", False)


# ---------------------------- launch budgets ---------------------------------


def test_launch_budget_api_reproduces_pr6_afa_budgets():
    """The documented budgets (fused = exactly 1, chained >= 2, jnp = 0)
    via the analysis API, not string matching."""
    from repro.core.afa import AFAConfig, afa_aggregate

    u, K = _u(10, 64), 10
    n_k = jnp.ones((K,), jnp.float32)
    p_k = jnp.full((K,), 0.5, jnp.float32)

    def route(launch, kernels="interpret"):
        cfg = AFAConfig(variant="gram", use_kernels=kernels,
                        kernel_launch=launch)
        return lambda a, b, c: afa_aggregate(a, b, c, config=cfg)

    assert check_launch_budget(
        route("fused"), u, n_k, p_k, budget=LAUNCH_BUDGETS["afa[fused]"]
    ) == []
    assert check_launch_budget(
        route("chained"), u, n_k, p_k, budget=LAUNCH_BUDGETS["afa[chained]"]
    ) == []
    assert pallas_launch_names(route("fused"), u, n_k, p_k) == [
        "_afa_screen_onepass_kernel"
    ]
    assert count_pallas_launches(route("fused", False), u, n_k, p_k) == 0


def test_launch_budget_violation_yields_error_finding():
    from repro.kernels.ops import gram as ops_gram

    findings = check_launch_budget(
        lambda x: ops_gram(x, interpret=True), _u(),
        budget=LaunchBudget(exact=2), target="gram",
    )
    assert len(findings) == 1
    assert findings[0].severity == "error"
    assert "_gram_kernel" in findings[0].message


# ---------------------------- host transfers ---------------------------------


def test_callback_inside_scan_body_is_flagged():
    def bad(x):
        def body(c, _):
            jax.debug.print("c={c}", c=c)  # traces to debug_print
            return c + 1.0, c

        return jax.lax.scan(body, x, None, length=4)

    findings = check_no_host_transfers(bad, jnp.float32(0.0))
    assert any(
        f.severity == "error" and "debug_print" in f.message
        for f in findings
    )


def test_clean_scan_has_no_transfer_findings():
    def good(x):
        return jax.lax.scan(lambda c, _: (c * 1.5, c), x, None, length=4)

    assert check_no_host_transfers(good, jnp.float32(1.0)) == []


# ------------------------------- retrace -------------------------------------


def test_pow2_bucket_bound_is_logarithmic():
    assert pow2_bucket_bound(range(1, 33), cap=32) == 6  # 1,2,4,8,16,32
    assert pow2_bucket_bound([3, 5, 9, 17], cap=32) == 4
    assert pow2_bucket_bound([7, 8], cap=8) == 1


def test_audit_jit_cache_detects_bound_violation():
    from repro.analysis import audit_jit_cache

    @jax.jit
    def f(x):
        return x * 2.0

    calls = [(jnp.zeros((4,), jnp.float32),), (jnp.zeros((8,), jnp.float32),)]
    assert audit_jit_cache(f, calls, bound=2) == []
    findings = audit_jit_cache(f, calls, bound=1)
    assert len(findings) == 1 and findings[0].severity == "error"


def test_tree_dispatch_sweep_stays_within_pow2_bound():
    """The engine retrace contract on the real entry point: sweeping live
    counts across 4 pow2 buckets creates at most 4 jit entries, and the
    identical repeat adds none."""
    from repro.analysis import audit_jit_cache
    from repro.core.baselines import RuleOptions, _dispatch_tree_jit
    from repro.data.sharding import pow2_bucket

    ks, cap = (3, 5, 9, 17), 32
    opts = RuleOptions(use_kernels=False)
    calls = []
    for k in ks:
        b = pow2_bucket(k, cap)
        stacked = {"w": jnp.zeros((b, 6), jnp.float32)}
        calls.append((
            (stacked, jnp.ones((b,), jnp.float32), None, jnp.arange(b) < k),
            {"name": "fa", "opts": opts, "layout": "packed"},
        ))
    findings = audit_jit_cache(
        _dispatch_tree_jit, calls, bound=pow2_bucket_bound(ks, cap)
    )
    assert findings == []


# --------------------- collective budget (multi-device) ----------------------


_COLLECTIVE_SCRIPT = r"""
import json, os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
).strip()
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.analysis.collectives import CollectiveBudget, check_screening_budget
from repro.analysis.registry import run_lint
from repro.core.afa import AFAConfig, afa_aggregate
from repro.launch.mesh import client_axis, make_client_mesh

# 1. the registry check itself must audit (not info-skip) and pass
rep = run_lint(checks=("collective-budget",))
print("REGISTRY::" + json.dumps({
    "ok": rep.ok,
    "severities": [f.severity for f in rep.findings],
}))

# 2. a deliberately tight budget must FAIL — proving the checker counts the
# screening loop's real collectives rather than vacuously passing
mesh = make_client_mesh(2)
axis = client_axis(mesh)
cfg = AFAConfig(variant="iterative", client_axis=axis, client_shards=2)
rng = np.random.default_rng(0)
u = jnp.asarray(rng.normal(size=(8, 128)).astype(np.float32))
u = u.at[:2].multiply(25.0)
n_k = jnp.asarray(rng.integers(1, 50, size=8).astype(np.float32))
p_k = jnp.full((8,), 0.5, jnp.float32)
mask = jnp.ones((8,), bool)

def body(u, n_k, p_k, mask):
    r = afa_aggregate(u, n_k, p_k, mask0=mask, config=cfg)
    return (r.aggregate, r.good_mask, r.rounds, r.similarities)

spec = P(axis)
sharded = jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 4,
                        out_specs=(P(), spec, P(), spec), check_vma=False)
tight = check_screening_budget(
    sharded, u, n_k, p_k, mask,
    budget=CollectiveBudget(max_heavy_psum=0, max_heavy_all_gather=0,
                            scalar_elements=4),
)
print("TIGHT::" + json.dumps({
    "errors": sum(1 for f in tight if f.severity == "error"),
    "messages": [f.message[:120] for f in tight],
}))
"""


def _run_sub(script):
    return subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": SRC},
    )


def _payload(out, mark):
    line = next(ln for ln in out.splitlines() if ln.startswith(mark))
    return json.loads(line[len(mark):])


def test_sharded_afa_collective_budget_on_forced_multidevice():
    """PR 7's contract via the analysis API on a 4-device CPU host: one
    heavy psum + one heavy all_gather per screening iteration passes; a
    zero budget fails (the checker sees the real collectives)."""
    res = _run_sub(_COLLECTIVE_SCRIPT)
    assert res.returncode == 0, res.stderr[-3000:]
    registry = _payload(res.stdout, "REGISTRY::")
    assert registry["ok"], registry
    assert registry["severities"] == []  # audited, no info-skip
    tight = _payload(res.stdout, "TIGHT::")
    assert tight["errors"] >= 2, tight  # both the psum and the all_gather


def test_missing_while_loop_is_an_error_not_a_pass():
    from repro.analysis import check_screening_budget

    findings = check_screening_budget(lambda x: x * 2.0, jnp.ones((4,)))
    assert len(findings) == 1 and findings[0].severity == "error"
    assert "no while loop" in findings[0].message


# ------------------------------ registry/CLI ---------------------------------


def test_run_lint_clean_on_current_codebase_interpret_column():
    report = run_lint(
        checks=("launch-budget", "grid-race", "host-transfer"),
        modes=("jnp", "interpret"),
    )
    assert report.ok, report.to_json()
    assert report.errors == []


def test_pallas_gpu_column_proves_forced_geometry_safe():
    report = run_lint(
        checks=("grid-race",), modes=("pallas-gpu",)
    )
    assert report.ok, report.to_json()


def test_unbudgeted_registered_rule_is_flagged():
    """Registering a rule without a LAUNCH_BUDGETS row is itself a lint
    error — the budget table cannot silently go stale."""
    from repro.core.baselines import RULES, register_rule

    def _noop_rule(u, n_k, p_k, mask, opts):
        from repro.core.baselines import fa_aggregate

        return fa_aggregate(u, n_k, p_k, mask)

    register_rule("_lint_test_rule", _noop_rule)
    try:
        report = run_lint(checks=("launch-budget",), modes=("jnp",),
                          rules=("fa",))
        assert any(
            f.severity == "error" and "_lint_test_rule" in f.message
            for f in report.findings
        ), report.to_json()
    finally:
        RULES.pop("_lint_test_rule", None)


def test_run_lint_rejects_unknown_mode_and_check():
    with pytest.raises(ValueError, match="unknown lint mode"):
        run_lint(modes=("metal",))
    with pytest.raises(ValueError, match="unknown check"):
        run_lint(checks=("vibes",))


def test_report_serialization_roundtrip():
    rep = Report(meta={"x": 1})
    rep.extend([Finding("grid-race", "error", "t", "msg|with`pipe")])
    rep.mark_ran("grid-race")
    doc = json.loads(rep.to_json())
    assert doc["ok"] is False
    assert doc["counts"]["error"] == 1
    assert doc["findings"][0]["check"] == "grid-race"
    md = rep.to_markdown()
    assert "FAIL" in md and "grid-race" in md and "\\|" in md


def test_cli_smoke_and_known_bad_gate():
    env = {**os.environ, "PYTHONPATH": SRC}
    res = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint",
         "--rules", "fa", "--modes", "interpret",
         "--checks", "launch-budget", "grid-race",
         "--json", "/tmp/lint_test.json", "--markdown", "/tmp/lint_test.md"],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    doc = json.loads(open("/tmp/lint_test.json").read())
    assert doc["ok"] and doc["checks_run"] == ["launch-budget", "grid-race"]
    assert "PASS" in open("/tmp/lint_test.md").read()

    res_kb = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "--known-bad"],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert res_kb.returncode == 0, res_kb.stderr[-2000:]
    assert "race DETECTED" in res_kb.stdout


def test_lint_modes_cover_policy_matrix():
    # the CLI matrix must stay in sync with the kernel policy's modes
    from repro.kernels.policy import MODES

    assert set(LINT_MODES) <= set(MODES) | {"jnp"}
    assert "pallas-gpu" in LINT_MODES  # the parallel-grid column
