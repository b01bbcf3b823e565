"""Lint-check registry and the rule × kernel-mode matrix driver.

The linter's unit of work is a **check** — a callable that traces some
entry points and returns findings.  Checks register here by name; the CLI
(:mod:`repro.analysis.lint`) runs a selected subset over the full
aggregation-rule registry × kernel-policy matrix and aggregates one
:class:`~repro.analysis.report.Report`.

Registering coverage for new code (DESIGN.md §"Static invariant linting"):

* a new **kernel** declares its geometry in its own module via
  :func:`repro.kernels.meta.register_kernel_geometry`; the grid-race check
  picks it up automatically through whatever rules launch it;
* a new **aggregation rule** gets a row in :data:`LAUNCH_BUDGETS` (its
  expected ``pallas_call`` count per kernel mode); registering the rule in
  ``repro.core.baselines.RULES`` without a budget row is a lint error, so
  the budget table cannot silently go stale;
* a genuinely new *kind* of invariant adds a ``@register_check`` function
  here.
"""

from __future__ import annotations

from typing import Callable, Iterator, NamedTuple

import numpy as np

from repro.analysis.launches import LaunchBudget, check_launch_budget
from repro.analysis.races import analyze_pallas_races
from repro.analysis.report import Finding, Report, error, info
from repro.analysis.transfers import check_no_host_transfers

# Kernel-policy modes the matrix covers on a CPU host.  "pallas" (TPU
# Mosaic) traces identically to "pallas-gpu" at the jaxpr level but cannot
# resolve off-TPU; "pallas-gpu" is the route whose single-grid-step geometry
# the race detector statically proves safe, so it is the interesting column.
LINT_MODES = ("jnp", "interpret", "pallas-gpu")

# Grid parallelism per mode: only the Triton route runs grid steps
# concurrently; Mosaic and the interpreter are sequential.
PARALLEL_GRID_MODES = frozenset({"pallas-gpu"})

# Declarative pallas_call budgets per aggregation rule under a kernel mode
# (PR 6's documented counts).  Under "jnp" every rule must trace to zero
# launches.  AFA is keyed per launch strategy.
LAUNCH_BUDGETS: dict[str, LaunchBudget] = {
    "fa": LaunchBudget(exact=1),
    "mkrum": LaunchBudget(exact=2),           # gram + weighted sum
    "comed": LaunchBudget(exact=1),
    "trimmed_mean": LaunchBudget(exact=1),
    "bulyan": LaunchBudget(exact=3),          # gram + wsum + masked comed
    "norm_clip": LaunchBudget(exact=1),
    "geomed": LaunchBudget(exact=0),          # pure-jnp rule on every route
    "centered_clip": LaunchBudget(exact=0),   # pure-jnp rule on every route
    "afa[fused]": LaunchBudget(exact=1),      # the PR 6 tentpole claim
    "afa[chained]": LaunchBudget(min=2),      # gram + weighted sum at least
}


class LintCheck(NamedTuple):
    name: str
    fn: Callable[[Report, "LintScope"], None]
    doc: str


CHECKS: dict[str, LintCheck] = {}


def register_check(name: str, doc: str = ""):
    def deco(fn: Callable[[Report, LintScope], None]) -> Callable:
        CHECKS[name] = LintCheck(name, fn, doc or (fn.__doc__ or ""))
        return fn

    return deco


class LintScope(NamedTuple):
    """What one lint run covers."""

    rules: tuple[str, ...]
    modes: tuple[str, ...]


class _Target(NamedTuple):
    label: str
    fn: Callable
    args: tuple
    mode: str
    budget: LaunchBudget | None


def _workload(K: int = 8, d: int = 256, seed: int = 0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(size=(K, d)).astype(np.float32))
    u = u.at[: max(K // 4, 1)].multiply(25.0)  # outliers: screening iterates
    n_k = jnp.asarray(rng.integers(1, 50, size=K).astype(np.float32))
    p_k = jnp.asarray(rng.uniform(0.2, 0.8, size=K).astype(np.float32))
    mask = jnp.ones((K,), bool)
    return u, n_k, p_k, mask


def _adapter_workload(K: int = 8, seed: int = 0):
    """Packed LoRA adapter proposals — the workload-layer twin of
    :func:`_workload`.  Rows are one client's adapter tree packed with its
    ``PackSpec`` (exactly the buffer the fused engine hands ``dispatch_rule``
    for delta workloads), so every rule × mode budget is checked on the
    adapter wire format too."""
    import jax
    import jax.numpy as jnp

    from repro.fed.workload import init_lora_adapters
    from repro.utils.trees import pack_spec, pack_stack, tree_broadcast_clients

    layers = {
        "attn": {
            "wq": jnp.zeros((2, 16, 16), jnp.float32),
            "wo": jnp.zeros((2, 16, 16), jnp.float32),
        }
    }
    adapters = init_lora_adapters(
        jax.random.PRNGKey(seed), layers, ("wq", "wo"), rank=2
    )
    rng = np.random.default_rng(seed)
    u = pack_stack(tree_broadcast_clients(adapters, K), pack_spec(adapters))
    u = u + jnp.asarray(rng.normal(size=u.shape).astype(np.float32))
    u = u.at[: max(K // 4, 1)].multiply(25.0)  # outliers: screening iterates
    n_k = jnp.asarray(rng.integers(1, 50, size=K).astype(np.float32))
    p_k = jnp.asarray(rng.uniform(0.2, 0.8, size=K).astype(np.float32))
    mask = jnp.ones((K,), bool)
    return u, n_k, p_k, mask


def _registered_rules() -> dict:
    import repro.core.extra_rules  # noqa: F401  (registers geomed & co)
    from repro.core.baselines import RULES

    return RULES


def iter_targets(scope: LintScope) -> Iterator[_Target]:
    """One traceable entry point per (rule, mode) cell — AFA contributes a
    cell per launch strategy, and every cell is traced twice: on the dense
    full-parameter buffer and on the packed adapter buffer
    (``adapter:{rule}/{mode}``) with the SAME budget, since the dispatch path
    must be workload-agnostic."""
    from repro.core.afa import AFAConfig
    from repro.core.baselines import RuleOptions, dispatch_rule

    rules = _registered_rules()
    args = _workload()
    adapter_args = _adapter_workload()
    for mode in scope.modes:
        use_kernels: bool | str = False if mode == "jnp" else mode
        for name in scope.rules:
            if name not in rules:
                continue
            variants: list[tuple[str, RuleOptions]] = []
            if name == "afa":
                for launch in ("fused", "chained"):
                    cfg = AFAConfig(variant="gram", use_kernels=use_kernels,
                                    kernel_launch=launch)
                    variants.append((
                        f"afa[{launch}]",
                        RuleOptions(use_kernels=use_kernels, afa=cfg),
                    ))
            else:
                variants.append((name, RuleOptions(use_kernels=use_kernels)))
            for label, opts in variants:
                budgeted = LAUNCH_BUDGETS.get(label)
                budget = (
                    LaunchBudget(exact=0) if mode == "jnp" else budgeted
                )

                def entry(u, n_k, p_k, mask, _name=name, _opts=opts):
                    return dispatch_rule(_name, u, n_k, p_k, mask, _opts)

                yield _Target(f"{label}/{mode}", entry, args, mode, budget)
                yield _Target(
                    f"adapter:{label}/{mode}", entry, adapter_args, mode,
                    budget,
                )


@register_check(
    "launch-budget",
    "pallas_call counts per rule × mode match the declared budgets",
)
def _check_launch_budgets(report: Report, scope: LintScope) -> None:
    rules = _registered_rules()
    for name in rules:
        keyed = {name} if name != "afa" else {"afa[fused]", "afa[chained]"}
        for k in keyed:
            if k not in LAUNCH_BUDGETS:
                report.extend([error(
                    "launch-budget", k,
                    f"rule {name!r} is registered in repro.core but has no "
                    "launch budget row in repro.analysis.registry."
                    "LAUNCH_BUDGETS — declare its expected pallas_call "
                    "count",
                )])
    for t in iter_targets(scope):
        if t.budget is None:
            continue
        report.extend(check_launch_budget(
            t.fn, *t.args, budget=t.budget, target=t.label
        ))


@register_check(
    "grid-race",
    "no pallas output block is revisited with RMW on a parallel grid",
)
def _check_grid_races(report: Report, scope: LintScope) -> None:
    for t in iter_targets(scope):
        report.extend(analyze_pallas_races(
            t.fn, *t.args,
            parallel_grid=t.mode in PARALLEL_GRID_MODES,
            target=t.label,
        ))


@register_check(
    "host-transfer",
    "no callbacks/device transfers inside screening or fused-scan bodies",
)
def _check_host_transfers(report: Report, scope: LintScope) -> None:
    for t in iter_targets(scope):
        report.extend(check_no_host_transfers(t.fn, *t.args, target=t.label))
    # the fused engine's T-round scan body — the invariant the fused
    # engine's whole speedup rests on
    scan_fn, _, trace_args = _tiny_fused_sim()
    report.extend(check_no_host_transfers(
        scan_fn, *trace_args, target="engine.fused_scan"
    ))
    # ...and the same scan with the transformer LoRA workload in the round
    # body: the scanned frozen-base forward/backward must stay transfer-free
    lora_fn, lora_args = _tiny_lora_sim()
    report.extend(check_no_host_transfers(
        lora_fn, *lora_args, target="engine.lora_fused_scan"
    ))


def _tiny_fused_sim():
    """A minimal fused simulation, built (never run) for engine-level lint.

    Returns ``(scan_fn, round_fn, (params0, seed, data))``.
    """
    import jax.numpy as jnp

    from repro.data import make_mnist_like
    from repro.fed import ServerConfig, SimConfig
    from repro.fed.simulator import _fused_data, _make_setup_sim, _Setup

    data = make_mnist_like(n_train=120, n_test=40, dim=24)
    sim = SimConfig(
        num_clients=5, bad_frac=0.4, scenario="byzantine", rounds=2,
        local_epochs=1, batch_size=30, hidden=(8,), engine="fused", seed=0,
    )
    setup = _Setup(data, sim)
    scan_fn, round_fn = _make_setup_sim(
        setup, ServerConfig(rule="afa", num_clients=sim.num_clients)
    )
    return scan_fn, round_fn, (
        setup.params0, jnp.uint32(sim.seed), _fused_data(setup)
    )


def _tiny_lora_sim():
    """A minimal LoRA fused simulation, built (never run) for engine lint.

    Returns ``(scan_fn, (params0, seed, data))``.
    """
    import jax
    import jax.numpy as jnp

    from repro.fed.engine import EngineConfig, make_fused_sim
    from repro.fed.server import ServerConfig, make_rule_options
    from repro.fed.workload import get_workload, make_llm_fused_data
    from repro.models import ModelConfig

    cfg = ModelConfig(
        name="lint-lora", family="dense", num_layers=2, d_model=32,
        vocab_size=64, num_heads=4, num_kv_heads=2, d_ff=64,
        block_q=16, block_k=16,
    )
    workload = get_workload("lora", model_cfg=cfg, rank=2)
    K = 4
    data = make_llm_fused_data(
        cfg, clients=K, samples_per_client=4, seq=16, n_test=4
    )
    bad = np.zeros((K,), bool)
    bad[0] = True
    scfg = ServerConfig(rule="afa", num_clients=K)
    scan_fn, _ = make_fused_sim(
        workload,
        EngineConfig(scenario="byzantine", lr=0.2, momentum=0.9, dropout=False),
        rule="afa", opts=make_rule_options(scfg, K),
        delta_block=scfg.delta_block, num_clients=K, num_rounds=2,
        batch_s=1, batch_b=2, bad_mask=bad,
    )
    params0 = workload.init_params(jax.random.PRNGKey(0))
    return scan_fn, (params0, jnp.uint32(0), data)


@register_check(
    "retrace",
    "jit cache misses stay within the O(log K) pow2-bucket bound",
)
def _check_retrace(report: Report, scope: LintScope) -> None:
    """Sweep the tree-dispatch entry point over live-client counts spanning
    several pow2 buckets; the jit cache must hold at most one entry per
    bucket, and an identical repeat sweep must add none (drift)."""
    import jax.numpy as jnp

    from repro.analysis.retrace import (
        audit_host_cache,
        audit_jit_cache,
        pow2_bucket_bound,
    )
    from repro.core.baselines import RuleOptions, _dispatch_tree_jit
    from repro.data.sharding import pow2_bucket

    ks = (3, 5, 9, 17)
    cap = 32
    bound = pow2_bucket_bound(ks, cap)
    opts = RuleOptions(use_kernels=False)
    calls = []
    for k in ks:
        b = pow2_bucket(k, cap)
        stacked = {
            "w": jnp.zeros((b, 6), jnp.float32),
            "b": jnp.zeros((b, 2), jnp.float32),
        }
        n_k = jnp.ones((b,), jnp.float32)
        mask = jnp.arange(b) < k
        calls.append((
            (stacked, n_k, None, mask),
            {"name": "fa", "opts": opts, "layout": "packed"},
        ))
    report.extend(audit_jit_cache(
        _dispatch_tree_jit, calls, bound=bound,
        target=f"dispatch_rule_tree[fa] sweep K={list(ks)}",
    ))

    # adapter-shaped stacks (the LoRA workload's proposal trees) obey the
    # same pow2-bucket bound — the dispatch cache must not key on tree shape
    # beyond the bucket
    import jax

    from repro.fed.workload import init_lora_adapters
    from repro.utils.trees import tree_broadcast_clients

    adapters = init_lora_adapters(
        jax.random.PRNGKey(0),
        {"attn": {"wq": jnp.zeros((2, 8, 8), jnp.float32)}},
        ("wq",), rank=2,
    )
    acalls = []
    for k in ks:
        b = pow2_bucket(k, cap)
        acalls.append((
            (
                tree_broadcast_clients(adapters, b),
                jnp.ones((b,), jnp.float32),
                None,
                jnp.arange(b) < k,
            ),
            {"name": "fa", "opts": opts, "layout": "packed"},
        ))
    report.extend(audit_jit_cache(
        _dispatch_tree_jit, acalls, bound=bound,
        target=f"dispatch_rule_tree[fa] adapter sweep K={list(ks)}",
    ))

    # engine builder: rebuilding the identical fused sim must be a host
    # cache hit, not a silent re-trace of the whole scan
    from repro.fed import engine as engine_mod

    report.extend(audit_host_cache(
        engine_mod._make_fused_sim_cached,
        lambda: (_tiny_fused_sim(), _tiny_fused_sim()),
        bound=1,
        target="engine.make_fused_sim rebuild",
    ))


@register_check(
    "collective-budget",
    "sharded AFA: ≤ 1 heavy psum + 1 heavy all_gather per screening "
    "iteration",
)
def _check_collective_budget(report: Report, scope: LintScope) -> None:
    """PR 7's contract, checked on the shard_map-traced jaxpr.  Needs a
    multi-device host (``--host-devices``); single-device runs record an
    info finding instead of silently passing."""
    import jax

    if jax.device_count() < 2:
        report.extend([info(
            "collective-budget", "afa[sharded]",
            f"host has {jax.device_count()} device(s); the shard_map trace "
            "needs >= 2 (rerun with --host-devices N)",
        )])
        return

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.analysis.collectives import (
        CollectiveBudget,
        check_screening_budget,
    )
    from repro.core.afa import AFAConfig, afa_aggregate
    from repro.launch.mesh import client_axis, make_client_mesh

    shards = 2
    mesh = make_client_mesh(shards)
    axis = client_axis(mesh)
    cfg = AFAConfig(
        variant="iterative", client_axis=axis, client_shards=shards
    )
    u, n_k, p_k, mask = _workload(K=8, d=128)

    def body(u, n_k, p_k, mask):
        r = afa_aggregate(u, n_k, p_k, mask0=mask, config=cfg)
        # shard_map out_specs need a plain tuple, not the AFAResult pytree
        return (r.aggregate, r.good_mask, r.rounds, r.similarities)

    spec = P(axis)
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(P(), spec, P(), spec),
        check_vma=False,
    )
    # scalar_elements=4 sits above the 3-element mean/var/count stats psum
    # and below anything scaling with K or d, so the lint workload's small
    # K=8 all_gather still counts as heavy
    report.extend(check_screening_budget(
        sharded, u, n_k, p_k, mask,
        budget=CollectiveBudget(max_heavy_psum=1, max_heavy_all_gather=1,
                                scalar_elements=4),
        target=f"afa[sharded x{shards}]",
    ))


def known_bad_findings() -> list[Finding]:
    """The seeded known-bad geometry: a multi-grid-step accumulating gram
    launched compiled (``interpret=False``) on the parallel-grid route,
    bypassing ``ops.py``'s one-pass forcing.  The race detector MUST flag
    this — CI runs it to prove the detector has teeth."""
    from repro.kernels.gram import gram as raw_gram

    u, _, _, _ = _workload(K=8, d=256)
    return analyze_pallas_races(
        lambda x: raw_gram(x, block_d=64, interpret=False),
        u,
        parallel_grid=True,
        target="known-bad:gram[block_d=d/4]/pallas-gpu",
    )


def run_lint(
    checks: tuple[str, ...] | None = None,
    rules: tuple[str, ...] | None = None,
    modes: tuple[str, ...] | None = None,
) -> Report:
    """Run the selected checks over the rule × mode matrix."""
    import jax

    all_rules = tuple(sorted(_registered_rules()))
    scope = LintScope(
        rules=tuple(rules) if rules else all_rules,
        modes=tuple(modes) if modes else LINT_MODES,
    )
    unknown_modes = set(scope.modes) - set(LINT_MODES)
    if unknown_modes:
        raise ValueError(
            f"unknown lint mode(s) {sorted(unknown_modes)}; "
            f"expected a subset of {LINT_MODES}"
        )
    report = Report(meta={
        "rules": list(scope.rules),
        "modes": list(scope.modes),
        "devices": jax.device_count(),
        "backend": jax.default_backend(),
    })
    selected = checks if checks else tuple(CHECKS)
    for name in selected:
        if name not in CHECKS:
            raise ValueError(
                f"unknown check {name!r}; registered: {sorted(CHECKS)}"
            )
        CHECKS[name].fn(report, scope)
        report.mark_ran(name)
    return report
