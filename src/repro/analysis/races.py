"""Pallas grid-race detector.

For every ``pallas_call`` eqn in a traced entry point this module

1. reconstructs each *output* block's ``index_map`` image across the whole
   grid (evaluating the index-map jaxpr at every grid step — pure integer
   arithmetic, no device work) to find blocks that are **revisited**;
2. classifies each output ref's access pattern inside the kernel jaxpr as
   read / write / read-modify-write (``get``/``swap``/``addupdate``
   primitives, with refs tracked through ``cond``/``scan`` sub-jaxprs by
   suffix-aligned invar mapping — the init-to-zero branch of an accumulator
   lives inside a ``cond``);
3. cross-checks the derived behavior against the kernel's *declared*
   geometry (:mod:`repro.kernels.meta`).

A block revisited with RMW semantics is safe only when grid steps execute
sequentially (TPU Mosaic, the Pallas interpreter).  On a parallel grid
(Triton / the ``pallas-gpu`` route) it is a data race — this statically
proves what ``ops.GPU_ONEPASS_BUDGET`` enforces by runtime carve-out.
"""

from __future__ import annotations

import itertools
import math
from typing import Any, NamedTuple

import jax

from repro.analysis.jaxpr_utils import (
    Var,
    eqns_by_primitive,
    is_drop_var,
    subjaxprs,
    trace,
)
from repro.analysis.launches import launch_name
from repro.analysis.report import Finding, error, warning
from repro.kernels.meta import kernel_geometry

# Primitives that touch a Ref.  ``get`` reads a window, ``swap`` stores one
# (returning the old value — a DropVar outvar means a pure store), and
# ``addupdate`` accumulates in place.
_REF_READ = "get"
_REF_SWAP = "swap"
_REF_ADDUPDATE = "addupdate"


class OutputAccess(NamedTuple):
    """Derived behavior of one pallas_call output across the grid."""

    kernel: str
    out_index: int
    grid: tuple[int, ...]
    steps_evaluated: int
    truncated: bool          # grid larger than the enumeration cap
    revisited: bool          # some block index tuple produced twice
    reads: bool
    writes: bool

    @property
    def rmw(self) -> bool:
        return self.reads and self.writes


def _track_ref_access(
    jaxpr: Any,
    tracked: dict[Any, int],
    reads: set[int],
    writes: set[int],
) -> None:
    """Accumulate read/write sets for tracked refs, recursing into
    sub-jaxprs with suffix-aligned invar mapping (cond branches take the
    eqn's trailing operands; scan/while bodies carry consts+carry)."""
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        iv = eqn.invars
        ref = iv[0] if iv and isinstance(iv[0], Var) else None
        slot = tracked.get(ref) if ref is not None else None
        if slot is not None and prim == _REF_READ:
            reads.add(slot)
            continue
        if slot is not None and prim == _REF_SWAP:
            writes.add(slot)
            if eqn.outvars and not is_drop_var(eqn.outvars[0]):
                reads.add(slot)
            continue
        if slot is not None and prim == _REF_ADDUPDATE:
            reads.add(slot)
            writes.add(slot)
            continue
        for val in eqn.params.values():
            for sub in subjaxprs(val):
                m = min(len(sub.invars), len(iv))
                sub_tracked: dict[Any, int] = {}
                for sv, ov in zip(sub.invars[-m:], iv[-m:]):
                    if isinstance(ov, Var) and ov in tracked:
                        sub_tracked[sv] = tracked[ov]
                if sub_tracked:
                    _track_ref_access(sub, sub_tracked, reads, writes)


def _eval_index_map(closed: Any, step: tuple[int, ...]) -> tuple[int, ...]:
    out = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *step)
    return tuple(int(x) for x in out)


def analyze_pallas_eqn(eqn: Any, step_cap: int = 4096) -> list[OutputAccess]:
    """Derived per-output access patterns for one ``pallas_call`` eqn."""
    gm = eqn.params["grid_mapping"]
    name = launch_name(eqn)
    grid = tuple(gm.grid)
    if any(not isinstance(g, int) for g in grid):
        # dynamic grid: cannot enumerate; report as truncated with 0 steps
        return [
            OutputAccess(name, i, grid, 0, True, False, False, False)
            for i in range(gm.num_outputs)
        ]
    total = math.prod(grid) if grid else 1
    n_steps = min(total, step_cap)
    steps = list(itertools.islice(
        itertools.product(*(range(g) for g in grid)), n_steps
    )) if grid else [()]

    kernel_jaxpr = eqn.params["jaxpr"]
    lo = gm.num_index_operands + gm.num_inputs
    out_refs = kernel_jaxpr.invars[lo: lo + gm.num_outputs]
    tracked = {ref: i for i, ref in enumerate(out_refs)}
    reads: set[int] = set()
    writes: set[int] = set()
    _track_ref_access(kernel_jaxpr, tracked, reads, writes)

    out = []
    for i, bm in enumerate(gm.block_mappings_output):
        visits = [_eval_index_map(bm.index_map_jaxpr, s) for s in steps]
        out.append(
            OutputAccess(
                kernel=name,
                out_index=i,
                grid=grid,
                steps_evaluated=len(steps),
                truncated=total > n_steps,
                revisited=len(set(visits)) < len(visits),
                reads=i in reads,
                writes=i in writes,
            )
        )
    return out


def analyze_pallas_races(
    fn_or_jaxpr: Any,
    *args: Any,
    parallel_grid: bool = False,
    target: str = "<anonymous>",
    step_cap: int = 4096,
) -> list[Finding]:
    """Race-lint every pallas_call reachable from an entry point.

    ``parallel_grid=True`` models a backend that runs grid steps
    concurrently (Triton — the ``pallas-gpu`` policy route); interpreted
    launches (``interpret=True`` in the eqn params) are always sequential
    regardless.  Findings:

    * ERROR — revisited output block with derived RMW on a parallel grid;
    * ERROR — declared ``parallel_grid_safe=False`` kernel launched on a
      parallel grid with more than one grid step (covers scratch-recurrence
      kernels whose *output* index maps look clean);
    * ERROR — declaration claims ``parallel_grid_safe=True`` while the jaxpr
      shows cross-step RMW (lying metadata, flagged on every route);
    * WARNING — revisited block with write-only semantics on a parallel grid
      (last-writer-wins nondeterminism), stale declarations, undeclared
      kernels with cross-step RMW, or truncated grid enumeration.
    """
    jx = trace(fn_or_jaxpr, *args) if callable(fn_or_jaxpr) else fn_or_jaxpr
    findings: list[Finding] = []
    for eqn in eqns_by_primitive(jx, "pallas_call"):
        interpreted = bool(eqn.params.get("interpret", False))
        effective_parallel = parallel_grid and not interpreted
        accesses = analyze_pallas_eqn(eqn, step_cap=step_cap)
        if not accesses:
            continue
        name = accesses[0].kernel
        grid = accesses[0].grid
        total_steps = math.prod(grid) if grid else 1
        declared = kernel_geometry(name)
        race_prone = [a for a in accesses if a.revisited and a.rmw]

        for a in accesses:
            if a.truncated:
                findings.append(warning(
                    "grid-race", target,
                    f"{name}: grid {grid} exceeds the {step_cap}-step "
                    f"enumeration cap; output {a.out_index} only partially "
                    "checked",
                ))
        if effective_parallel:
            for a in race_prone:
                findings.append(error(
                    "grid-race", target,
                    f"{name}: output {a.out_index} block revisited across "
                    f"grid {grid} with read-modify-write semantics — data "
                    "race on a parallel grid",
                ))
            for a in accesses:
                if a.revisited and not a.rmw:
                    findings.append(warning(
                        "grid-race", target,
                        f"{name}: output {a.out_index} block revisited with "
                        f"write-only stores across grid {grid} — "
                        "last-writer-wins nondeterminism on a parallel grid",
                    ))
            if (
                declared is not None
                and not declared.parallel_grid_safe
                and total_steps > 1
                and not race_prone
            ):
                findings.append(error(
                    "grid-race", target,
                    f"{name}: declared {declared.accumulation!r} "
                    "(parallel-grid unsafe) but launched with "
                    f"{total_steps} grid steps on a parallel backend"
                    + (f" — {declared.notes}" if declared.notes else ""),
                ))
        if declared is not None:
            if declared.parallel_grid_safe and race_prone:
                findings.append(error(
                    "grid-race", target,
                    f"{name}: declaration claims parallel_grid_safe=True "
                    "but the jaxpr shows cross-step read-modify-write on "
                    f"output(s) {[a.out_index for a in race_prone]}",
                ))
            if (
                declared.accumulation in ("per-step", "single-step")
                and any(a.revisited for a in accesses)
            ):
                findings.append(warning(
                    "grid-race", target,
                    f"{name}: declared {declared.accumulation!r} but some "
                    f"output block is revisited across grid {grid} — stale "
                    "declaration in repro.kernels.meta",
                ))
            if declared.accumulation == "single-step" and total_steps > 1:
                findings.append(warning(
                    "grid-race", target,
                    f"{name}: declared 'single-step' but traced with grid "
                    f"{grid} ({total_steps} steps)",
                ))
        elif race_prone:
            findings.append(warning(
                "grid-race", target,
                f"{name}: kernel with cross-step read-modify-write has no "
                "declared geometry — register it in repro.kernels.meta",
            ))
    return findings
