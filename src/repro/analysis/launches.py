"""Launch-count checker: declarative ``pallas_call`` budgets per entry point.

Replaces the ad-hoc jaxpr string asserts formerly duplicated across
``tests/test_afa_screen.py`` and ``benchmarks/fused_engine.py`` with one
API: trace the entry point, enumerate its ``pallas_call`` eqns (launch names
come from the ``name`` every kernel in ``repro.kernels`` passes to
``pallas_call`` — its body's ``__name__``),
and compare against a :class:`LaunchBudget`.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

from repro.analysis.jaxpr_utils import eqns_by_primitive, trace
from repro.analysis.report import Finding, error


class LaunchBudget(NamedTuple):
    """Budget for the number of ``pallas_call`` eqns in one trace.

    ``exact`` pins the count; otherwise ``min``/``max`` bound it (either may
    be None for unbounded on that side).
    """

    exact: int | None = None
    min: int | None = None
    max: int | None = None

    def describe(self) -> str:
        if self.exact is not None:
            return f"exactly {self.exact}"
        parts = []
        if self.min is not None:
            parts.append(f">= {self.min}")
        if self.max is not None:
            parts.append(f"<= {self.max}")
        return " and ".join(parts) if parts else "unconstrained"

    def satisfied_by(self, count: int) -> bool:
        if self.exact is not None:
            return count == self.exact
        if self.min is not None and count < self.min:
            return False
        if self.max is not None and count > self.max:
            return False
        return True


def launch_name(eqn: Any) -> str:
    """The ``name`` a ``pallas_call`` eqn was given (``<pallas_call>`` for
    an unnamed launch outside this package)."""
    return eqn.params.get("name") or "<pallas_call>"


def pallas_launch_names(fn_or_jaxpr: Any, *args: Any) -> list[str]:
    """Kernel-body names of every ``pallas_call`` in the (traced) jaxpr.

    Pass either a pre-traced (Closed)Jaxpr, or a callable plus its example
    arguments (traced here, never executed).
    """
    jx = trace(fn_or_jaxpr, *args) if callable(fn_or_jaxpr) else fn_or_jaxpr
    return [launch_name(e) for e in eqns_by_primitive(jx, "pallas_call")]


def count_pallas_launches(fn_or_jaxpr: Any, *args: Any) -> int:
    """Number of ``pallas_call`` eqns, sub-jaxprs included."""
    return len(pallas_launch_names(fn_or_jaxpr, *args))


def check_launch_budget(
    fn_or_jaxpr: Any,
    *args: Any,
    budget: LaunchBudget,
    target: str = "<anonymous>",
) -> list[Finding]:
    """Trace + count + compare; one ``error`` finding on violation."""
    names = pallas_launch_names(fn_or_jaxpr, *args)
    if budget.satisfied_by(len(names)):
        return []
    return [
        error(
            "launch-budget",
            target,
            f"expected {budget.describe()} pallas launch(es), traced "
            f"{len(names)}: {names or '(none)'}",
        )
    ]


def assert_launch_budget(
    fn: Callable, *args: Any, budget: LaunchBudget, target: str = "<anonymous>"
) -> None:
    """Raise AssertionError on violation — the drop-in form for tests and
    benchmarks that previously hand-rolled jaxpr walks."""
    findings = check_launch_budget(fn, *args, budget=budget, target=target)
    if findings:
        raise AssertionError(findings[0].message)
