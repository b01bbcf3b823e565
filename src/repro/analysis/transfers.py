"""Host-transfer detector for device-resident loop bodies.

The fused round engine's whole point is that a multi-round segment runs as
one device program — a callback or host transfer inside the ``scan`` (or a
screening ``while``) body would serialize every iteration on the host and
silently destroy that.  This check walks every scan/while body in a traced
entry point and errors on any primitive that crosses the host boundary.
"""

from __future__ import annotations

from typing import Any

from repro.analysis.jaxpr_utils import iter_eqns, subjaxprs, trace
from repro.analysis.report import Finding, error

# Exact jaxpr primitive names that imply host involvement or an explicit
# device transfer.  ``device_put`` inside a traced loop body means a
# transfer was staged into the device program.
HOST_BOUNDARY_PRIMITIVES = frozenset({
    "pure_callback",
    "io_callback",
    "debug_callback",
    "debug_print",
    "callback",
    "outside_call",
    "infeed",
    "outfeed",
    "device_put",
    "host_local_array_to_global_array",
})

_LOOP_PRIMITIVES = frozenset({"scan", "while"})


def check_no_host_transfers(
    fn_or_jaxpr: Any, *args: Any, target: str = "<anonymous>"
) -> list[Finding]:
    """Error for every host-boundary primitive inside a scan/while body."""
    jx = trace(fn_or_jaxpr, *args) if callable(fn_or_jaxpr) else fn_or_jaxpr
    findings: list[Finding] = []
    for eqn in iter_eqns(jx):
        if eqn.primitive.name not in _LOOP_PRIMITIVES:
            continue
        for val in eqn.params.values():
            for body in subjaxprs(val):
                for inner in iter_eqns(body):
                    if inner.primitive.name in HOST_BOUNDARY_PRIMITIVES:
                        findings.append(error(
                            "host-transfer", target,
                            f"{inner.primitive.name} inside a "
                            f"{eqn.primitive.name} body — host round-trip "
                            "per iteration breaks the fused device program",
                        ))
    # nested loops make the outer walk re-report inner bodies: dedupe
    return list(dict.fromkeys(findings))
