"""The comparisons that decide ``correct``: what the timed path produced,
held to the plain reference.  Each function returns ``{name: value}``; the
cell's limits file (``bench/limits/<cell>.json``) gives each name its limit,
and a run is correct when every value is at most its limit."""

from __future__ import annotations

import numpy as np

TIE_RTOL = 2.0**-20


def _z(sims, kept):
    s = np.asarray(sims, np.float64)
    med = float(np.median(s[kept]))
    sd = max(float(s[kept].std()), TIE_RTOL * abs(med))
    return (s - med) / sd


def edge_z(k, sims_keep, kept_keep, sims_drop, kept_drop) -> float:
    """How far client ``k``, kept by one run and dropped by the other, sat
    inside the kept set's edge in the run that kept it (0: it was the kept
    set's extreme), in z-scores over that kept set.  A dropped client lies
    outside the kept range of the run that dropped it; where it does not,
    the flip is no edge case and reads infinity."""
    z = _z(sims_drop, kept_drop)
    lo, hi = z[kept_drop].min(), z[kept_drop].max()
    if lo <= z[k] <= hi:
        return float("inf")
    z = _z(sims_keep, kept_keep)
    lo, hi = z[kept_keep].min(), z[kept_keep].max()
    return float(min(z[k] - lo, hi - z[k]))


def kept_edge_z(kept_a, sims_a, kept_b, sims_b) -> float:
    """Largest :func:`edge_z` over every (round, client) kept by exactly one
    of two runs; 0 where the kept sets agree."""
    worst = 0.0
    for r in np.nonzero((kept_a != kept_b).any(axis=1))[0]:
        for k in np.nonzero(kept_a[r] != kept_b[r])[0]:
            if kept_a[r, k]:
                z = edge_z(k, sims_a[r], kept_a[r], sims_b[r], kept_b[r])
            else:
                z = edge_z(k, sims_b[r], kept_b[r], sims_a[r], kept_a[r])
            worst = max(worst, z)
    return worst


def compare_sim(got: dict, ref: dict, n_bad: int) -> dict:
    """One simulated experiment against the reference's run of it.  The
    cell's limits judge ``first_round_similarity_gap`` (the local update,
    attack, packing and screening of round 1, before the two runs' params
    can part), ``update_gap`` (params after the last round) and
    ``byzantine_blocked_differing``; the rest is logged beside them."""
    kept_got = np.asarray(got["kept"], bool)
    kept_ref = np.asarray(ref["kept"], bool)
    br_got = np.asarray(got["blocked_round"])
    return {
        "first_round_similarity_gap": float(np.abs(
            np.asarray(got["sims"], np.float64)[0] - ref["sims"][0]).max()),
        "byzantine_blocked_differing": int(
            (br_got[:n_bad] != ref["blocked_round"][:n_bad]).sum()),
        "kept_differing": int((kept_got != kept_ref).sum()),
        "blocked_rounds_differing": int((br_got != ref["blocked_round"]).sum()),
        "update_gap": float(
            np.linalg.norm(got["params"] - ref["params"])
            / np.linalg.norm(ref["params"] - ref["params0"])),
        "test_error_gap_points": float(
            np.abs(np.asarray(got["test_error"]) - ref["test_error"]).max()),
        "kept_edge_z": kept_edge_z(kept_got, np.asarray(got["sims"]),
                                   kept_ref, np.asarray(ref["sims"])),
    }


def sim_differences(got: dict, ref: dict) -> dict:
    """Where two runs of one experiment part (for the log): each client
    whose blocked round differs, and the kept-set differences per round."""
    br_g, br_r = np.asarray(got["blocked_round"]), np.asarray(ref["blocked_round"])
    diff = np.asarray(got["kept"], bool) != np.asarray(ref["kept"], bool)
    return {
        "blocked": [[int(k), int(br_g[k]), int(br_r[k])]
                    for k in np.nonzero(br_g != br_r)[0]],
        "kept_differing_by_round": diff.sum(axis=1).tolist(),
    }


def compare_serve(got: dict, ref: dict) -> dict:
    """The served rounds of a window against the reference's replay."""
    gaps = [
        float(np.linalg.norm(got["aggregates"][r] - ref["aggregates"][r])
              / np.linalg.norm(ref["aggregates"][r]))
        for r in ref["aggregates"]
    ]
    return {
        "decisions_differing": int(sum(
            a != b for a, b in zip(got["decisions"], ref["decisions"]))
            + abs(len(got["decisions"]) - len(ref["decisions"]))),
        "kept_differing": int((np.asarray(got["kept"]) != ref["kept"]).sum()),
        "blocked_rounds_differing": int(
            (np.asarray(got["blocked_round"]) != ref["blocked_round"]).sum()),
        "reputation_gap": float(max(
            np.abs(np.asarray(got["alpha"], np.float64) - ref["alpha"]).max(),
            np.abs(np.asarray(got["beta"], np.float64) - ref["beta"]).max())),
        "aggregate_gap": max(gaps),
    }


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``: correct when every value
    is at most its limit; a missing or non-finite value is not correct."""
    out = {}
    ok = True
    for name, limit in limits.items():
        v = values.get(name)
        out[name] = {"value": v, "limit": limit}
        if v is None or not np.isfinite(v) or v > limit:
            ok = False
    return ok, out
