"""The trace reduction against a small trace recorded on the CPU
(``data/cpu_window.xplane.pb``): a ``window`` span holding two ``submit``
spans and one ``fire`` span, each around one call of a jitted
``(a @ a.T).sum()``.  Every expected value below was counted by hand from
the trace's events (start and duration in ns)."""

from pathlib import Path

import pytest

from bench import trace

TRACE = Path(__file__).parent / "data" / "cpu_window.xplane.pb"

# window span: start 28271, duration 8741053
WINDOW_NS = 8741053
# nine device ops, three calls of dot_general.1 / wrapped_reduce-window /
# wrapped_reduce, none overlapping
OPS_NS = {
    "dot_general.1": 111245 + 75950 + 114189,
    "wrapped_reduce-window": 20741 + 21295 + 25453,
    "wrapped_reduce": 950 + 1135 + 1450,
}
BUSY_NS = sum(OPS_NS.values())  # 372408
# the ten idle gaps, labelled by the innermost host event at their middle
GAPS_NS = {
    "PjRtCpuExecutable::ExecuteHelper": 264282,  # window start to the first op
    "submit": 905 + 461 + 2446045 + 688 + 375,
    "fire": 5594173 + 1455 + 884 + 59377,       # ... to the window's end
}


@pytest.fixture(scope="module")
def summary():
    return trace.reduce_trace(str(TRACE))


def test_window_and_busy(summary):
    assert summary.window_s == pytest.approx(WINDOW_NS * 1e-9, abs=1e-12)
    assert list(summary.busy_s) == ["cpu:0"]
    assert summary.busy_s["cpu:0"] == pytest.approx(BUSY_NS * 1e-9, abs=1e-12)


def test_op_time_by_name(summary):
    assert set(summary.op_s) == set(OPS_NS)
    for name, ns in OPS_NS.items():
        assert summary.op_s[name] == pytest.approx(ns * 1e-9, abs=1e-12)
    assert summary.kernel_s(["dot_general"]) == pytest.approx(
        OPS_NS["dot_general.1"] * 1e-9, abs=1e-12)
    assert summary.kernel_s(["_afa_screen_onepass_kernel"]) is None


def test_idle_gaps_by_host_span(summary):
    assert set(summary.idle_gaps) == set(GAPS_NS)
    for label, ns in GAPS_NS.items():
        assert summary.idle_gaps[label] == pytest.approx(ns * 1e-9, abs=1e-12)
    assert sum(GAPS_NS.values()) == WINDOW_NS - BUSY_NS


def test_collectives_and_breakdown(summary):
    assert summary.collective_s == {"cpu:0": 0.0}
    b = trace.breakdown(summary)
    assert [n for n, _ in b["device_ops"]] == [
        "dot_general.1", "wrapped_reduce-window", "wrapped_reduce"]
    assert [n for n, _ in b["idle_gaps"]] == [
        "fire", "submit", "PjRtCpuExecutable::ExecuteHelper"]


def test_nested_ops_count_their_self_time():
    # a while loop (0-100 ns) around two ops, and one op after it
    evs = [("w", "w", 0, 100), ("a", "a", 10, 30), ("b", "b", 40, 60),
           ("c", "c", 120, 130)]
    assert [(n, own) for n, _, _, _, own in trace._self_times(evs)] == [
        ("w", 60), ("a", 20), ("b", 20), ("c", 10)]


def test_collective_names():
    for name in ("%all-reduce.3", "psum.77", "all_gather.57", "%all-gather-start.1",
                 "%collective-permute.2"):
        assert trace.COLLECTIVE.search(name), name
    assert not trace.COLLECTIVE.search("%fusion.12")
