"""Regression-gate unit tests (benchmarks/check_regression.py): the
baseline-relative tolerance AND the packed absolute floor.

The packed baseline is deliberately conservative (rounded down toward the
weakest observed run, currently ~1.0x), so a purely relative gate would only
fire below baseline*(1-tol) — blind to the exact failure it exists to catch,
the packed dispatch collapsing to or below parity with the leaf layout.  The
absolute >=1.0x floor on packed_agg scenarios closes that hole.
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"))
import check_regression as cr  # noqa: E402


def _doc(packed=1.0, compaction=5.5):
    return {
        "compaction": [{"K": 10, "post_block_speedup": compaction}],
        "packed": [{"K": 200, "D": 545, "rule": "afa", "agg_speedup": packed}],
    }


def _run(tmp_path, cur, base, extra=()):
    c, b = tmp_path / "cur.json", tmp_path / "base.json"
    c.write_text(json.dumps(cur))
    b.write_text(json.dumps(base))
    return cr.main([str(c), str(b), *extra])


def test_matching_speedups_pass(tmp_path):
    assert _run(tmp_path, _doc(), _doc()) == 0


def test_relative_regression_fails(tmp_path):
    # 5.5x -> 3.0x is far past the 25% tolerance
    assert _run(tmp_path, _doc(compaction=3.0), _doc(compaction=5.5)) == 1


def test_packed_below_parity_fails_despite_relative_tolerance(tmp_path):
    # baseline 1.0 with 25% tolerance gives a relative floor of 0.75x, so
    # 0.9x would sneak through a purely relative gate — the absolute floor
    # must catch it
    assert _run(tmp_path, _doc(packed=0.9), _doc(packed=1.0)) == 1


def test_packed_at_or_above_parity_passes(tmp_path):
    assert _run(tmp_path, _doc(packed=1.0), _doc(packed=1.0)) == 0
    assert _run(tmp_path, _doc(packed=1.4), _doc(packed=1.0)) == 0


def test_abs_floor_binds_even_with_wide_tolerance(tmp_path):
    # a user-widened tolerance must not defang the parity floor
    assert _run(
        tmp_path, _doc(packed=0.95), _doc(packed=1.0), ("--tolerance", "0.9")
    ) == 1


def test_empty_intersection_fails(tmp_path):
    assert _run(tmp_path, {"compaction": []}, _doc()) == 1
