"""The controls at a test size on the CPU: the plain reference in the
program's place, a precision step down, must fail the limits.  Sim: bf16
local training against the sim cell's real limits.  Serve: one-pass bf16
contractions, spelled out, against the serve traffic's test limits
(``fixtures/limits``); its three-pass control is not separated (no serve
cell is declared)."""

import json
from pathlib import Path

import pytest

from bench.check import judge
from bench.control import control_values

BENCH = Path(__file__).resolve().parents[1]
FIX = BENCH / "tests" / "fixtures"


def _plan(config, traffic):
    return {"config": json.loads((FIX / "configs" / f"{config}.json").read_text()),
            "traffic": json.loads((FIX / "traffic" / f"{traffic}.json").read_text())}


@pytest.mark.parametrize("limits, config, traffic, rounds, precision", [
    (BENCH / "limits", "tiny_sim", "tiny_experiments", None, "bfloat16"),
    (FIX / "limits", "tiny_serve", "tiny_closed_loop", 64, "default"),
])
def test_control_is_not_correct(limits, config, traffic, rounds, precision):
    cell = "afa_mnist_k100." + ("sim" if rounds is None else "serve")
    limits = json.loads((limits / f"{cell}.json").read_text())
    values = control_values(_plan(config, traffic), 2**31 + 11, rounds)[precision]
    correct, checks = judge(values, limits)
    assert not correct, checks
