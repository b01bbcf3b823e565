"""Peak table and work counts: tied to the paper DNN's shapes, and no share
built on them passes 100% when fed a time equal to its own bound."""

import types

import pytest

from bench import peaks, work
from bench.metrics import afa_screen_roofline, round_mfu

PAPER = (784, 512, 256, 10)


def test_paper_dnn_counts():
    assert work.dnn_param_count(PAPER) == 535_818
    assert work.dnn_forward_flops(PAPER) == 2 * (784 * 512 + 512 * 256 + 256 * 10)
    # forward + weight grads + input grads of layers 2 and 3
    assert work.dnn_train_flops(PAPER) == 2_407_424


def test_sim_round_work_counts_live_honest_clients_only():
    f, b = work.sim_round_work(PAPER, honest_live=70, steps=30, batch=200,
                               n_test=10_000, buffer_live=100)
    assert f == 70 * 30 * 200 * 2_407_424 + 10_000 * 1_070_080
    assert b == 100 * 535_818 * 4
    f2, _ = work.sim_round_work(PAPER, 0, 30, 200, 10_000, 30)
    assert f2 == 10_000 * 1_070_080


def test_unknown_device_kind_raises():
    assert peaks.peak_of("TPU v5 lite").flops_bf16 == 197e12
    assert peaks.peak_of("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError):
        peaks.peak_of("TPU v4")


class _Driver:
    cfg = {"model": {"sizes": list(PAPER)}}

    def __init__(self, work_rounds, calls):
        self._w, self._c = work_rounds, calls

    def round_work(self):
        return self._w

    def screen_calls(self):
        return self._c


@pytest.mark.parametrize("chips", [1, 4])
def test_round_mfu_is_100_at_its_bound(chips):
    peak = peaks.peak_of("TPU v5 lite")
    rounds = [work.sim_round_work(PAPER, 70, 30, 200, 10_000, 100),
              work.serve_round_work(PAPER, 70, 10_000)]
    need = sum(peaks.roofline_s(f, b, peak) for f, b in rounds)
    r = types.SimpleNamespace(driver=_Driver(rounds, []), peak=peak,
                              window_s=need / chips, chips=chips)
    assert round_mfu.read(r) == pytest.approx(100.0)
    r.window_s *= 2
    assert round_mfu.read(r) == pytest.approx(50.0)


def test_afa_screen_roofline_is_100_at_its_bound():
    peak = peaks.peak_of("TPU v5 lite")
    calls = [100, 100, 70, 70]
    nbytes = sum(work.afa_screen_bytes(k, 535_818) for k in calls)
    t = nbytes / peak.hbm_bytes_per_s
    summary = types.SimpleNamespace(kernel_s=lambda names: t)
    r = types.SimpleNamespace(driver=_Driver([], calls), peak=peak, trace=summary)
    assert afa_screen_roofline.read(r) == pytest.approx(100.0)
    summary.kernel_s = lambda names: None
    assert afa_screen_roofline.read(r) is None
