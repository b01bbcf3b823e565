"""Whole rounds' share of the chips' peak: for each round of the window the
least time its required work could take (``bench/work.py``: the larger of
FLOPs over the bf16 peak and bytes over the HBM peak), summed, over the
window's time times the chips."""

from bench.peaks import roofline_s


def read(r):
    need = sum(roofline_s(f, b, r.peak) for f, b in r.driver.round_work())
    return 100.0 * need / (r.window_s * r.chips)
