"""The fused ``afa_screen`` kernel's share of its bandwidth roofline: one
read of the live rows of the (K, D) buffer and the (D,) aggregate written,
for every screening call of the window, at the HBM peak, over the kernel's
summed device time in the trace."""

from bench.work import afa_screen_bytes, dnn_param_count

KERNELS = ("_afa_screen_onepass_kernel", "_afa_screen_twopass_kernel")


def read(r):
    if r.trace is None:
        return None
    t = r.trace.kernel_s(KERNELS)
    if not t:
        return None
    D = dnn_param_count(r.driver.cfg["model"]["sizes"])
    need = sum(afa_screen_bytes(rows, D) for rows in r.driver.screen_calls())
    return 100.0 * need / r.peak.hbm_bytes_per_s / t
