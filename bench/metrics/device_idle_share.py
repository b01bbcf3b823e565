"""Share of the traced window in which no operation ran on the device
(mean over the chips used)."""


def read(r):
    if r.trace is None or not r.trace.busy_s:
        return None
    return 100.0 * (1.0 - r.trace.mean_busy_s() / r.trace.window_s)
