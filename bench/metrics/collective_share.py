"""Collective operations' device time over the traced window, per chip,
the largest over the chips."""


def read(r):
    if r.trace is None or not any(r.trace.collective_s.values()):
        return None
    return 100.0 * max(r.trace.collective_s.values()) / r.trace.window_s
