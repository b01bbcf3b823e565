"""Share of the window's ``fed.segment.stage`` spans that found the pool
their client stacks are gathered from already on the device (``pool`` is
``"hit"``), in %.  None where no staging in the window says (a program
without a device pool)."""

from bench.program_spans import window_records


def read(r):
    recs = window_records(r, "fed.segment.stage")
    pools = [s.attrs["pool"] for s in recs or () if "pool" in s.attrs]
    if not pools:
        return None
    return 100.0 * pools.count("hit") / len(pools)
