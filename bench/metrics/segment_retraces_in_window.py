"""Traces of a segment program inside the window: each ``fed.segment.trace``
record is one run of the segment's Python body, which runs only when JAX
traces it."""

from bench.program_spans import window_records


def read(r):
    recs = window_records(r, "fed.segment.trace")
    return None if recs is None else float(len(recs))
