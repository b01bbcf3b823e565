"""The expert share's load: per experiment, the largest (layer, held
expert) count of token-choices over the mean of those counts
(``max_over_mean`` of the program's ``fed.moe.route`` record), averaged
over the window's experiments.  None where the program writes no such
record."""

from bench.program_spans import window_records


def read(r):
    recs = window_records(r, "fed.moe.route")
    if not recs:
        return None
    return sum(s.attrs["max_over_mean"] for s in recs) / len(recs)
