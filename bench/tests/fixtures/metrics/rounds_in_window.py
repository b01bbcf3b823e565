"""A per-layer metric that lives only in the test fixtures: it shows that a
metric is a new file plus an entry, read by name."""


def read(r):
    return float(r.rounds)
