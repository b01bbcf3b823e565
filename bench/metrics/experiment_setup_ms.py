"""Per experiment, the program's ``fed.setup`` span: ``_Setup`` (host
sharding, data poisoning, init, the test set to the device)."""

from bench.program_spans import per_experiment, window_records


def read(r):
    recs = window_records(r, "fed.setup")
    if recs is None:
        return None
    return per_experiment(r, 1e3 * sum(s.t1 - s.t0 for s in recs))
