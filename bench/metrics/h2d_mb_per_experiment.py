"""Per experiment, the bytes the program hands to the device, counted where
it does so: the ``h2d_bytes`` of ``fed.setup`` (the test set) and of every
``fed.segment.stage`` (the compacted client stacks and masks), in MB."""

from bench.program_spans import per_experiment, window_records


def read(r):
    recs = window_records(r, "fed.setup", "fed.segment.stage")
    if recs is None:
        return None
    return per_experiment(r, 1e-6 * sum(s.attrs["h2d_bytes"] for s in recs))
