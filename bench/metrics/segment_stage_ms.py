"""Per experiment, the program's ``fed.segment.stage`` spans: at each bucket
change of the segment driver, the padded stack built on first use, the
compacted stacks and masks, their copy to the device and the server state's
gather and scatter."""

from bench.program_spans import per_experiment, window_records


def read(r):
    recs = window_records(r, "fed.segment.stage")
    if recs is None:
        return None
    return per_experiment(r, 1e3 * sum(s.t1 - s.t0 for s in recs))
