"""Per experiment, ``api.run``'s wall time less the sum of its
``round_times`` (the time inside segments), averaged over the window's
experiments: the program's set-up of each experiment."""


def read(r):
    exps = getattr(r.driver, "experiments", None)
    if not exps:
        return None
    return 1e3 * sum(e["wall_s"] - e["in_segments_s"] for e in exps) / len(exps)
