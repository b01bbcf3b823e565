"""Per round, the time of the submissions that did not fire the round, plus
one mean submission for the one that did."""


def read(r):
    sub, fire = r.window_spans("submit"), r.window_spans("fire")
    if not sub or not fire:
        return None
    return 1e3 * (sum(sub) / len(fire) + sum(sub) / len(sub))
