"""Per round, the submission that fired the round (staging, host-to-device
copy, the aggregation step and its sync) less one mean submission."""


def read(r):
    sub, fire = r.window_spans("submit"), r.window_spans("fire")
    if not sub or not fire:
        return None
    return 1e3 * (sum(fire) / len(fire) - sum(sub) / len(sub))
