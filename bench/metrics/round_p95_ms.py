"""95th percentile of every round's latency in the window: from the round's
first submission until its aggregate, reputations and blocked set are on
the host."""

import numpy as np


def read(r):
    lat = getattr(r.driver, "latencies", None)
    if not lat:
        return None
    return float(np.percentile(lat, 95)) * 1e3
