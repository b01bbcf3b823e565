"""Paper Table 1: test error of AFA / FA / MKRUM / COMED under clean /
byzantine / flipping / noisy scenarios (10 clients, 30% bad), on the
MNIST-like and Spambase-like synthetic datasets with the paper's DNNs."""

from __future__ import annotations

import numpy as np

from repro.data import make_mnist_like, make_spambase_like
from repro.fed import ServerConfig, SimConfig
from repro.fed import run as fed_run

SCENARIOS = ["clean", "byzantine", "flipping", "noisy"]
RULES = ["afa", "fa", "mkrum", "comed"]


def run(quick: bool = False) -> list[dict]:
    rows = []
    datasets = {
        "mnist_like": (make_mnist_like(n_train=3000, n_test=800), (512, 256)),
        "spambase_like": (make_spambase_like(), (100, 50)),
    }
    rounds = 6 if quick else 15
    for dname, (data, hidden) in datasets.items():
        for scenario in SCENARIOS:
            for rule in RULES:
                sim = SimConfig(
                    num_clients=10, scenario=scenario, rounds=rounds,
                    local_epochs=2, batch_size=200, hidden=hidden,
                    dropout=False, seed=0,
                    lr=0.1 if dname == "mnist_like" else 0.05,
                )
                res = fed_run(None, sim, ServerConfig(rule=rule, num_clients=10), data=data)
                err = float(np.mean(res.test_error[-3:]))
                rows.append({
                    "name": f"table1/{dname}/{scenario}/{rule}",
                    "us_per_call": round(res.round_time * 1e6, 1),
                    "derived": f"test_err={err:.2f}%",
                })
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit

    emit(run())
