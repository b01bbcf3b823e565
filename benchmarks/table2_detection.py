"""Paper Table 2: percentage of bad clients blocked by AFA and the average
number of rounds needed to block them, per scenario."""

from __future__ import annotations

from repro.data import make_mnist_like, make_spambase_like
from repro.fed import ServerConfig, SimConfig
from repro.fed import run as fed_run

SCENARIOS = ["byzantine", "flipping", "noisy"]


def run(quick: bool = False) -> list[dict]:
    rows = []
    datasets = {
        "mnist_like": (make_mnist_like(n_train=3000, n_test=800), (512, 256)),
        "spambase_like": (make_spambase_like(), (100, 50)),
    }
    rounds = 8 if quick else 20
    for dname, (data, hidden) in datasets.items():
        for scenario in SCENARIOS:
            sim = SimConfig(
                num_clients=10, scenario=scenario, rounds=rounds, local_epochs=2,
                batch_size=200, hidden=hidden, dropout=False, seed=0,
            )
            res = fed_run(None, sim, ServerConfig(rule="afa", num_clients=10), data=data)
            rows.append({
                "name": f"table2/{dname}/{scenario}",
                "us_per_call": "",
                "derived": f"detected={res.detection_rate:.0%};rounds_to_block={res.mean_rounds_to_block:.1f}",
            })
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit

    emit(run())
