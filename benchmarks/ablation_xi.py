"""Beyond-paper ablation: sensitivity of AFA to the threshold schedule
(ξ0, Δξ) and to non-IID (Dirichlet) client shards.

The paper fixes ξ0=2, Δξ=0.5 and IID shards.  Two robustness questions it
leaves open:
  1. how tight can ξ0 go before benign clients get blocked (false positives),
     and how loose before byzantine clients leak through?
  2. do heterogeneous (non-IID) shards make benign clients look malicious?
"""

from __future__ import annotations

import numpy as np

from repro.data import dirichlet_shards, make_mnist_like
from repro.fed import ServerConfig, SimConfig
from repro.fed import run as fed_run


def run(quick: bool = False) -> list[dict]:
    rows = []
    data = make_mnist_like(n_train=2500, n_test=600)
    rounds = 6 if quick else 12

    # --- 1. xi sweep under byzantine attack --------------------------------
    for xi0 in ([1.0, 2.0] if quick else [0.5, 1.0, 2.0, 3.0]):
        sim = SimConfig(num_clients=10, scenario="byzantine", rounds=rounds,
                        local_epochs=2, batch_size=200, hidden=(512, 256),
                        dropout=False, seed=0)
        res = fed_run(
            None, sim,
            ServerConfig(rule="afa", num_clients=10, xi0=xi0),
            data=data,
        )
        benign_blocked = sum(
            1 for k in range(10)
            if k not in res.bad_clients and res.blocked_round[k] > 0
        )
        rows.append({
            "name": f"ablation/xi0={xi0}/byzantine",
            "us_per_call": "",
            "derived": (
                f"err={res.test_error[-1]:.2f}%;detected={res.detection_rate:.0%};"
                f"benign_blocked={benign_blocked}"
            ),
        })

    # --- 2. non-IID shards, no attack: false-positive pressure --------------
    # AFA weights by p_k * n_k: dirichlet shards give UNEQUAL n_k, exercising
    # the paper's n_k-weighting that MKRUM/COMED lack
    for alpha in ([0.5] if quick else [0.1, 0.5, 5.0]):
        sim = SimConfig(num_clients=10, scenario="clean", rounds=rounds,
                        local_epochs=2, batch_size=200, hidden=(512, 256),
                        dropout=False, seed=0,
                        sharding="dirichlet", dirichlet_alpha=alpha)
        res = fed_run(None, sim, ServerConfig(rule="afa", num_clients=10), data=data)
        shards = dirichlet_shards(data.x_train, data.y_train, 10, alpha=alpha, seed=0)
        sizes = np.asarray([len(x) for x, _ in shards], np.float32)
        rows.append({
            "name": f"ablation/dirichlet_alpha={alpha}/clean",
            "us_per_call": "",
            "derived": (
                f"err={res.test_error[-1]:.2f}%;"
                f"blocked_benign={(res.blocked_round > 0).sum()};"
                f"shard_size_cv={sizes.std()/sizes.mean():.2f}"
            ),
        })
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit

    emit(run())
