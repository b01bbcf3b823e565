"""Paper Fig 2 (and appendix Figs 4-7): test error vs training round for
AFA / FA / MKRUM / COMED on each scenario.  Emits per-round CSV curves to
experiments/convergence/ and summary rows."""

from __future__ import annotations

import os

from repro.data import make_mnist_like
from repro.fed import ServerConfig, SimConfig
from repro.fed import run as fed_run

OUT = os.path.join(os.path.dirname(__file__), "..", "experiments", "convergence")


def run(quick: bool = False) -> list[dict]:
    os.makedirs(OUT, exist_ok=True)
    data = make_mnist_like(n_train=3000, n_test=800)
    rounds = 6 if quick else 15
    rows = []
    for scenario in ["clean", "byzantine", "flipping", "noisy"]:
        curves = {}
        for rule in ["afa", "fa", "mkrum", "comed"]:
            sim = SimConfig(num_clients=10, scenario=scenario, rounds=rounds,
                            local_epochs=2, batch_size=200, hidden=(512, 256),
                            dropout=False, seed=0)
            res = fed_run(None, sim, ServerConfig(rule=rule, num_clients=10), data=data)
            curves[rule] = res.test_error
        path = os.path.join(OUT, f"mnist_like_{scenario}.csv")
        with open(path, "w") as f:
            f.write("round," + ",".join(curves) + "\n")
            for i in range(rounds):
                f.write(f"{i}," + ",".join(f"{curves[r][i]:.2f}" for r in curves) + "\n")
        # convergence speed: first round AFA dips under 1.5x final error
        afa = curves["afa"]
        tgt = 1.5 * max(afa[-1], 1e-6) + 0.5
        t_conv = next((i for i, e in enumerate(afa) if e <= tgt), rounds)
        rows.append({
            "name": f"fig2/mnist_like/{scenario}",
            "us_per_call": "",
            "derived": f"afa_final={afa[-1]:.2f}%;afa_rounds_to_converge={t_conv};csv={os.path.basename(path)}",
        })
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit

    emit(run())
