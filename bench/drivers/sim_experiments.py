"""Whole simulated experiments back to back through ``repro.fed.api.run``,
as a researcher runs them: each experiment on its own seed drawn from the
run's seed, each paying its own set-up and staging.

Traffic keys: ``rounds`` (T), ``segment_rounds``, ``compact``,
``scenario``.  Set-up runs one whole experiment, which compiles every
program the window runs.  The check re-runs one of the window's
experiments, drawn from the seed, through the plain reference.
"""

from __future__ import annotations

import time

import numpy as np

from bench import check, data
from bench.data import kernel_mode
from bench.reference import fl_afa
from bench.work import sim_round_work


class Driver:
    def __init__(self, bench):
        self.b = bench
        self.cfg = bench.config
        self.traffic = bench.traffic
        self.experiments: list[dict] = []
        self.results: list = []

    # -- set-up ---------------------------------------------------------------
    def _configs(self, seed: int):
        from repro.fed.server import ServerConfig
        from repro.fed.simulator import SimConfig
        from repro.kernels.policy import KernelPlan

        c, t = self.cfg, self.traffic
        sizes = tuple(c["model"]["sizes"])
        sim = SimConfig(
            num_clients=c["clients"], bad_frac=c["bad_frac"],
            scenario=t["scenario"], rounds=t["rounds"],
            local_epochs=c["local_epochs"], batch_size=c["batch_size"],
            lr=c["lr"], momentum=c["momentum"], dropout=c["model"]["dropout"] > 0,
            byzantine_scale=c["byzantine_scale"], seed=seed, hidden=sizes[1:-1],
            engine="fused", segment_rounds=t["segment_rounds"],
            compact=t["compact"], client_shards=c["client_shards"],
        )
        server = ServerConfig(
            rule=c["rule"], num_clients=c["clients"], alpha0=c["alpha0"],
            beta0=c["beta0"], xi0=c["xi0"], delta_xi=c["delta_xi"],
            delta_block=c["delta_block"], afa_variant=c["afa_variant"],
            kernel_plan=KernelPlan(mode=kernel_mode(c)),
        )
        return sim, server

    def setup(self) -> None:
        from repro.data import SyntheticClassification
        from repro.fed.server import make_rule_options

        c = self.cfg
        self.arrays = data.classification(
            self.b.seed, c["n_train"], c["n_test"], c["model"]["sizes"][0],
            c["classes"], c["class_separation"])
        self.data = SyntheticClassification(
            self.arrays["x_train"], self.arrays["y_train"],
            self.arrays["x_test"], self.arrays["y_test"], c["classes"])
        sim, server = self._configs(data.sub_seeds(self.b.seed, 1, salt=3)[0])
        opts = make_rule_options(server, c["clients"])
        self.b.note(kernel_route=str(opts.afa.use_kernels),
                    kernel_launch=opts.afa.kernel_launch,
                    afa_variant=opts.afa.variant)
        self._run(sim, server)  # warm-up: compiles every program

    def _run(self, sim, server):
        from repro.fed.api import run

        return run(None, sim, server, data=self.data)

    # -- window ---------------------------------------------------------------
    def window(self, seconds: float) -> None:
        seeds = data.sub_seeds(self.b.seed, 10_000, salt=2)
        t0 = time.perf_counter()
        for seed in seeds:
            sim, server = self._configs(seed)
            with self.b.spans.span("experiment"):
                ta = time.perf_counter()
                res = self._run(sim, server)
                wall = time.perf_counter() - ta
            self.experiments.append(dict(
                seed=seed, wall_s=wall, in_segments_s=float(sum(res.round_times)),
                rounds=sim.rounds, blocked_round=np.asarray(res.blocked_round)))
            self.results.append(res)
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0

    # -- facts the metrics read ------------------------------------------------
    @property
    def rounds(self) -> int:
        return sum(e["rounds"] for e in self.experiments)

    def round_work(self) -> list[tuple[float, float]]:
        """(FLOPs, bytes) of every round of the window."""
        c = self.cfg
        K = c["clients"]
        bad = np.arange(K) < int(round(c["bad_frac"] * K))
        steps = c["local_epochs"] * max(c["samples_per_client"] // c["batch_size"], 1)
        out = []
        for e in self.experiments:
            br = e["blocked_round"]
            for r in range(e["rounds"]):
                live = (br < 0) | (r < br)
                out.append(sim_round_work(
                    c["model"]["sizes"], int((live & ~bad).sum()), steps,
                    c["batch_size"], c["n_test"], int(live.sum())))
        return out

    def screen_calls(self) -> list[int]:
        """Live rows of every screening call of the window."""
        out = []
        for e in self.experiments:
            br = e["blocked_round"]
            out += [int(((br < 0) | (r < br)).sum()) for r in range(e["rounds"])]
        return out

    # -- check ----------------------------------------------------------------
    def release(self) -> None:
        """Keep the checked experiment's outputs on the host; free the rest."""
        import jax

        i = data.sub_seeds(self.b.seed, 1, salt=4)[0] % len(self.results)
        res = self.results[i]
        self.checked = dict(
            seed=self.experiments[i]["seed"],
            test_error=np.asarray(res.test_error, np.float64),
            kept=np.asarray(res.good_mask_history, bool),
            sims=np.asarray(res.similarity_history, np.float32),
            blocked_round=np.asarray(res.blocked_round),
            params=fl_afa.pack_host(jax.device_get(res.params),
                                    tuple(self.cfg["model"]["sizes"])),
        )
        self.results = []

    def reference(self, seed: int, precision: str) -> dict:
        return fl_afa.run_experiment(
            self.arrays, self.cfg, seed, self.traffic["rounds"], precision)

    def check(self) -> dict:
        ref = self.reference(self.checked["seed"], "default")
        self.b.note(differences=check.sim_differences(self.checked, ref))
        n_bad = int(round(self.cfg["bad_frac"] * self.cfg["clients"]))
        return check.compare_sim(self.checked, ref, n_bad)
