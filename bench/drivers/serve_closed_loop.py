"""One ``AggregationService`` under closed-loop rounds: every client submits
a host-resident float32 row each round, the round fires when the last live
client's row fills the buffer, and the next round starts once the fired
round's aggregate (the new model), reputations and blocked set are on the
host.  Blocked clients keep submitting and are refused at ingress.

Traffic keys: ``pool_rounds`` (distinct rounds of rows, cycled),
``orders`` (distinct submission orders, cycled), ``sample_share`` (share of
rounds whose aggregate the check compares; the last round always is),
``benign_scale`` (range of the benign rows' noise scales), ``base_scale``.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from bench import check, data
from bench.data import kernel_mode
from bench.reference import fl_afa, serve_replay
from bench.work import dnn_param_count, serve_round_work


@functools.lru_cache(maxsize=2)
def _pool_fn(P, K, D, n_bad, base_scale, lo, hi, byz_scale):
    import jax
    import jax.numpy as jnp

    # every seed gets the same set of noise scales, evenly spread over
    # [lo, hi], in its own order: the seed changes which client is the
    # noisiest, not how many clients sit near the screening cut
    scales = jnp.linspace(lo, hi, K, dtype=jnp.float32)

    @jax.jit
    def make(key):
        kb, ks, kn, kz = jax.random.split(key, 4)
        base = base_scale * jax.random.normal(kb, (D,), jnp.float32)
        scale = jax.random.permutation(ks, scales).reshape(1, K, 1)
        u = base + scale * jax.random.normal(kn, (P, K, D), jnp.float32)
        return u.at[:, :n_bad].set(
            byz_scale * jax.random.normal(kz, (P, n_bad, D), jnp.float32))

    return make


def make_pool(seed, P, K, D, n_bad, traffic, byz_scale) -> np.ndarray:
    """``(P, K, D)`` rows: benign rows around one base direction, each client
    at its own noise scale (so the rows screening keeps are decided by the
    data, not by rounding), byzantine rows (the first ``n_bad``) N(0,
    scale^2)."""
    import jax

    lo, hi = traffic["benign_scale"]
    make = _pool_fn(P, K, D, n_bad, float(traffic["base_scale"]), float(lo),
                    float(hi), float(byz_scale))
    return np.asarray(jax.device_get(
        make(jax.random.PRNGKey(data.sub_seeds(seed, 1, salt=5)[0]))))


class Driver:
    def __init__(self, bench):
        self.b = bench
        self.cfg = bench.config
        self.traffic = bench.traffic
        self.latencies: list[float] = []
        self.sample: dict[int, np.ndarray] = {}

    def setup(self) -> None:
        import jax.numpy as jnp

        from repro.fed.engine import FusedData
        from repro.fed.server import ServerConfig, make_rule_options
        from repro.fed.workload import DnnWorkload
        from repro.kernels.policy import KernelPlan
        from repro.serve.service import ServeConfig

        c, t = self.cfg, self.traffic
        self.sizes = tuple(c["model"]["sizes"])
        K, D = c["clients"], dnn_param_count(self.sizes)
        self.K, self.D = K, D
        n_bad = int(round(c["bad_frac"] * K))
        self.pool = make_pool(self.b.seed, t["pool_rounds"], K, D, n_bad, t,
                              c["byzantine_scale"])
        rng = np.random.default_rng(data.sub_seeds(self.b.seed, 1, salt=6)[0])
        self.orders = [rng.permutation(K) for _ in range(t["orders"])]
        self.n_k = np.full(K, float(c["samples_per_client"]), np.float32)
        test = data.classification(
            self.b.seed, 1, c["n_test"], self.sizes[0], c["classes"],
            c["class_separation"])
        dim = self.sizes[0]
        self.fdata = FusedData(
            x=jnp.zeros((K, 1, dim), jnp.float32), y=jnp.zeros((K, 1), jnp.int32),
            lengths=jnp.ones((K,), jnp.int32), n_k=jnp.asarray(self.n_k),
            x_test=jnp.asarray(test["x_test"]), y_test=jnp.asarray(test["y_test"]))
        self.params0 = fl_afa.init_params(data.sub_seeds(self.b.seed, 1, salt=7)[0],
                                          self.sizes)
        self.workload = DnnWorkload(self.sizes)
        self.server_cfg = ServerConfig(
            rule=c["rule"], num_clients=K, alpha0=c["alpha0"], beta0=c["beta0"],
            xi0=c["xi0"], delta_xi=c["delta_xi"], delta_block=c["delta_block"],
            afa_variant=c["afa_variant"],
            kernel_plan=KernelPlan(mode=kernel_mode(c)),
        )
        self.serve_cfg = ServeConfig(buffer_size=K)
        opts = make_rule_options(self.server_cfg, K)
        self.b.note(kernel_route=str(opts.afa.use_kernels),
                    kernel_launch=opts.afa.kernel_launch,
                    afa_variant=opts.afa.variant)
        warm = self._service()
        self._round(warm, 0, record=False)  # compiles the service's step

    def _service(self):
        from repro.serve.service import AggregationService

        return AggregationService(self.workload, self.server_cfg, self.serve_cfg,
                                  self.params0, self.fdata)

    def _round(self, svc, r: int, record: bool = True) -> None:
        """One closed-loop round: every client submits, in this round's
        order; the last live client's row fires it."""
        import jax

        rows = self.pool[r % len(self.pool)]
        order = self.orders[r % len(self.orders)]
        blocked = svc.blocked
        firing = [k for k in order if not blocked[k]][-1]
        spans = self.b.spans
        ta = time.perf_counter()
        for k in order:
            with spans.span("fire" if k == firing else "submit"):
                svc.submit(int(k), rows[k], svc.round, float(r))
        with spans.span("fetch"):
            params = jax.device_get(svc.params)
        tb = time.perf_counter()
        if record:
            self.latencies.append(tb - ta)
            if r in self._sampled:
                self.sample[r] = fl_afa.pack_host(params, self.sizes)

    def window(self, seconds: float) -> None:
        import jax

        rng = np.random.default_rng(data.sub_seeds(self.b.seed, 1, salt=8)[0])
        share = self.traffic["sample_share"]
        self._sampled = set(np.nonzero(rng.random(100_000) < share)[0].tolist())
        svc = self._service()
        t0 = time.perf_counter()
        r = 0
        while time.perf_counter() - t0 < seconds:
            self._round(svc, r)
            r += 1
        self.window_s = time.perf_counter() - t0
        self.svc = svc
        if r - 1 not in self.sample:  # the last round is always compared
            self.sample[r - 1] = fl_afa.pack_host(jax.device_get(svc.params),
                                                  self.sizes)

    # -- facts the metrics read ------------------------------------------------
    @property
    def rounds(self) -> int:
        return len(self.latencies)

    def round_work(self) -> list[tuple[float, float]]:
        return [serve_round_work(self.sizes, n, self.cfg["n_test"])
                for n in self.screen_calls()]

    def screen_calls(self) -> list[int]:
        """Live rows of every screening call (the rows each round accepted)."""
        return self.accepted

    # -- check ----------------------------------------------------------------
    def release(self) -> None:
        import jax

        svc = self.svc
        state = jax.device_get(svc.state)
        self.got = dict(
            decisions=[d for _, _, d in svc.log],
            kept=np.stack([rec.good_mask for rec in svc.rounds]),
            blocked_round=np.asarray(state.rounds_blocked),
            alpha=np.asarray(state.reputation.alpha),
            beta=np.asarray(state.reputation.beta),
            aggregates=self.sample,
        )
        self.accepted = [rec.n_accepted for rec in svc.rounds]
        self.svc = None
        self.n_rounds = len(self.got["kept"])

    def reference(self, precision: str) -> dict:
        return serve_replay.replay(
            self.pool, self.orders, self.n_rounds, self.n_k, self.cfg,
            sorted(self.sample), precision)

    def check(self) -> dict:
        return check.compare_serve(self.got, self.reference("float64"))
