"""Whole federated LoRA fine-tuning experiments back to back through
``repro.fed.api.run`` (the LLM route: ``simulate_llm`` -> ``make_fused_sim``),
each on its own seed drawn from the run's seed.

The configuration names the model in the program's registry and the cut
(layers, experts held, vocabulary slice); the driver checks the program's
published numbers against the file.  Set-up makes the run's corpus of
token sequences over the vocabulary slice from ``--seed`` (a seeded
bigram-markov source) and runs one whole experiment, which compiles every
program the window runs.  Each window experiment's seed shards the corpus
into the clients' sequences and the held-out batch.

Traffic keys: ``rounds`` (T), ``local_steps``, ``batch`` (sequences a
step), ``seq``, ``samples_per_client``, ``n_test``, ``corpus_sequences``,
``scenario``.

Set-up also draws the frozen base from ``--seed`` with the reference's
own draw (``bench/reference/granite_lora.init_base``), and every experiment
fine-tunes that one base, from adapters the reference draws from the
experiment's seed.  The program keeps each experiment's adapters after
round 1 (``keep_round1``).

The check takes the window's first experiment and holds what its timed run
produced to the reference's round 1 (``granite_lora.round_one``, routed by
the run's own expert choices): the adapters after round 1, the round's
similarities and kept set, and the share of the run's expert choices that
the reference's own top-k would not have made; and its blocked rounds to
the exact Beta rule over its own kept sets.
"""

from __future__ import annotations

import time

import numpy as np

from bench import data
from bench.data import kernel_mode
from bench.reference import granite_lora
from bench.work_lora import lora_round_work

# published numbers of the configuration file -> the program's ModelConfig
PUBLISHED = {
    "hidden_size": "d_model", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "intermediate_size": "d_ff",
    "shared_intermediate_size": "shared_d_ff", "num_experts_per_tok": "top_k",
    "mamba_d_state": "ssm_state", "mamba_d_head": "ssm_head_dim",
    "mamba_expand": "ssm_expand", "mamba_chunk_size": "ssm_chunk",
    "mamba_d_conv": "ssm_conv_width", "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier", "logits_scaling": "logits_scaling",
    "attention_multiplier": "attention_multiplier", "rms_norm_eps": "norm_eps",
}


def model_config(cfg: dict):
    """The program's registry configuration with the file's cut applied,
    after checking that every published number agrees with the file."""
    from repro.configs import get_config

    mc = get_config(cfg["registry_name"])
    for key, attr in PUBLISHED.items():
        if float(getattr(mc, attr)) != float(cfg[key]):
            raise ValueError(f"{key}: file {cfg[key]} != program {getattr(mc, attr)}")
    if mc.ssm_heads != cfg["mamba_n_heads"] or mc.num_experts != cfg["experts_published"]:
        raise ValueError("Mamba heads or the router's width differ from the file")
    if tuple(mc.layer_types[:cfg["num_hidden_layers"]]) != tuple(cfg["layer_types"]):
        raise ValueError("layer_types differ from the program's")
    return mc.with_(num_layers=cfg["num_hidden_layers"],
                    layer_types=tuple(cfg["layer_types"]),
                    experts_held=tuple(cfg["experts_held"]), vocab_size=cfg["vocab_size"])


class Driver:
    def __init__(self, bench):
        self.b = bench
        self.cfg = bench.config
        self.traffic = bench.traffic
        self.experiments: list[dict] = []

    # -- set-up ---------------------------------------------------------------
    def _configs(self, seed: int):
        from repro.fed.server import ServerConfig
        from repro.fed.simulator import SimConfig
        from repro.kernels.policy import KernelPlan

        c, t = self.cfg, self.traffic
        sim = SimConfig(
            num_clients=c["clients"], bad_frac=c["byzantine"] / c["clients"],
            scenario=t["scenario"], rounds=t["rounds"], local_epochs=t["local_steps"],
            batch_size=t["batch"], lr=c["lr"], momentum=c["momentum"], dropout=False,
            byzantine_scale=c["byzantine_scale"], seed=seed, engine="fused",
        )
        server = ServerConfig(
            rule=c["rule"], num_clients=c["clients"], alpha0=c["alpha0"],
            beta0=c["beta0"], xi0=c["xi0"], delta_xi=c["delta_xi"],
            delta_block=c["delta_block"], afa_variant=c["afa_variant"],
            kernel_plan=KernelPlan(mode=kernel_mode(c)),
        )
        return sim, server

    def prepare(self) -> None:
        """The model, the workload, the run's corpus and the frozen base."""
        from repro.data import markov_sequences
        from repro.fed.workload import get_workload

        c, t = self.cfg, self.traffic
        self.model_cfg = model_config(c)
        self.workload = get_workload("lora", model_cfg=self.model_cfg,
                                     rank=c["lora"]["rank"], alpha=c["lora"]["alpha"])
        if tuple(self.workload.targets) != tuple(c["lora"]["targets"]):
            raise ValueError("LoRA targets differ from the file")
        self.corpus = markov_sequences(data.sub_seeds(self.b.seed, 1, salt=1)[0],
                                       c["vocab_size"], t["corpus_sequences"], t["seq"] + 1)
        self.dims = granite_lora.model_dims(c)
        self.base = granite_lora.init_base(data.sub_seeds(self.b.seed, 1, salt=5)[0], self.dims)

    def setup(self) -> None:
        from repro.fed.server import make_rule_options

        c = self.cfg
        self.prepare()
        sim, server = self._configs(data.sub_seeds(self.b.seed, 1, salt=3)[0])
        opts = make_rule_options(server, c["clients"])
        self.b.note(kernel_route=str(opts.afa.use_kernels),
                    kernel_launch=opts.afa.kernel_launch, afa_variant=opts.afa.variant)
        self._run(sim, server)  # warm-up: compiles every program

    def _adapters0(self, seed: int):
        lora = self.cfg["lora"]
        return granite_lora.init_adapters(seed, self.base, lora["targets"], lora["rank"])

    def _run(self, sim, server):
        from repro.fed.api import run

        t = self.traffic
        return run(self.workload, sim, server, data=self.corpus,
                   samples_per_client=t["samples_per_client"], seq=t["seq"],
                   n_test=t["n_test"], keep_round1=True,
                   params0={"base": self.base, "adapters": self._adapters0(sim.seed)})

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
            blocked = np.asarray(res["blocked"], bool)
            first = np.where(blocked.any(axis=0), blocked.argmax(axis=0) + 1, -1)
            e = dict(seed=seed, wall_s=wall, rounds=sim.rounds, blocked_round=first,
                     kept=np.asarray(res["good_mask"], bool),
                     sims=np.asarray(res["similarities"], np.float64),
                     test_error=np.asarray(res["test_error"], np.float64))
            if not self.experiments:  # the checked one: its round 1 as the run made it
                e["w1"] = granite_lora.flat_row(res["params_round1"]["adapters"])
                e["choices"] = res["experts"][0]
            self.experiments.append(e)
            del res
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0

    # -- facts the metrics read ------------------------------------------------
    @property
    def rounds(self) -> int:
        return sum(e["rounds"] for e in self.experiments)

    def round_work(self) -> list[tuple[float, float]]:
        """(FLOPs, bytes) every round of the window requires
        (``bench/work_lora``): the live honest clients' local training and
        the held-out forward."""
        c, t = self.cfg, self.traffic
        bad = np.arange(c["clients"]) < c["byzantine"]
        out = []
        for e in self.experiments:
            br = e["blocked_round"]
            for r in range(e["rounds"]):
                live = (br < 0) | (r < br)
                out.append(lora_round_work(
                    c, int((live & ~bad).sum()), t["local_steps"], t["batch"],
                    t["seq"], t["n_test"]))
        return out

    # -- check ----------------------------------------------------------------
    def release(self) -> None:
        """The window's results are host arrays; the base stays for the check."""
        self.checked = self.experiments[0]

    def fed(self) -> dict:
        c, t = self.cfg, self.traffic
        keys = ("clients", "byzantine", "lr", "momentum", "byzantine_scale", "alpha0",
                "beta0", "xi0", "delta_xi", "afa_max_rounds")
        return dict({k: c[k] for k in keys}, steps=t["local_steps"], batch=t["batch"],
                    scaling=c["lora"]["alpha"] / c["lora"]["rank"])

    def reference(self, seed: int, choices=None, precision: str = "float32") -> dict:
        """The reference's round 1 of the experiment on ``seed``, from its own
        draw of the adapters and its own shards of the corpus."""
        c, t = self.cfg, self.traffic
        d = granite_lora.shards(self.corpus, c["clients"], t["samples_per_client"],
                                t["n_test"], seed)
        return granite_lora.round_one(self.base, self._adapters0(seed), d, seed, self.dims,
                                      self.fed(), choices, precision)

    def compare(self, got: dict, ref: dict) -> dict:
        """The numbers the cell's limits judge (and the logged rest).  With
        ``got["blocked_round"]`` and ``got["kept_all"]`` (the run's blocked
        rounds and its kept sets of every round), its byzantine clients'
        blocking is held to the exact rule over those kept sets."""
        c = self.cfg
        honest = np.arange(c["clients"]) >= c["byzantine"]
        out = {
            "first_round_update_gap": float(np.linalg.norm(got["w1"] - ref["w1"])
                                            / np.linalg.norm(ref["w1"] - ref["w0"])),
            "first_round_similarity_gap": float(np.abs(got["sims"] - ref["sims"]).max()),
            "first_round_kept_differing": int((got["kept"] != ref["kept"]).sum()),
            "byzantine_kept_first_round": int(ref["kept"][~honest].sum()),
            "expert_choices_outside_top_k_share": (
                ref["choices_outside_top_k"] / max(ref["choices_total"], 1)),
        }
        if "kept_all" in got:
            ref_blocked = granite_lora.blocked_rounds(got["kept_all"], c["alpha0"], c["beta0"],
                                                      c["delta_block"])
            out["byzantine_blocked_differing"] = int(
                (np.asarray(got["blocked_round"])[~honest] != ref_blocked[~honest]).sum())
        return out

    def check(self) -> dict:
        e = self.checked
        ref = self.reference(e["seed"], e["choices"])
        got = dict(w1=e["w1"], sims=e["sims"][0], kept=e["kept"][0], kept_all=e["kept"],
                   blocked_round=e["blocked_round"])
        return self.compare(got, ref)
