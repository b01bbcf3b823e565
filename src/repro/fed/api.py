"""repro.fed.api — the ONE front door for running federated experiments.

Historically the repo grew three entrypoints that callers had to pick between
by hand: ``run_simulation`` (the classification simulator over its four round
engines), ``run_sweep`` (the seed-vmapped fused sweep), and
``run_llm_simulation`` (the LLM/LoRA fused driver in ``fed/workload.py``).
:func:`run` routes between them from its arguments, so examples, benchmarks,
and CI all call one function:

    from repro.fed.api import run

    # the paper's classification experiments (workload=None -> the paper DNN)
    result = run(None, sim, server, data=data)

    # seed sweep: one vmapped device program over the seed grid
    sweep = run(None, sim, server, data=data, seeds=range(8))

    # federated LoRA fine-tuning (any non-classification ClientWorkload)
    out = run(lora_workload, sim, server, local_steps=2)

Routing rules:

* ``workload`` is ``None``, a :class:`~repro.fed.workload.ClientWorkload`,
  or a registry name (``"dnn"`` / ``"lora"``, resolved through
  :func:`~repro.fed.workload.get_workload` with ``workload_kwargs``).
* ``None`` / ``DnnWorkload`` -> the classification simulator
  (``data`` must be a :class:`~repro.data.SyntheticClassification`);
  ``seeds`` selects the vmapped fused sweep.
* any other workload -> the LLM fused driver (``data`` may be a prebuilt
  :class:`~repro.fed.engine.FusedData` or a corpus of token sequences, and
  a ``server`` given sets the rule's options); extra keyword args
  (``local_steps``, ``samples_per_client``, ``seq``, ...) pass through.

The old names still work as thin shims that emit ``DeprecationWarning`` and
delegate to the same implementations (``tests/test_api.py`` asserts the
facade's trajectories are bit-identical to the shims').
"""

from __future__ import annotations

from typing import Any, Iterable, Optional, Union

from repro.fed.server import ServerConfig
from repro.fed.simulator import SimConfig, SimResult, SweepResult, simulate, sweep
from repro.fed.workload import ClientWorkload, DnnWorkload, get_workload, simulate_llm
from repro.utils.spans import span

WorkloadLike = Union[None, str, ClientWorkload]


def _resolve_workload(workload: WorkloadLike, workload_kwargs: dict | None):
    if isinstance(workload, str):
        return get_workload(workload, **(workload_kwargs or {}))
    if workload_kwargs:
        raise ValueError(
            "workload_kwargs only applies when `workload` is a registry name"
        )
    return workload


def run(
    workload: WorkloadLike,
    sim: SimConfig,
    server: Optional[ServerConfig] = None,
    *,
    data: Any = None,
    seeds: Optional[Iterable[int]] = None,
    eval_every: int = 1,
    workload_kwargs: Optional[dict] = None,
    **extra,
) -> Union[SimResult, SweepResult, dict]:
    """Run a federated experiment — simulation, sweep, or LLM fine-tuning.

    Parameters
    ----------
    workload:
        ``None`` (the paper DNN, sized from ``sim.hidden`` and the dataset),
        a ``ClientWorkload`` instance, or a registry name resolved with
        ``workload_kwargs``.
    sim:
        The :class:`~repro.fed.simulator.SimConfig` — clients, rounds,
        scenario, engine, seed.  On the LLM route its fields map onto the
        fused driver (``num_clients``/``bad_frac``/``rounds``/``batch_size``/
        ``local_epochs``/``seed``/``lr``/``scenario``).
    server:
        The :class:`~repro.fed.server.ServerConfig` (rule + AFA knobs +
        kernel plan).  Defaults to ``ServerConfig(num_clients=
        sim.num_clients)``.
    data:
        Classification route: a ``SyntheticClassification`` (required).
        LLM route: an optional prebuilt ``FusedData``.
    seeds:
        Classification route only — runs the seed-vmapped fused sweep and
        returns a :class:`~repro.fed.simulator.SweepResult`.
    extra:
        LLM route only — forwarded to the fused driver
        (``local_steps``, ``samples_per_client``, ``seq``, ``n_test``, ...).

    Returns ``SimResult``, ``SweepResult`` (with ``seeds``), or the LLM
    driver's result dict.
    """
    workload = _resolve_workload(workload, workload_kwargs)
    given_server = server
    if server is None:
        server = ServerConfig(num_clients=sim.num_clients)

    classification = workload is None or isinstance(workload, DnnWorkload)
    route = ("simulate" if seeds is None else "sweep") if classification else "llm"
    with span("fed.run", route=route, engine=sim.engine, K=sim.num_clients,
              rounds=sim.rounds):
        if classification:
            if extra:
                raise TypeError(
                    f"unexpected keyword arguments for the classification "
                    f"route: {sorted(extra)}"
                )
            if data is None:
                raise ValueError(
                    "the classification route needs `data` (a "
                    "SyntheticClassification); build one with repro.data"
                )
            if seeds is not None:
                return sweep(data, sim, server, seeds)
            return simulate(data, sim, server, eval_every=eval_every, workload=workload)

        # LLM / delta-workload route: the fused driver owns its geometry knobs
        if seeds is not None:
            raise ValueError(
                "seed sweeps are not wired for the LLM route; loop over "
                "sim.seed instead"
            )
        llm_kwargs = dict(
            clients=sim.num_clients,
            byzantine=int(round(sim.bad_frac * sim.num_clients)),
            rounds=sim.rounds,
            local_steps=sim.local_epochs,
            batch=sim.batch_size,
            seed=sim.seed,
            lr=sim.lr,
            scenario=sim.scenario,
            rule=server.rule,
            data=data,
            server=given_server,
        )
        llm_kwargs.update(extra)  # samples_per_client / seq / n_test / overrides
        return simulate_llm(workload, **llm_kwargs)
