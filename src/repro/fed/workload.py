"""ClientWorkload — the pluggable client-training layer (DESIGN.md §Workload).

Every round engine (fused, segmented, sharded, and the serve tier) runs the same
pipeline: *propose* (local training per client), *attack* (update-level
transforms on the stacked proposals), *screen + aggregate* (the AFA stack),
*apply* (fold the aggregate back into the model).  The engines used to
hard-wire the paper's tiny DNN (``fed/dnn.py``) into that pipeline; this
module factors the model-specific pieces behind one protocol so the same
engines drive any workload:

* ``init_params(key)`` — build the full model state (whatever the workload
  trains on; may contain frozen parts).
* ``local_update(cfg, params, batches, key)`` — one client's local training.
  Returns a **proposal-space** tree: the thing clients send to the server.
* ``codec`` (a :class:`ProposalCodec`) — the params <-> proposal-space map.
  ``proposal_of(params)`` projects the server's current params to proposal
  space (the reference row ``w_t`` that attacks perturb and non-trainers
  hold); ``apply(params, aggregate)`` folds an aggregated proposal back into
  full params.
* ``delta_spec(params)`` — the cached :class:`~repro.utils.trees.PackSpec`
  of one proposal row, i.e. the layout of the ``(K, D)`` buffer the
  matrix-form rules aggregate.
* ``eval_metric(params, x_test, y_test)`` — scalar error in [0, 1] emitted
  per round by the fused trajectory.

The key property (the source paper's, arXiv:1909.05125): AFA's screening is
cosine similarity of *update vectors* against the weighted aggregate — it
never looks inside the model.  So a workload whose proposal space is a
low-rank adapter tree (``TransformerLoraWorkload``) runs the whole robust
aggregation stack — screening, reputation, blocking, compaction, packed
``(K, D_adapter)`` dispatch — unmodified, on a buffer with
``D_adapter ≪ D``.  The paper DNN remains available as ``DnnWorkload`` and
is **bit-identical** through the protocol to the pre-refactor engines
(asserted in ``tests/test_workload.py``).

Workloads are frozen dataclasses (hashable by field values) and codecs are
module-level function pairs, so they are stable cache keys for the engines'
``lru_cache``'d builders — constructing the "same" workload twice reuses the
compiled scan.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.fed.client import local_sgd, local_sgd_frozen
from repro.fed.dnn import dnn_error, dnn_loss, init_dnn
from repro.utils.spans import span
from repro.utils.trees import PackSpec, pack_spec, tree_size


class ProposalCodec(NamedTuple):
    """params <-> proposal-space map (module-level functions: stable hash).

    ``proposal_of(params) -> tree`` projects full params onto the space
    clients propose in; ``apply(params, aggregate) -> params'`` folds an
    aggregated proposal back.  For full-parameter workloads both are
    (near-)identities; for delta workloads ``proposal_of`` selects the
    trainable sub-tree and ``apply`` swaps it in against the frozen rest.
    """

    proposal_of: Callable[[Any], Any]
    apply: Callable[[Any, Any], Any]


def _identity_proposal(params):
    return params


def _identity_apply(params, aggregate):
    del params
    return aggregate


#: full-parameter proposals: clients send whole models, the aggregate IS the
#: next global model (the paper's setting).
IDENTITY_CODEC = ProposalCodec(_identity_proposal, _identity_apply)


def _adapter_proposal(params):
    return params["adapters"]


def _adapter_apply(params, aggregate):
    return {"base": params["base"], "adapters": aggregate}


#: low-rank-delta proposals: clients send only the adapter tree; the server
#: swaps the aggregated adapters in against the frozen base.
ADAPTER_CODEC = ProposalCodec(_adapter_proposal, _adapter_apply)


def validate_submission(spec: PackSpec, payload) -> np.ndarray:
    """Validate ONE submitted packed proposal row against a workload's
    :class:`~repro.utils.trees.PackSpec` — the serving tier's wire contract.

    A client submission is a ``(D,)`` row of the packed aggregation buffer in
    the spec's promoted dtype.  Anything else — wrong rank, wrong width,
    non-castable dtype, NaN/Inf entries — raises ``ValueError`` and the
    service rejects the submission at ingress (reason ``invalid``).  The
    finiteness check is load-bearing, not cosmetic: the engines' masked-row
    invariance (a rejected row never influences the aggregate) relies on
    masked rows being zeroed by multiplication, and ``0 * inf = nan`` would
    leak a poisoned row through the mask.

    Returns the row as a host array in ``spec.dtype``.
    """
    row = np.asarray(payload)
    if row.shape != (spec.dim,):
        raise ValueError(
            f"submission shape {row.shape} != ({spec.dim},) — one packed "
            "proposal row per submission"
        )
    if not np.can_cast(row.dtype, spec.dtype, casting="same_kind"):
        raise ValueError(
            f"submission dtype {row.dtype} does not safely cast to the "
            f"packed buffer dtype {spec.dtype}"
        )
    row = row.astype(spec.dtype, copy=False)
    if np.issubdtype(row.dtype, np.floating) and not np.all(np.isfinite(row)):
        raise ValueError("submission contains non-finite entries")
    return row


class ClientWorkload:
    """Protocol base (subclasses are frozen dataclasses — see module doc).

    The engines treat a workload as an opaque hashable value: it keys the
    ``lru_cache``'d scan builders and its methods are traced into the round
    body.  Methods must therefore be pure jax (jit/vmap/scan-safe) and the
    instance itself must never close over tracers.
    """

    name: str = "abstract"
    codec: ProposalCodec = IDENTITY_CODEC

    def init_params(self, key):
        raise NotImplementedError

    def local_update(self, cfg, params, batches, key):
        """One client's local training -> proposal-space tree.

        ``cfg`` is the engine's :class:`~repro.fed.engine.EngineConfig`
        (static at trace time); ``batches`` is a pytree of ``(S, b, ...)``
        prebuilt minibatches; ``key`` the client's per-round RNG key.
        """
        raise NotImplementedError

    def local_update_with_stats(self, cfg, params, batches, key):
        """``(proposal, stats)``: ``stats`` is a pytree of this client's
        local-training statistics, which the fused engines sum over the
        clients that trained into ``FusedTrajectory.workload_stats`` (None:
        the workload reports none)."""
        return self.local_update(cfg, params, batches, key), None

    def eval_metric(self, params, x_test, y_test):
        """Scalar error in [0, 1] on the held-out set."""
        raise NotImplementedError

    def delta_spec(self, params):
        """PackSpec of one proposal row — the ``(K, D)`` buffer layout."""
        return pack_spec(self.codec.proposal_of(params))

    def proposal_dim(self, params) -> int:
        """D: flattened size of one proposal row."""
        return tree_size(self.codec.proposal_of(params))

    def validate_submission(self, params, payload) -> np.ndarray:
        """Ingress validation of one submitted packed proposal row (the
        serving tier's wire contract) — see :func:`validate_submission`."""
        return validate_submission(self.delta_spec(params), payload)

    def param_dim(self, params) -> int:
        """Total model size (frozen + trainable)."""
        return tree_size(params)


# ---------------------------------------------------------------------------
# DnnWorkload — the paper's DNN, bit-identical through the protocol
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DnnWorkload(ClientWorkload):
    """The paper's MNIST/Spambase DNN as a workload (the reference).

    ``local_update`` is a literal pass-through to ``local_sgd(dnn_loss, ...)``
    with the identical argument spelling the engines used before the workload
    seam existed, and the codec is the identity — the traced round body is
    the same jaxpr, so trajectories are bit-identical to the pre-refactor
    engines (``tests/test_workload.py`` holds the line).
    """

    sizes: tuple  # (d_in, *hidden, d_out)

    name = "dnn"
    codec = IDENTITY_CODEC

    def __post_init__(self):
        object.__setattr__(self, "sizes", tuple(int(s) for s in self.sizes))

    def init_params(self, key):
        return init_dnn(key, self.sizes)

    def local_update(self, cfg, params, batches, key):
        return local_sgd(
            dnn_loss, params, batches, key,
            lr=cfg.lr, momentum=cfg.momentum, dropout=cfg.dropout,
        )

    def eval_metric(self, params, x_test, y_test):
        return dnn_error(params, x_test, y_test)


# ---------------------------------------------------------------------------
# TransformerLoraWorkload — federated LLM fine-tuning on low-rank deltas
# ---------------------------------------------------------------------------
#
# Clients hold a frozen transformer base (models/ stack: vmapped per-layer
# init, jax.checkpoint'd scan over layers) and train only LoRA adapters on
# the stacked attention and state-space projections: for each target matrix
# W (L, d_in, d_out) an A (L, d_in, r) / B (L, r, d_out) pair with B
# zero-initialised.  Training applies them unmerged inside each layer,
# x @ W + (alpha/r) (x @ A) @ B (``attach_lora``; ``models.layers.linear``),
# so the frozen base stays one unbatched copy under the client vmap;
# ``merge_lora`` builds W + (alpha/r) A @ B for export.  The proposal space is the
# adapter tree, so the packed aggregation buffer is (K, D_adapter) with
# D_adapter ≪ D, and every update-level attack (byzantine/alie/ipm) operates
# on adapters for free — w_prev handed to the attack layer is the current
# adapter state.
#
# The model/loss builders are module-level lru_caches keyed on the hashable
# ModelConfig so the jit identity of the round body is stable across workload
# re-construction (same reason local_sgd_frozen takes the frozen base as a
# *traced* argument instead of closing over it).


@functools.lru_cache(maxsize=8)
def _lora_model(model_cfg):
    from repro.models import build_model

    return build_model(model_cfg)


def _adapter_sites(layers, targets):
    """(path, shape) of every stacked ``(L, d_in, d_out)`` leaf whose final
    key names a LoRA target, in deterministic (dict-order) traversal."""
    sites = []

    def walk(node, path):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, path + (k,))
        elif path and path[-1] in targets and getattr(node, "ndim", 0) == 3:
            sites.append((path, node.shape))

    walk(layers, ())
    return sites


def init_lora_adapters(key, layers, targets, rank: int):
    """Adapter tree mirroring ``layers``: at each target leaf a
    ``{"a": (L, d_in, r), "b": (L, r, d_out)}`` pair, A ~ N(0, 1/d_in),
    B = 0 — so the initial delta is exactly zero and round 0 starts from the
    frozen base."""
    sites = _adapter_sites(layers, targets)
    if not sites:
        raise ValueError(
            f"no LoRA target leaves {targets!r} found in the layer stack"
        )
    keys = jax.random.split(key, len(sites))
    adapters: dict = {}
    for k, (path, shape) in zip(keys, sites):
        L, d_in, d_out = shape
        a = jax.random.normal(k, (L, d_in, rank), jnp.float32) / np.sqrt(d_in)
        b = jnp.zeros((L, rank, d_out), jnp.float32)
        node = adapters
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = {"a": a, "b": b}
    return adapters


def _is_site(anode) -> bool:
    return isinstance(anode, dict) and set(anode) == {"a", "b"}


def attach_lora(layers, adapters, scaling: float):
    """Layer stack with each adapted leaf W replaced by ``{"w": W, "a":
    scaling * A, "b": B}``, which ``models.layers.linear`` applies unmerged
    (``x @ W + (x @ scaling A) @ B``).  Nothing is copied: W is the frozen
    base's own array, shared by every client under the vmap."""

    def walk(node, anode):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            sub = anode.get(k) if isinstance(anode, dict) else None
            if _is_site(sub) and not isinstance(v, dict):
                out[k] = {"w": v, "a": sub["a"] * scaling, "b": sub["b"]}
            else:
                out[k] = walk(v, sub)
        return out

    return walk(layers, adapters)


def merge_lora(layers, adapters, scaling: float):
    """Effective layer stack: target leaves get ``W + scaling * A @ B``
    (batched over the layer axis), everything else passes through."""

    def walk(node, anode):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            sub = anode.get(k) if isinstance(anode, dict) else None
            if _is_site(sub) and not isinstance(v, dict):
                delta = jnp.einsum("lir,lro->lio", sub["a"], sub["b"]) * scaling
                out[k] = (v.astype(jnp.float32) + delta).astype(v.dtype)
            else:
                out[k] = walk(v, sub)
        return out

    return walk(layers, adapters)


def _merged_params(base, adapters, scaling: float):
    eff = dict(base)
    eff["layers"] = merge_lora(base["layers"], adapters, scaling)
    return eff


def _attached_params(base, adapters, scaling: float):
    eff = dict(base)
    eff["layers"] = attach_lora(base["layers"], adapters, scaling)
    return eff


@functools.lru_cache(maxsize=8)
def _lora_loss_fn(model_cfg, targets, scaling: float):
    """Loss over (frozen base, adapters) with the engine's ``{"x","y"}``
    batch convention mapped to the LM's ``{"tokens","labels"}``, returned
    with the experts every token chose (``{"experts": ...}``) where the
    model reports them, ``{}`` where it does not.  Accepts (and ignores)
    ``dropout_rng`` so the client RNG stream is spelled exactly like the DNN
    path's."""
    model = _lora_model(model_cfg)

    def loss(base, adapters, mb, *, dropout_rng=None):
        del dropout_rng  # the LM stack is deterministic; key split still happens
        eff = _attached_params(base, adapters, scaling)
        value, metrics = model.loss_fn(eff, {"tokens": mb["x"], "labels": mb["y"]})
        return value, {k: metrics[k] for k in ("experts",) if k in metrics}

    return loss


@dataclasses.dataclass(frozen=True)
class TransformerLoraWorkload(ClientWorkload):
    """Federated LLM fine-tuning: clients propose LoRA deltas on a frozen
    transformer base (see the section comment above)."""

    model_cfg: Any  # repro.models.ModelConfig (frozen dataclass, hashable)
    rank: int = 4
    alpha: float = 8.0
    # attention q/k/v/o and the Mamba-2 mixer's input and output projections
    targets: tuple = ("wq", "wk", "wv", "wo", "in_proj", "out_proj")

    name = "lora"
    codec = ADAPTER_CODEC

    def __post_init__(self):
        object.__setattr__(self, "targets", tuple(self.targets))

    @property
    def scaling(self) -> float:
        return float(self.alpha) / float(self.rank)

    def init_params(self, key):
        k_base, k_adapt = jax.random.split(key)
        base = _lora_model(self.model_cfg).init(k_base)
        adapters = init_lora_adapters(
            k_adapt, base["layers"], self.targets, self.rank
        )
        return {"base": base, "adapters": adapters}

    def local_update(self, cfg, params, batches, key):
        return self.local_update_with_stats(cfg, params, batches, key)[0]

    def local_update_with_stats(self, cfg, params, batches, key):
        """Stats, for a model that routes: ``experts``, the expert ids each
        token chose in each layer at each local step, and ``trained`` (True;
        the engine zeroes both for a client that did not train)."""
        loss = _lora_loss_fn(self.model_cfg, self.targets, self.scaling)
        adapters, aux = local_sgd_frozen(
            loss, params["base"], params["adapters"], batches, key,
            lr=cfg.lr, momentum=cfg.momentum, dropout=cfg.dropout,
        )
        if not aux:
            return adapters, None
        return adapters, {"experts": aux["experts"], "trained": jnp.ones((), bool)}

    def eval_metric(self, params, x_test, y_test):
        """Masked next-token error: fraction of (label >= 0) positions where
        the greedy prediction misses."""
        model = _lora_model(self.model_cfg)
        eff = _attached_params(params["base"], params["adapters"], self.scaling)
        logits = model.forward(eff, {"tokens": x_test})
        pred = jnp.argmax(logits, axis=-1)
        mask = y_test >= 0
        wrong = jnp.sum(((pred != y_test) & mask).astype(jnp.float32))
        return wrong / jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)

    def merged_params(self, params):
        """Full effective model (base + scaled deltas) — inference/export."""
        return _merged_params(params["base"], params["adapters"], self.scaling)


# ---------------------------------------------------------------------------
# registry — the launch CLI routes --arch / --workload through here
# ---------------------------------------------------------------------------


def _build_dnn(*, sizes, **_ignored) -> DnnWorkload:
    return DnnWorkload(sizes=tuple(sizes))


def _build_lora(
    *, arch: str = "smollm-135m", reduced: bool = True, rank: int = 4,
    alpha: float = 8.0, model_cfg=None, clients: int | None = None, **_ignored,
) -> TransformerLoraWorkload:
    if model_cfg is None:
        from repro.configs import get_config

        model_cfg = get_config(arch)
        if reduced:
            model_cfg = model_cfg.reduced().with_(
                param_dtype="float32", compute_dtype="float32"
            )
    if clients is not None:
        model_cfg = model_cfg.with_(fed_clients=int(clients))
    return TransformerLoraWorkload(model_cfg=model_cfg, rank=rank, alpha=alpha)


WORKLOADS: dict[str, Callable[..., ClientWorkload]] = {
    "dnn": _build_dnn,
    "lora": _build_lora,
}


def get_workload(name: str, **kwargs) -> ClientWorkload:
    """Build a registered workload: ``get_workload("dnn", sizes=(...))`` or
    ``get_workload("lora", arch="smollm-135m", reduced=True, rank=4)``."""
    if name not in WORKLOADS:
        raise ValueError(f"unknown workload {name!r}; expected {sorted(WORKLOADS)}")
    return WORKLOADS[name](**kwargs)


# ---------------------------------------------------------------------------
# fused-engine driver for the LLM workload (examples / CI smoke / benchmarks)
# ---------------------------------------------------------------------------


def make_llm_fused_data(
    model_cfg, *, clients: int, samples_per_client: int = 16, seq: int = 32,
    n_test: int = 16, seed: int = 0,
):
    """Device-ready :class:`~repro.fed.engine.FusedData` over the synthetic
    bigram-markov token stream: per-client ``(n, seq)`` int32 token/label
    shards stacked to ``(K, n, seq)`` plus a held-out eval batch.  Shapes are
    exactly what the fused engine's generic per-client gather expects — the
    trailing shard shape is opaque to the engine."""
    from repro.data import make_token_stream
    from repro.data.sharding import padded_stack
    from repro.fed.engine import FusedData

    need = (clients * samples_per_client + n_test) * (seq + 1)
    stream = make_token_stream(
        seed=seed, vocab=model_cfg.vocab_size, n=max(4 * need, 8_192)
    )
    rng = np.random.default_rng(seed)
    shards = []
    for _ in range(clients):
        b = next(iter(stream.batches(rng, batch=samples_per_client, seq=seq, n_batches=1)))
        shards.append(
            (np.asarray(b["tokens"], np.int32), np.asarray(b["labels"], np.int32))
        )
    x, y, lengths = padded_stack(shards)
    tb = next(iter(stream.batches(rng, batch=n_test, seq=seq, n_batches=1)))
    return FusedData(
        x=jnp.asarray(x), y=jnp.asarray(y),
        lengths=jnp.asarray(lengths),
        n_k=jnp.asarray(lengths, jnp.float32),
        x_test=jnp.asarray(tb["tokens"]), y_test=jnp.asarray(tb["labels"]),
    )


def llm_data_from_sequences(seqs, *, clients: int, samples_per_client: int,
                            n_test: int, seed: int):
    """Host :class:`~repro.fed.engine.FusedData` for one experiment from a
    corpus of token sequences ``(N, seq + 1)``: ``seed`` permutes the corpus,
    the first ``clients * samples_per_client`` sequences become the clients'
    shards (inputs ``s[:-1]``, next-token labels ``s[1:]``) and the next
    ``n_test`` the held-out batch."""
    from repro.fed.engine import FusedData

    need = clients * samples_per_client + n_test
    if len(seqs) < need:
        raise ValueError(f"corpus of {len(seqs)} sequences; the experiment needs {need}")
    pick = np.asarray(seqs)[np.random.default_rng(seed).permutation(len(seqs))[:need]]
    train = pick[: clients * samples_per_client].reshape(clients, samples_per_client, -1)
    test = pick[clients * samples_per_client:]
    lengths = np.full((clients,), samples_per_client, np.int32)
    return FusedData(
        x=train[..., :-1].astype(np.int32), y=train[..., 1:].astype(np.int32),
        lengths=lengths, n_k=lengths.astype(np.float32),
        x_test=test[:, :-1].astype(np.int32), y_test=test[:, 1:].astype(np.int32),
    )


def simulate_llm(
    workload: TransformerLoraWorkload,
    *,
    clients: int = 6,
    byzantine: int = 2,
    rounds: int = 6,
    local_steps: int = 2,
    batch: int = 2,
    samples_per_client: int = 16,
    seq: int = 32,
    n_test: int = 16,
    seed: int = 0,
    lr: float = 0.2,
    scenario: str = "byzantine",
    rule: str = "afa",
    data=None,
    server=None,
    params0=None,
    keep_round1: bool = False,
):
    """Run the fused T-round simulation on the LLM workload and summarize.

    The first ``byzantine`` clients run the update-level attack ``scenario``
    (on the *adapter* proposals — the attack layer is workload-agnostic);
    AFA screens the packed ``(K, D_adapter)`` buffer, reputation accumulates,
    and blocking kicks the attackers out of the aggregate.  ``data`` is a
    prebuilt ``FusedData``, a corpus of token sequences ``(N, seq + 1)``
    that ``seed`` shards (:func:`llm_data_from_sequences`), or None (the
    synthetic token stream).  ``server`` (a ``ServerConfig``) sets the
    rule's options; None builds one for ``rule``.  ``params0`` are the
    initial ``{"base", "adapters"}`` (a pretrained base to fine-tune); None
    draws them from ``seed`` (``workload.init_params``).  Returns a dict of
    host numpy results (trajectory, blocking, buffer geometry), the final
    ``params`` on the device, with ``keep_round1`` the params after round 1
    (``params_round1``), and for a model that routes, ``experts``
    ``(T, K, steps, L, batch, seq, top_k)`` (the ids every token chose in
    every layer) and ``trained`` ``(T, K)`` (which rows they belong to).

    Spans: ``fed.setup`` (data to the device, ``h2d_bytes``; the model's
    init where no ``params0`` is given), ``fed.llm.call`` (the scan's
    dispatch; ``rows``, the clients, and ``train_rows``, the rows the local
    update runs over), ``fed.llm.wait`` (until its trajectory is ready),
    ``fed.result``; and, for a workload whose model reports routing, one
    ``fed.moe.route`` record (see :func:`_record_route`).
    """
    from repro.fed.engine import (
        EngineConfig,
        FusedData,
        make_fused_sim,
        trainer_count,
    )
    from repro.fed.server import ServerConfig, make_rule_options

    with span("fed.setup") as attrs:
        if data is None:
            data = make_llm_fused_data(
                workload.model_cfg, clients=clients,
                samples_per_client=samples_per_client, seq=seq, n_test=n_test,
                seed=seed,
            )
        elif not isinstance(data, FusedData):
            data = llm_data_from_sequences(
                data, clients=clients, samples_per_client=samples_per_client,
                n_test=n_test, seed=seed)
        attrs["h2d_bytes"] = sum(
            int(a.nbytes) for a in data if not isinstance(a, jax.Array))
        data = FusedData(*(jnp.asarray(a) for a in data))
        bad = np.zeros((clients,), bool)
        bad[:byzantine] = True

        cfg = EngineConfig(scenario=scenario, lr=lr, momentum=0.9, dropout=False)
        scfg = server if server is not None else ServerConfig(
            rule=rule, num_clients=clients,
            num_byzantine=max(byzantine, 1), trim=max(min(byzantine, (clients - 1) // 2), 1),
        )
        scan_fn, _ = make_fused_sim(
            workload, cfg, rule=scfg.rule, opts=make_rule_options(scfg, clients),
            delta_block=scfg.delta_block, num_clients=clients, num_rounds=rounds,
            batch_s=local_steps, batch_b=batch, bad_mask=bad, agg_layout="packed",
            alpha0=scfg.alpha0, beta0=scfg.beta0, keep_round1=keep_round1,
        )
        if params0 is None:
            params0 = _init_fn(workload)(jax.random.PRNGKey(seed))
    with span("fed.llm.call", rows=clients,
              train_rows=trainer_count(scenario, bad)):
        params, state, traj, *first = scan_fn(params0, np.uint32(seed), data)
    with span("fed.llm.wait"):
        jax.block_until_ready(traj)

    with span("fed.result"):
        d_adapter = workload.proposal_dim(params0)
        d_total = workload.param_dim(params0)
        good_frac = np.asarray(traj.good_mask, np.float32).mean(axis=1)
        out = {
            "test_error": np.asarray(traj.test_error),
            "good_frac": good_frac,
            "good_mask": np.asarray(traj.good_mask),
            "similarities": np.asarray(traj.similarities),
            "blocked": np.asarray(traj.blocked),
            "rounds_blocked": np.asarray(state.rounds_blocked),
            "bad_mask": bad,
            "adapter_dim": int(d_adapter),
            "param_dim": int(d_total),
            "adapter_fraction": float(d_adapter) / float(d_total),
            "params": params,
        }
        if first:
            out["params_round1"] = first[0]
        if traj.workload_stats is not None:
            out["experts"] = np.asarray(traj.workload_stats["experts"])
            out["trained"] = np.asarray(traj.workload_stats["trained"]).astype(bool)
    if "experts" in out:
        _record_route(out["experts"], out["trained"], workload.model_cfg)
    return out


@functools.lru_cache(maxsize=8)
def _init_fn(workload):
    """``workload.init_params`` as one compiled program (op by op, a model
    of this size dispatches hundreds of small random draws)."""
    return jax.jit(workload.init_params)


def _record_route(experts, trained, model_cfg) -> None:
    """One ``fed.moe.route`` record for the experiment, over every round,
    client that trained, local step, layer and token: ``tokens_held`` the
    token-choices this chip's experts (``model_cfg.held_range``) computed;
    ``held_share`` their share of all token-choices (top-k per token and
    layer; 9/72 = 12.5% under uniform routing); ``max_over_mean`` the
    largest (layer, held expert) count over the mean of those counts."""
    lo, hi = model_cfg.held_range
    chosen = np.asarray(experts)[np.asarray(trained, bool)]  # (n, S, L, B, seq, k)
    per = np.stack([
        np.bincount(chosen[:, :, layer].ravel(), minlength=model_cfg.num_experts)[lo:hi]
        for layer in range(chosen.shape[2])]).astype(np.float64)  # (L, held)
    held = float(per.sum())
    with span("fed.moe.route", tokens_held=int(held),
              held_share=held / max(float(chosen.size), 1.0),
              max_over_mean=float(per.max() / max(per.mean(), 1e-30))):
        pass
