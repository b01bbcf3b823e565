"""Server-side aggregation: registry-based rule dispatch + AFA
reputation/blocking state.

The server layer is a **pure functional core** wrapped by a thin stateful
shell (DESIGN.md §2/§3):

* ``ServerState`` — the complete server-side round state as a pytree:
  Beta-Bernoulli reputation (which carries the blocked set), the 1-indexed
  ``rounds_blocked`` bookkeeping, and the round counter.
* ``server_step(state, proposals, n_k, mask0, ...) -> (state', result)`` —
  ONE pure implementation of "aggregate + absorb the screening outcome".
  Traced inside the fused ``lax.scan`` (``SimConfig.engine="fused"``) and
  run eagerly by ``FedServer``, so both share one source of truth (the
  serve tier runs its versioned form, ``server_step_versioned``).
* ``FedServer`` — a stateful wrapper that drives ``server_step`` eagerly,
  one call at a time; it owns a ``ServerState`` and replaces it with
  ``server_step``'s output each round.

Proposals arrive either as a dense ``(K, d)`` matrix (``aggregate``) or as a
stacked pytree with a leading client axis (``aggregate_tree``).  Both routes
go through the single ``dispatch_rule`` / ``dispatch_rule_tree`` interface in
``repro.core``; AFA is the paper's rule, the others are comparison baselines.

Proposals live in the **workload's proposal space** (DESIGN.md §Workload
layer), not necessarily in full-parameter space: the paper DNN proposes
whole models (identity codec), the LoRA workload proposes ``(K, D_adapter)``
low-rank deltas.  Nothing here inspects the model — screening, reputation,
and blocking only ever see update vectors — so the server layer is
workload-agnostic by construction.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    AFAConfig,
    RULES,
    RuleOptions,
    ReputationState,
    dispatch_rule,
    dispatch_rule_tree,
    gather_reputation,
    init_reputation,
    mark_blocked_round,
    p_good,
    scatter_reputation,
    update_reputation,
    update_reputation_weighted,
)
from repro.kernels.policy import KernelPlan, resolve_kernel_plan


@dataclasses.dataclass
class ServerConfig:
    rule: str = "afa"            # any key of repro.core.RULES:
                                 # afa | fa | mkrum | comed | trimmed_mean
                                 # | bulyan | norm_clip | geomed | centered_clip
    num_clients: int = 10
    # AFA
    alpha0: float = 3.0
    beta0: float = 3.0
    xi0: float = 2.0
    delta_xi: float = 0.5
    delta_block: float = 0.95
    afa_variant: str = "iterative"
    # baselines
    num_byzantine: int = 3       # f for mkrum/bulyan
    trim: int = 3                # for trimmed_mean
    # THE kernel/layout decision: one frozen, host-resolved plan
    # (repro.kernels.policy.KernelPlan) covering the kernel route, the AFA
    # screening launch geometry, and the aggregation layout.  None = resolve
    # from the legacy knobs below (and $REPRO_KERNELS) via
    # ``resolve_server_plan``; setting BOTH a plan and a conflicting
    # non-default legacy knob raises.
    kernel_plan: KernelPlan | None = None
    # DEPRECATED — prefer ``kernel_plan``.  Route every rule's hot ops (the
    # fused AFA screen, gram / cosine-sim / weighted-sum, coord-median,
    # trimmed-mean) through the Pallas kernels.
    # A bool selects automatically via $REPRO_KERNELS (auto -> pallas on TPU,
    # the jnp reference elsewhere — interpret-mode Pallas is far slower than
    # XLA, and the Triton route only fits block-resident operands, so
    # "pallas-gpu" is explicit opt-in); a mode string "pallas" /
    # "pallas-gpu" / "jnp" / "interpret" pins the route (repro.kernels.policy).
    # ``make_rule_options`` resolves the request on the host, so the resolved
    # mode — not the ambient env var — keys the jit cache.  The comed and
    # trimmed-mean kernels are mask-aware (compare-count rank selection), so
    # every kernel route works in-jit with traced masks; only geomed /
    # centered-clip stay kernel-less (see DESIGN.md §3).
    use_kernels: bool | str = False
    # DEPRECATED — prefer ``kernel_plan``.  Aggregation layout of the tree
    # dispatch (DESIGN.md §3): "packed" packs the stacked proposal pytree
    # into one contiguous (K, D) buffer and runs every rule's matrix form on
    # it; "leaf" keeps the legacy per-leaf path (AFA's native tree form,
    # per-leaf flatten for the rest) — the reference the packed path is
    # benchmarked against.
    agg_layout: str = "packed"


_LEGACY_KNOB_DEFAULTS = {"use_kernels": False, "agg_layout": "packed"}


def resolve_server_plan(cfg: ServerConfig) -> KernelPlan:
    """The config's :class:`~repro.kernels.policy.KernelPlan`, resolved once.

    Precedence: an explicit ``cfg.kernel_plan`` wins; the legacy knobs
    (``use_kernels`` / ``agg_layout``) may then only agree with it or keep
    their defaults — a non-default legacy knob that CONTRADICTS the plan
    raises, because two explicit requests disagree.  Without a plan, the
    legacy knobs resolve through :func:`~repro.kernels.policy
    .resolve_kernel_plan` (which itself raises on a config-pinned mode
    fighting an env-pinned one) and a DeprecationWarning points at the plan.
    """
    if cfg.kernel_plan is not None:
        plan = cfg.kernel_plan
        conflicts = {
            name: getattr(cfg, name)
            for name, default in _LEGACY_KNOB_DEFAULTS.items()
            if getattr(cfg, name) != default
            and getattr(cfg, name) != getattr(plan, _PLAN_FIELD[name])
        }
        if conflicts:
            raise ValueError(
                f"ServerConfig.kernel_plan={plan} conflicts with legacy "
                f"knobs {conflicts}; set the plan OR the legacy knobs, not "
                "disagreeing values of both"
            )
        return plan
    if any(
        getattr(cfg, name) != default
        for name, default in _LEGACY_KNOB_DEFAULTS.items()
    ):
        warnings.warn(
            "ServerConfig.use_kernels / ServerConfig.agg_layout are "
            "deprecated; pass ServerConfig(kernel_plan=resolve_kernel_plan("
            "use_kernels, agg_layout)) instead",
            DeprecationWarning,
            stacklevel=3,
        )
    return resolve_kernel_plan(cfg.use_kernels, cfg.agg_layout)


_PLAN_FIELD = {"use_kernels": "mode", "agg_layout": "layout"}


# ---------------------------------------------------------------------------
# pure functional core
# ---------------------------------------------------------------------------


class ServerState(NamedTuple):
    """Complete server-side round state, as a pytree (scan-carriable)."""

    reputation: ReputationState   # Beta posteriors + blocked set, (K,) leaves
    rounds_blocked: jnp.ndarray   # (K,) int32 — 1-indexed round of first
                                  # blocking, -1 = never blocked
    round: jnp.ndarray            # scalar int32 — completed rounds


def init_server_state(
    num_clients: int, alpha0: float = 3.0, beta0: float = 3.0
) -> ServerState:
    return ServerState(
        reputation=init_reputation(num_clients, alpha0, beta0),
        rounds_blocked=jnp.full((num_clients,), -1, jnp.int32),
        round=jnp.int32(0),
    )


def gather_server_state(state: ServerState, keep, pad_to: int) -> ServerState:
    """Compact the full-K server state to the kept clients (+ pad rows).

    ``keep`` is the segmented fused engine's index map of still-live clients;
    the result carries ``pad_to`` client entries, pads permanently blocked
    (``rounds_blocked = -1`` — a pad is never a real client, so it reads as
    "never blocked").  ``-1`` entries in ``keep`` are interleaved pad slots
    (per-shard compaction pads every shard block's tail) and gather the same
    fills as end padding.  The round counter stays absolute.  Leaf gathers
    act on the LAST axis so vmapped sweep states ``(n_seeds, K)`` compact
    with the same helper.
    """
    keep = jnp.asarray(keep, jnp.int32)
    pad = pad_to - keep.shape[0]
    rb = jnp.take(state.rounds_blocked, jnp.maximum(keep, 0), axis=-1)
    rb = jnp.where(keep >= 0, rb, jnp.int32(-1))
    if pad > 0:
        widths = [(0, 0)] * (rb.ndim - 1) + [(0, pad)]
        rb = jnp.pad(rb, widths, constant_values=-1)
    return ServerState(
        reputation=gather_reputation(state.reputation, keep, pad_to),
        rounds_blocked=rb,
        round=state.round,
    )


def scatter_server_state(
    full: ServerState, compact: ServerState, keep
) -> ServerState:
    """Re-embed a compacted server state into the full-K layout (inverse of
    :func:`gather_server_state`).  Non-kept clients keep their pre-compaction
    entries — exact, because only blocked clients are ever dropped and
    blocking freezes their posterior and bookkeeping.  ``-1`` entries in
    ``keep`` are pad slots and are dropped, mirroring the gather."""
    keep_np = np.asarray(keep)
    live = keep_np >= 0
    idx = jnp.asarray(keep_np[live], jnp.int32)
    sel = jnp.asarray(np.nonzero(live)[0], jnp.int32)
    return ServerState(
        reputation=scatter_reputation(full.reputation, compact.reputation, keep),
        rounds_blocked=full.rounds_blocked.at[..., idx].set(
            jnp.take(compact.rounds_blocked, sel, axis=-1)
        ),
        round=compact.round,
    )


def make_rule_options(cfg: ServerConfig, num_participants: int, *,
                      client_axis: str | None = None,
                      client_shards: int = 0) -> RuleOptions:
    """Host-side knob bundle for the registry (hashable -> jit-static).

    ``client_axis``/``client_shards`` mark the options for use INSIDE a
    ``shard_map`` over a client mesh axis: AFA then runs its hierarchical
    two-stage screening (core/afa.py) and the dispatch guard reduces the
    all-blocked flag globally.  Both are static strings/ints so they key the
    jit cache like every other knob.

    ``num_selected`` is populated only for the rule that consumes it (MKRUM)
    — it tracks the live participant count, and threading it into every
    rule's options would retrace the jit'd dispatch each time a client gets
    blocked.  (Only AFA blocks, so under MKRUM the participant count is
    constant and the fused engine can compute it once before tracing.)

    The kernel route, launch geometry, and layout all come from the config's
    resolved :class:`~repro.kernels.policy.KernelPlan`
    (:func:`resolve_server_plan`) — resolved HERE, on the host: RuleOptions
    is a static jit argument, so resolving early makes the request key the
    jit cache instead of being frozen from whatever $REPRO_KERNELS said at
    first trace.  Only the *env-pinned* part is resolved (an explicit mode
    string replaces the bool); an auto request stays a bool — the backend it
    resolves by is fixed per process, and collapsing auto-True into a
    concrete mode string would make rules without a kernel (trimmed-mean)
    mistake auto selection on TPU for an explicit pallas demand and raise.
    """
    plan = resolve_server_plan(cfg)
    return RuleOptions(
        num_byzantine=cfg.num_byzantine,
        trim=cfg.trim,
        num_selected=(
            max(num_participants - cfg.num_byzantine - 2, 1)
            if cfg.rule == "mkrum" else None
        ),
        use_kernels=plan.mode,
        afa=AFAConfig(
            xi0=cfg.xi0, delta_xi=cfg.delta_xi, variant=cfg.afa_variant,
            use_kernels=plan.mode, kernel_launch=plan.launch,
            client_axis=client_axis, client_shards=client_shards,
        ),
    )


@functools.partial(jax.jit, static_argnames=("delta",))
def _absorb(state: ServerState, good_mask, mask0, *, delta: float) -> ServerState:
    """Fold one round's screening outcome into the Beta posteriors, the
    blocked set, and the 1-indexed ``rounds_blocked`` bookkeeping.  Module-
    level jit so the compiled update is shared across server instances; under
    an outer trace (the fused scan) it simply inlines."""
    rep = update_reputation(state.reputation, good_mask, mask0, delta=delta)
    rounds_blocked = mark_blocked_round(
        state.rounds_blocked, state.reputation.blocked, rep.blocked, state.round
    )
    return ServerState(rep, rounds_blocked, state.round + 1)


def server_step(
    state: ServerState,
    proposals,
    n_k: jnp.ndarray,
    mask0: jnp.ndarray,
    *,
    rule: str,
    opts: RuleOptions,
    delta_block: float = 0.95,
    layout: str = "tree",
):
    """One pure server round: dispatch the rule, then (for reputation-driven
    rules) absorb the screening outcome.

    Returns ``(state', result)`` where ``result`` is the rule's native output
    (``.aggregate`` + ``.good_mask``; AFA adds ``rounds``/``similarities``).
    ``proposals`` is a stacked pytree (``layout="tree"`` — packed tree
    dispatch — or ``layout="leaf"`` — the legacy per-leaf path) or a dense
    ``(K, D)`` matrix (``layout="matrix"``, and its alias ``"packed"`` for a
    buffer the caller packed with ``utils/trees.pack_stack`` — the fused
    round body packs once per round and unpacks the aggregate itself).  Pure
    in ``state`` — callable eagerly by :class:`FedServer` or traced inside
    the fused ``lax.scan`` (every kernel route is mask-aware, so tracing
    ``mask0`` costs nothing).
    """
    if layout in ("matrix", "packed"):
        res = dispatch_rule(
            rule, proposals, jnp.asarray(n_k, jnp.float32),
            p_good(state.reputation), mask0, opts,
        )
    elif layout in ("tree", "leaf"):
        res = dispatch_rule_tree(
            rule, proposals, jnp.asarray(n_k, jnp.float32),
            p_good(state.reputation), mask0, opts,
            layout="packed" if layout == "tree" else "leaf",
        )
    else:
        raise ValueError(
            f"unknown layout {layout!r}; expected tree | leaf | matrix | packed"
        )
    if RULES[rule].updates_reputation:
        state = _absorb(state, res.good_mask, jnp.asarray(mask0), delta=delta_block)
    else:
        state = state._replace(round=state.round + 1)
    return state, res


@functools.partial(jax.jit, static_argnames=("delta",))
def _absorb_weighted(
    state: ServerState, good_mask, mask0, weights, *, delta: float
) -> ServerState:
    """:func:`_absorb` with per-client evidence weights — the staleness-decay
    route of the serving tier (weights = decay**tau)."""
    rep = update_reputation_weighted(
        state.reputation, good_mask, mask0, weights, delta=delta
    )
    rounds_blocked = mark_blocked_round(
        state.rounds_blocked, state.reputation.blocked, rep.blocked, state.round
    )
    return ServerState(rep, rounds_blocked, state.round + 1)


def server_step_versioned(
    state: ServerState,
    proposals,
    n_k: jnp.ndarray,
    mask0: jnp.ndarray,
    versions: jnp.ndarray,
    *,
    rule: str,
    opts: RuleOptions,
    delta_block: float = 0.95,
    layout: str = "packed",
    staleness_decay: float = 1.0,
):
    """:func:`server_step` for ASYNC buffers: per-update version stamps.

    ``versions`` is ``(K,)`` int32 — the round counter of the params each
    buffered update was trained against; its staleness is ``tau =
    state.round - version`` (clipped at 0).  The rule dispatch itself is
    UNCHANGED — screening judges the update that was actually submitted —
    but a stale update is weaker evidence about the client's current
    behaviour, so the reputation absorb down-weights its Bernoulli
    observation by ``staleness_decay ** tau`` (a tempered Beta update,
    ``core/reputation.update_reputation_weighted``).

    ``staleness_decay = 1.0`` (the default, a host-static float) routes
    through the exact synchronous :func:`_absorb`, so the serve tier's
    buffer=K / deadline=inf / decay-off configuration reproduces the fused
    engine's state evolution bit for bit — the acceptance contract of the
    streaming tier.  Entries of ``versions`` for non-participating rows are
    inert (their good/bad observations are already mask-zeroed).
    """
    if not 0.0 < staleness_decay <= 1.0:
        raise ValueError(
            f"staleness_decay={staleness_decay!r} outside (0, 1]"
        )
    if layout in ("matrix", "packed"):
        res = dispatch_rule(
            rule, proposals, jnp.asarray(n_k, jnp.float32),
            p_good(state.reputation), mask0, opts,
        )
    elif layout in ("tree", "leaf"):
        res = dispatch_rule_tree(
            rule, proposals, jnp.asarray(n_k, jnp.float32),
            p_good(state.reputation), mask0, opts,
            layout="packed" if layout == "tree" else "leaf",
        )
    else:
        raise ValueError(
            f"unknown layout {layout!r}; expected tree | leaf | matrix | packed"
        )
    if RULES[rule].updates_reputation:
        if staleness_decay == 1.0:
            state = _absorb(
                state, res.good_mask, jnp.asarray(mask0), delta=delta_block
            )
        else:
            tau = jnp.maximum(
                state.round - jnp.asarray(versions, jnp.int32), 0
            )
            weights = jnp.float32(staleness_decay) ** tau.astype(jnp.float32)
            state = _absorb_weighted(
                state, res.good_mask, jnp.asarray(mask0), weights,
                delta=delta_block,
            )
    else:
        state = state._replace(round=state.round + 1)
    return state, res


# ---------------------------------------------------------------------------
# stateful shell — drives server_step eagerly, one round per call
# ---------------------------------------------------------------------------


class FedServer:
    """Thin stateful wrapper over ``server_step``: holds a ``ServerState``
    and swaps it for the step's output each round.  The caller owns model
    (un)flattening."""

    def __init__(self, config: ServerConfig):
        self.cfg = config
        self.state = init_server_state(
            config.num_clients, config.alpha0, config.beta0
        )

    # -- state views ---------------------------------------------------------
    @property
    def reputation(self) -> ReputationState:
        return self.state.reputation

    @property
    def blocked(self) -> np.ndarray:
        return np.asarray(self.state.reputation.blocked)

    @property
    def rounds_blocked(self) -> np.ndarray:
        return np.asarray(self.state.rounds_blocked)

    # -- selection ----------------------------------------------------------
    def select(self, rng: Optional[np.random.Generator] = None, frac: float = 1.0):
        """Per-round client selection among un-blocked clients."""
        avail = np.nonzero(~self.blocked)[0]
        if frac >= 1.0 or rng is None:
            return avail
        m = max(1, int(round(frac * len(avail))))
        return np.sort(rng.choice(avail, size=m, replace=False))

    # -- dispatch plumbing ---------------------------------------------------
    def participation_mask(self, selected: np.ndarray) -> np.ndarray:
        mask0 = np.zeros(self.cfg.num_clients, bool)
        mask0[selected] = True
        mask0 &= ~self.blocked
        return mask0

    def rule_options(self, mask0: np.ndarray) -> RuleOptions:
        return make_rule_options(self.cfg, int(mask0.sum()))

    def _apply(self, proposals, n_k, selected: np.ndarray, layout: str):
        mask0 = self.participation_mask(selected)
        self.state, res = server_step(
            self.state, proposals, n_k, jnp.asarray(mask0),
            rule=self.cfg.rule, opts=self.rule_options(mask0),
            delta_block=self.cfg.delta_block, layout=layout,
        )
        info = {
            "good_mask": np.asarray(res.good_mask),
            # empty participation round: the aggregate is a zero update and
            # the engine must keep the previous parameters
            "all_blocked": bool(np.asarray(res.all_blocked)),
        }
        if RULES[self.cfg.rule].updates_reputation:
            info.update(
                rounds=int(res.rounds),
                similarities=np.asarray(res.similarities),
                blocked=self.blocked.copy(),
                p_good=np.asarray(p_good(self.state.reputation)),
            )
        return res.aggregate, info

    # -- aggregation ---------------------------------------------------------
    def aggregate(self, updates: jnp.ndarray, n_k: jnp.ndarray, selected: np.ndarray):
        """updates: (K, d) with rows outside ``selected`` ignored.
        Returns (aggregate vector, info dict)."""
        return self._apply(updates, n_k, selected, "matrix")

    def aggregate_tree(self, stacked, n_k: jnp.ndarray, selected: np.ndarray):
        """Stacked-pytree layout: every leaf carries a leading client axis.
        Dispatches through the packed (K, D) path unless the config's
        resolved plan pins ``layout="leaf"``.  Returns (aggregate pytree,
        info dict)."""
        layout = (
            "leaf" if resolve_server_plan(self.cfg).layout == "leaf" else "tree"
        )
        return self._apply(stacked, n_k, selected, layout)
