"""Baseline aggregation rules the paper compares against (plus extras) and the
rule REGISTRY the server/engine dispatch through.

All rules share the matrix-form signature ``rule(updates, n_k, p_k, mask) ->
(K-masked aggregate vector, good_mask)`` so the simulator/server can swap them
freely.  ``n_k`` / ``p_k`` are ignored by rules that do not use them (MKRUM,
COMED, ... — the paper notes these disregard per-client data counts).

Implemented here:
  * FA            — Federated Averaging (McMahan et al. 2017)
  * MKRUM         — Multi-KRUM (Blanchard et al. 2017)
  * COMED         — coordinate-wise median (Yin et al. 2018)
  * TRIMMED_MEAN  — coordinate-wise trimmed mean (Yin et al. 2018)
  * BULYAN        — MKRUM selection + per-coordinate closest-to-median mean
                    (Mhamdi et al. 2018)
  * NORM_CLIP     — norm-clipped mean (beyond-paper defensive baseline)

Registry (DESIGN.md §3): every dispatchable rule registers a ``RuleSpec``
via ``register_rule``.  A spec carries a *matrix* form ``(updates (K,d), n_k,
p_k, mask, opts) -> result`` and optionally a native *tree* form over stacked
pytrees; ``dispatch_rule`` / ``dispatch_rule_tree`` are the single entry
points.  AFA and the extra rules register themselves on import
(``repro.core`` imports everything).

Tree dispatch is **packed** by default (DESIGN.md §3): the stacked proposal
pytree is packed ONCE into a contiguous ``(K, D)`` buffer
(``utils/trees.pack_stack`` with a cached ``PackSpec``), every rule —
including AFA, via its matrix form — runs on that one matrix, and the
aggregate vector unpacks ONCE back to the template tree.  All of it is pure
jnp reshapes inside jit, so the dispatch stays device-resident.  The legacy
``layout="leaf"`` route keeps the old per-leaf behavior (AFA's native
sharding-preserving tree form; per-leaf flatten for the rest) as the
reference the packed path is benchmarked against and as the layout for
sharded trees that must not be concatenated.

``use_kernels`` policy, uniform across ALL rules, resolved by
``repro.kernels.policy.resolve_kernel_mode`` into one of four modes:
``pallas`` (compiled kernels — TPU), ``pallas-gpu`` (compiled via the
Triton lowering), ``jnp`` (this file's reference path), ``interpret`` (the
same Pallas kernel bodies under the interpreter — any backend; the CI
kernel-parity route).  ``use_kernels=True`` consults ``$REPRO_KERNELS``
(auto -> pallas on TPU, jnp elsewhere; pallas-gpu is never auto-selected —
its single-block geometries only fit small operands); a mode string
pins the route.  Rules whose hot op has no kernel (geomed/centered-clip's
iterations) use the reference path under auto selection and raise on an
explicit kernel demand.  comed and trimmed-mean both route through masked
compare-count rank-selection kernels — mask-aware, so they engage under
jit-traced masks (tree dispatch included) with no host row-selection.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

EPS = 1e-12


class AggResult(NamedTuple):
    aggregate: jnp.ndarray
    good_mask: jnp.ndarray
    # True when the participation mask was empty: the aggregate is then a
    # zero *update* (dispatch zeroes it) and callers must keep the previous
    # model instead of adopting it (set by dispatch_rule / dispatch_rule_tree)
    all_blocked: jnp.ndarray | bool = False


def _kernel_mode(use_kernels: bool | str) -> str:
    """Resolved kernel mode for this call (see repro.kernels.policy)."""
    from repro.kernels.policy import resolve_kernel_mode

    return resolve_kernel_mode(use_kernels)


def _norm_weights(mask, w):
    c = jnp.where(mask, w, 0.0)
    return c / jnp.maximum(jnp.sum(c), EPS)


def _weighted_rows(c, u32):
    """(K,) @ (K, d) -> (d,) on the jnp reference path."""
    return jnp.matmul(c, u32, precision=jax.lax.Precision.HIGHEST).astype(jnp.float32)


def _weighted_rows_for(mode: str):
    """Weighted-sum route for a resolved kernel mode."""
    if mode == "jnp":
        return _weighted_rows
    from repro.kernels import weighted_sum

    return functools.partial(weighted_sum, interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("use_kernels",))
def fa_aggregate(updates, n_k, p_k=None, mask=None, *, use_kernels: bool | str = False) -> AggResult:
    K = updates.shape[0]
    mask = jnp.ones((K,), bool) if mask is None else mask
    c = _norm_weights(mask, n_k.astype(jnp.float32))
    u32 = updates.astype(jnp.float32)
    ws = _weighted_rows_for(_kernel_mode(use_kernels))
    return AggResult(ws(c, u32).astype(updates.dtype), mask)


def pairwise_sq_dists(updates, *, use_kernels: bool | str = False):
    """K×K squared euclidean distances via the Gram identity (one matmul)."""
    u = updates.astype(jnp.float32)
    mode = _kernel_mode(use_kernels)
    if mode != "jnp":
        from repro.kernels import gram as gram_kernel

        g = gram_kernel(u, interpret=(mode == "interpret"))
    else:
        g = jnp.matmul(u, u.T, precision=jax.lax.Precision.HIGHEST)
    sq = jnp.diag(g)
    d2 = sq[:, None] + sq[None, :] - 2.0 * g
    return jnp.maximum(d2, 0.0)


@functools.partial(
    jax.jit, static_argnames=("num_byzantine", "num_selected", "use_kernels")
)
def mkrum_aggregate(
    updates, n_k=None, p_k=None, mask=None, *, num_byzantine: int,
    num_selected: int, use_kernels: bool = False
) -> AggResult:
    """Multi-KRUM: score_k = sum of the K−f−2 smallest distances to others;
    average the ``num_selected`` lowest-scoring updates."""
    K = updates.shape[0]
    mask = jnp.ones((K,), bool) if mask is None else mask
    d2 = pairwise_sq_dists(updates, use_kernels=use_kernels)
    big = jnp.float32(3.4e38)
    # self-distance and masked-out rows/cols excluded from neighbour sets
    off = jnp.where(jnp.eye(K, dtype=bool) | ~mask[None, :], big, d2)
    n_neigh = jnp.maximum(jnp.sum(mask) - num_byzantine - 2, 1)
    srt = jnp.sort(off, axis=1)
    idx = jnp.arange(K)[None, :]
    scores = jnp.sum(jnp.where(idx < n_neigh, srt, 0.0), axis=1)
    scores = jnp.where(mask, scores, big)
    m = jnp.minimum(num_selected, jnp.sum(mask))
    order = jnp.argsort(scores)
    ranks = jnp.zeros((K,), jnp.int32).at[order].set(jnp.arange(K, dtype=jnp.int32))
    sel = (ranks < m) & mask
    c = _norm_weights(sel, jnp.ones((K,), jnp.float32))
    ws = _weighted_rows_for(_kernel_mode(use_kernels))
    return AggResult(ws(c, updates.astype(jnp.float32)).astype(updates.dtype), sel)


@functools.partial(jax.jit, static_argnames=("use_kernels",))
def comed_aggregate(updates, n_k=None, p_k=None, mask=None, *, use_kernels: bool = False) -> AggResult:
    """Coordinate-wise median across clients (masked rows pushed to ±inf in
    balanced pairs so they never shift the median).

    The Pallas compare-count kernel ranks each live row against the live
    subset only, so the kernel route is mask-aware — it engages for traced
    masks too (tree dispatch) with no host row-selection round-trip.
    """
    K, _ = updates.shape
    mode = _kernel_mode(use_kernels)
    if mode != "jnp":
        from repro.kernels import coord_median

        m = jnp.ones((K,), bool) if mask is None else mask
        med = coord_median(
            updates.astype(jnp.float32),
            None if mask is None else m,
            interpret=(mode == "interpret"),
        )
        return AggResult(med.astype(updates.dtype), m)
    mask = jnp.ones((K,), bool) if mask is None else mask
    u = updates.astype(jnp.float32)
    m = jnp.sum(mask)
    # Replace masked rows so half go to +inf, half to -inf -> median of the
    # live subset is preserved for any live count.
    dead_rank = jnp.cumsum(~mask) - 1  # rank among dead rows, valid where ~mask
    hi = (dead_rank % 2) == 0
    fill = jnp.where(hi, jnp.inf, -jnp.inf)[:, None]
    u = jnp.where(mask[:, None], u, fill)
    srt = jnp.sort(u, axis=0)
    n_dead_lo = jnp.sum(~mask) // 2
    lo_i = n_dead_lo + jnp.maximum((m - 1) // 2, 0)
    hi_i = n_dead_lo + jnp.maximum(m // 2, 0)
    med = 0.5 * (srt[lo_i] + srt[hi_i])
    return AggResult(med.astype(updates.dtype), mask)


@functools.partial(jax.jit, static_argnames=("trim", "use_kernels"))
def trimmed_mean_aggregate(
    updates, n_k=None, p_k=None, mask=None, *, trim: int, use_kernels: bool | str = False
) -> AggResult:
    """Coordinate-wise mean after dropping ``trim`` extremes from both ends.

    Kernel modes route through the masked compare-count rank-trim kernel
    (``kernels/trimmed_mean.py``) — the sort is replaced by ranking each live
    row against the live subset, which keeps exactly the values the sort
    would keep, so the result is value-identical up to f32 summation order.

    When the live count ``m <= 2 * trim`` the trim window is empty — the rule
    degrades to the masked coordinate-wise mean instead of silently returning
    a zero aggregate (which would reset the model mid-run once blocking
    shrinks participation below the window); the kernel mirrors this
    fallback."""
    K, _ = updates.shape
    mask = jnp.ones((K,), bool) if mask is None else mask
    mode = _kernel_mode(use_kernels)
    if mode != "jnp":
        from repro.kernels import trimmed_mean

        out = trimmed_mean(
            updates.astype(jnp.float32), mask, trim=trim,
            interpret=(mode == "interpret"),
        )
        return AggResult(out.astype(updates.dtype), mask)
    u32 = updates.astype(jnp.float32)
    srt = jnp.sort(jnp.where(mask[:, None], u32, jnp.inf), axis=0)
    m = jnp.sum(mask)
    i = jnp.arange(K)[:, None]
    live = (i >= trim) & (i < m - trim)
    cnt = jnp.maximum(jnp.sum(live), 1)
    trimmed = jnp.sum(jnp.where(live, srt, 0.0), axis=0) / cnt
    w = mask.astype(jnp.float32)[:, None]
    masked_mean = jnp.sum(u32 * w, axis=0) / jnp.maximum(jnp.sum(w), 1.0)
    mean = jnp.where(m > 2 * trim, trimmed, masked_mean)
    return AggResult(mean.astype(updates.dtype), mask)


@functools.partial(jax.jit, static_argnames=("num_byzantine", "use_kernels"))
def bulyan_aggregate(
    updates, n_k=None, p_k=None, mask=None, *, num_byzantine: int,
    use_kernels: bool = False
) -> AggResult:
    """Bulyan: MKRUM-style selection of theta = K−2f updates, then per
    coordinate average the beta = theta−2f values closest to the median."""
    K, d = updates.shape
    mask = jnp.ones((K,), bool) if mask is None else mask
    theta = max(K - 2 * num_byzantine, 1)
    sel = mkrum_aggregate(
        updates, mask=mask, num_byzantine=num_byzantine, num_selected=theta,
        use_kernels=use_kernels,
    ).good_mask
    med = comed_aggregate(
        updates, mask=sel, use_kernels=use_kernels
    ).aggregate.astype(jnp.float32)
    dist = jnp.where(sel[:, None], jnp.abs(updates.astype(jnp.float32) - med[None]), jnp.inf)
    beta = max(theta - 2 * num_byzantine, 1)
    order = jnp.argsort(dist, axis=0)
    ranks = jnp.zeros((K, d), jnp.int32)
    ranks = ranks.at[order, jnp.arange(d)[None, :]].set(
        jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[:, None], (K, d))
    )
    use = ranks < beta
    val = jnp.where(use, updates.astype(jnp.float32), 0.0)
    out = jnp.sum(val, axis=0) / beta
    return AggResult(out.astype(updates.dtype), sel)


@functools.partial(jax.jit, static_argnames=("use_kernels",))
def norm_clip_aggregate(
    updates, n_k, p_k=None, mask=None, clip=None, *, use_kernels: bool = False
) -> AggResult:
    """Clip each update to the masked-median norm, then weighted-average."""
    K = updates.shape[0]
    mask = jnp.ones((K,), bool) if mask is None else mask
    u = updates.astype(jnp.float32)
    norms = jnp.linalg.norm(u, axis=1)
    from repro.core.stats import masked_median

    c = masked_median(norms, mask) if clip is None else clip
    scale = jnp.minimum(1.0, c / jnp.maximum(norms, EPS))
    u = u * scale[:, None]
    w = _norm_weights(mask, n_k.astype(jnp.float32))
    ws = _weighted_rows_for(_kernel_mode(use_kernels))
    return AggResult(ws(w, u).astype(updates.dtype), mask)


# ---------------------------------------------------------------------------
# rule registry — single dispatch interface for server and round engine
# ---------------------------------------------------------------------------


class RuleOptions(NamedTuple):
    """Per-call rule knobs, hashable so the whole bundle can ride through jit
    as a static argument.  ``afa`` holds an ``AFAConfig`` when rule == afa;
    ``num_selected`` (MKRUM) must be host-computed from the concrete
    participation count (it is a static shape-like parameter).

    ``use_kernels`` may be a bool (auto selection via ``$REPRO_KERNELS``) or
    a pinned mode string ``"pallas"``/``"pallas-gpu"``/``"jnp"``/
    ``"interpret"``; resolve on the host (``make_rule_options`` does) so the
    resolved mode — not the ambient env var — keys the jit cache."""

    num_byzantine: int = 3
    trim: int = 3
    num_selected: int | None = None
    use_kernels: bool | str = False
    afa: Any = None  # AFAConfig | None (typed Any to avoid an import cycle)


class RuleSpec(NamedTuple):
    name: str
    matrix_fn: Callable  # (updates, n_k, p_k, mask, opts) -> result
    tree_fn: Callable | None = None  # (stacked_tree, n_k, p_k, mask, opts) -> result
    updates_reputation: bool = False  # AFA: result drives the Beta posterior


RULES: dict[str, RuleSpec] = {}


def register_rule(
    name: str,
    matrix_fn: Callable,
    tree_fn: Callable | None = None,
    *,
    updates_reputation: bool = False,
) -> RuleSpec:
    spec = RuleSpec(name, matrix_fn, tree_fn, updates_reputation)
    RULES[name] = spec
    return spec


def _opts_client_axis(opts: RuleOptions) -> str | None:
    """The shard_map client axis the options request, or None.

    Reads ``opts.afa`` (an AFAConfig) without importing it: the axis only
    matters when the config both names one and spans more than one shard —
    a one-shard client mesh runs the unsharded code verbatim."""
    cfg = opts.afa
    axis = getattr(cfg, "client_axis", None) if cfg is not None else None
    shards = getattr(cfg, "client_shards", 0) if cfg is not None else 0
    return axis if (axis is not None and shards > 1) else None


def _guard_all_blocked(res, mask, client_axis: str | None = None):
    """Post-dispatch guard for the empty-participation round.

    When every client is masked out (e.g. AFA eventually blocks the whole
    cohort under a majority attack) the rules' internal weight normalizations
    divide by their EPS floor and emit an all-zero weight vector — FA/AFA
    would silently return a zero aggregate (resetting the model), comed's
    ±inf fills would surface as the aggregate.  The dispatch layer instead
    returns an explicit zero *update* plus an ``all_blocked`` flag; engines
    keep the previous parameters when the flag is set.  When any client is
    live the ``where`` is the identity, bit for bit.

    Under client sharding ``mask`` is the SHARD-LOCAL participation block, so
    the emptiness test reduces over the client axis: a shard whose local
    cohort is fully blocked must NOT zero its (replicated) copy of the
    aggregate while other shards keep theirs — that would desynchronize the
    model across shards.
    """
    if mask is None:
        return res._replace(all_blocked=jnp.bool_(False))
    any_live = jnp.any(mask)
    if client_axis is not None:
        any_live = jax.lax.psum(any_live.astype(jnp.int32), client_axis) > 0
    all_blocked = ~any_live
    aggregate = jax.tree_util.tree_map(
        lambda l: jnp.where(all_blocked, jnp.zeros_like(l), l), res.aggregate
    )
    return res._replace(aggregate=aggregate, all_blocked=all_blocked)


def dispatch_rule(name: str, updates, n_k, p_k=None, mask=None,
                  opts: RuleOptions = RuleOptions()):
    """Matrix-form dispatch: updates is (K, d).  Returns the rule's native
    result (``.aggregate`` vector + ``.good_mask`` + ``.all_blocked``, AFA
    adds extras).  With a client axis in ``opts.afa`` (the sharded fused
    engine), ``updates`` is the shard-local row block and only AFA — whose
    hierarchical two-stage form exists — may dispatch."""
    try:
        spec = RULES[name]
    except KeyError:
        raise ValueError(f"unknown rule {name!r}; registered: {sorted(RULES)}")
    client_axis = _opts_client_axis(opts)
    if client_axis is not None and name != "afa":
        raise ValueError(
            f"rule {name!r} has no client-sharded form; only 'afa' runs "
            "hierarchically over a client mesh axis"
        )
    return _guard_all_blocked(
        spec.matrix_fn(updates, n_k, p_k, mask, opts), mask, client_axis
    )


TREE_LAYOUTS = ("packed", "leaf")


def dispatch_rule_tree(name: str, stacked, n_k, p_k=None, mask=None,
                       opts: RuleOptions = RuleOptions(), *,
                       layout: str = "packed"):
    """Tree-form dispatch: stacked is a pytree with a leading client axis on
    every leaf.

    ``layout="packed"`` (default, DESIGN.md §3): the tree is packed ONCE into
    a contiguous ``(K, D)`` buffer (cached ``PackSpec``), the rule's matrix
    form — AFA's included — runs on that one matrix, and the aggregate vector
    unpacks ONCE back to the template structure.  All pure jnp reshapes
    inside jit: device-resident, no host round-trip, and bit-identical to
    calling ``dispatch_rule`` on ``pack_stack(stacked)`` directly.

    ``layout="leaf"``: the legacy per-leaf path — AFA's native
    sharding-preserving tree form, per-leaf flatten for matrix-only rules.
    Kept as the reference the packed path is benchmarked against
    (``benchmarks/fused_engine.py`` "packed" scenario) and for sharded trees
    whose leaves must not be concatenated.

    The whole dispatch is jit'd with (name, opts, layout) static, so
    per-round host overhead is one cached call."""
    if name not in RULES:
        raise ValueError(f"unknown rule {name!r}; registered: {sorted(RULES)}")
    if layout not in TREE_LAYOUTS:
        raise ValueError(f"unknown layout {layout!r}; expected {TREE_LAYOUTS}")
    return _dispatch_tree_jit(stacked, n_k, p_k, mask, name=name, opts=opts,
                              layout=layout)


@functools.partial(jax.jit, static_argnames=("name", "opts", "layout"))
def _dispatch_tree_jit(stacked, n_k, p_k, mask, *, name: str,
                       opts: RuleOptions, layout: str = "packed"):
    spec = RULES[name]
    if _opts_client_axis(opts) is not None:
        raise ValueError(
            "tree dispatch has no client-sharded form; the sharded engine "
            "packs once and calls dispatch_rule on the local (K_local, D) "
            "block"
        )
    if layout == "leaf" and spec.tree_fn is not None:
        return _guard_all_blocked(spec.tree_fn(stacked, n_k, p_k, mask, opts), mask)
    if layout == "leaf":
        from repro.utils.trees import flatten_to_matrix, unflatten_from_vector

        leaves = jax.tree_util.tree_leaves(stacked)
        K = leaves[0].shape[0]
        res = spec.matrix_fn(flatten_to_matrix(stacked, K), n_k, p_k, mask, opts)
        template = jax.tree_util.tree_map(lambda l: l[0], stacked)
        res = res._replace(aggregate=unflatten_from_vector(res.aggregate, template))
        return _guard_all_blocked(res, mask)

    from repro.utils.trees import pack_spec, pack_stack, unpack_stack

    pspec = pack_spec(stacked, stacked=True)
    res = spec.matrix_fn(pack_stack(stacked, pspec), n_k, p_k, mask, opts)
    res = res._replace(aggregate=unpack_stack(res.aggregate, pspec))
    return _guard_all_blocked(res, mask)


def _mkrum_rule(u, n_k, p_k, mask, o: RuleOptions):
    m_sel = o.num_selected
    if m_sel is None:  # static fallback: assume full participation
        m_sel = max(u.shape[0] - o.num_byzantine - 2, 1)
    return mkrum_aggregate(
        u, mask=mask, num_byzantine=o.num_byzantine, num_selected=m_sel,
        use_kernels=o.use_kernels,
    )


def _comed_rule(u, n_k, p_k, mask, o: RuleOptions):
    # the kernel is mask-aware (rank among live rows), so one route covers
    # concrete and traced masks alike — no host row-selection special case
    return comed_aggregate(u, mask=mask, use_kernels=o.use_kernels)


register_rule(
    "fa", lambda u, n, p, m, o: fa_aggregate(u, n, mask=m, use_kernels=o.use_kernels)
)
register_rule("mkrum", _mkrum_rule)
register_rule("comed", _comed_rule)
register_rule(
    "trimmed_mean",
    lambda u, n, p, m, o: trimmed_mean_aggregate(
        u, mask=m, trim=o.trim, use_kernels=o.use_kernels
    ),
)
register_rule(
    "bulyan",
    lambda u, n, p, m, o: bulyan_aggregate(
        u, mask=m, num_byzantine=o.num_byzantine, use_kernels=o.use_kernels
    ),
)
register_rule(
    "norm_clip",
    lambda u, n, p, m, o: norm_clip_aggregate(u, n, mask=m, use_kernels=o.use_kernels),
)
