"""Adaptive Federated Averaging — the paper's Algorithm 1, in JAX.

Two executable forms:

* **matrix form** (``afa_aggregate``): updates as a dense ``(K, d)`` matrix.
  Used by the paper-scale simulator, the kernels, the benchmarks — and the
  default *packed* tree dispatch (DESIGN.md §3), which packs the stacked
  proposal pytree into one contiguous ``(K, D)`` buffer and runs this form
  on it.
* **tree form** (``afa_aggregate_tree``): updates as a pytree with a leading
  client axis on every leaf.  Sharding-preserving — under pjit the per-leaf
  contractions lower to partial dots + psum over the *model* mesh axis and the
  weighted sum to a weighted psum over *data*; the while-loop state is K
  scalars, replicated.  The distributed path and the legacy ``layout="leaf"``
  dispatch use this form.

Two algorithmic variants (both forms):

* ``variant="iterative"`` — paper-faithful: every while iteration recomputes
  the aggregate and re-touches the full update set, O(rounds · K · d).
* ``variant="gram"`` — beyond-paper: precompute the K×K Gram matrix of the
  updates once (one O(K²d) MXU pass), after which every while iteration is
  O(K²) on scalars:   ⟨w_agg, u_k⟩ = (G c)_k,  ‖w_agg‖² = cᵀGc,
  ‖u_k‖² = diag(G).  The full update set is touched exactly twice (Gram +
  final weighted sum) regardless of how many outlier-removal rounds run.
  Under a kernel mode this variant defaults to the FUSED screening kernel
  (``kernels/afa_screen.py``): the whole algorithm — Gram, VMEM-resident
  screening loop, final weighted sum — is ONE Pallas launch
  (``AFAConfig.kernel_launch="fused"``; ``"chained"`` keeps the per-op
  kernel launches as the benchmark baseline).

Direction convention follows the paper's algorithm box (not the prose, which
has a sign typo): when mean ≥ median the *high*-similarity tail is removed
(``s_k > median + ξσ`` — colluding/huge-norm clients drag the aggregate toward
themselves, saturating their own similarity), otherwise the low tail
(``s_k < median − ξσ``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.stats import masked_mean, masked_median, masked_std
from repro.kernels.policy import resolve_kernel_mode
from repro.utils.trees import tree_dot

# every aggregation contraction runs at full f32: on the TPU the default
# precision rounds f32 matmul operands to bf16 (the kernels match this)
HIGHEST = jax.lax.Precision.HIGHEST

EPS = 1e-12
# similarities closer to the median than this share of it are ties: the
# spread the tail test scales ``xi`` by is floored at SIM_TIE_RTOL * |median|,
# so rows that differ only by f32 rounding in the cosine are never cut
SIM_TIE_RTOL = 2.0**-20

# Lazy module-level accessor for the kernel ops (satisfies the one-time
# import contract: resolve_kernel_mode is imported at module scope above —
# policy has no core dependency — while the kernel package itself, which
# pulls in every Pallas module, loads once on first kernel-mode use instead
# of per call site).
_KERNEL_OPS = None


def _kernel_ops():
    global _KERNEL_OPS
    if _KERNEL_OPS is None:
        from repro import kernels

        _KERNEL_OPS = kernels
    return _KERNEL_OPS


class AFAConfig(NamedTuple):
    xi0: float = 2.0
    delta_xi: float = 0.5
    max_rounds: int = 8       # fixed upper bound for lax.while_loop safety
    ddof: int = 0
    variant: str = "iterative"  # "iterative" | "gram"
    # Route the hot ops through the Pallas kernels: bool for auto selection
    # via $REPRO_KERNELS (pallas on TPU, jnp elsewhere — pallas-gpu is an
    # explicit opt-in, see repro.kernels.policy) or a pinned mode string
    # "pallas" / "pallas-gpu" / "jnp" / "interpret".  Matrix form only — the tree form is already
    # XLA-fused.  With variant="gram" a kernel mode selects the FUSED
    # screening kernel by default (kernel_launch="fused"): Algorithm 1 runs
    # as ONE Pallas launch — gram, VMEM-resident screening loop, and final
    # weighted sum — emitting (aggregate, good_mask, rounds, similarities)
    # without relaunches or HBM re-reads of the (K, d) operand; under
    # interpret it is bit-identical (f32) to the jnp gram reference.
    use_kernels: bool | str = False
    # "fused" (one afa_screen launch, gram variant only) | "chained" (the
    # PR-4 route: separate gram / weighted-sum kernel launches around an
    # XLA-composed while loop — kept as the benchmark baseline the fused
    # launch is gated against).  afa_aggregate validates the value: anything
    # else raises ValueError rather than silently taking the chained route.
    kernel_launch: str = "fused"
    # Hierarchical two-stage screening over a mesh client axis (DESIGN.md
    # §4).  When ``client_axis`` names a shard_map axis and ``client_shards``
    # > 1, ``afa_aggregate`` treats its inputs as the SHARD-LOCAL row block
    # (K_local = K / client_shards rows) and runs Algorithm 1 with exactly
    # two collective shapes per screening iteration: one ``psum`` of the
    # (d,) partial weighted aggregate and one tiled ``all_gather`` of the
    # K_local similarity scalars (O(K) scalars round-trip total); the
    # screening stats compute on shard 0 and broadcast as a 3-scalar psum,
    # with only the elementwise mask update replicated.  The final
    # reputation-weighted aggregate is one more weighted (d,) ``psum``.  The
    # full (K, d) matrix is never gathered.  With ``client_shards <= 1`` the
    # config falls through to the unsharded code path verbatim, so a
    # one-shard client mesh is bit-identical to today's single-device route
    # by construction (mega-kernel included).  Both fields are static and
    # key the jit cache.
    client_axis: str | None = None
    client_shards: int = 0


class AFAResult(NamedTuple):
    aggregate: jnp.ndarray | dict  # (d,) vector or pytree
    good_mask: jnp.ndarray         # (K,) bool — True = kept
    rounds: jnp.ndarray            # scalar int — outlier-removal rounds run
    similarities: jnp.ndarray      # (K,) final-round cosine similarities
    # set by dispatch_rule / dispatch_rule_tree: True when the participation
    # mask was empty, in which case the aggregate is a zero update and the
    # caller must keep the previous parameters
    all_blocked: jnp.ndarray | bool = False


def _weights(mask, p, n):
    c = jnp.where(mask, p * n, 0.0)
    return c / jnp.maximum(jnp.sum(c), EPS)


def _mark_bad(s, mask, xi, ddof):
    """One Algorithm-1 screening pass: returns the newly-bad mask."""
    mu_hat = masked_mean(s, mask)
    mu_bar = masked_median(s, mask)
    sigma = jnp.maximum(masked_std(s, mask, ddof=ddof), SIM_TIE_RTOL * jnp.abs(mu_bar))
    low_tail = mask & (s < mu_bar - xi * sigma)
    high_tail = mask & (s > mu_bar + xi * sigma)
    bad = jnp.where(mu_hat < mu_bar, low_tail, high_tail)
    # never remove below 2 survivors — the similarity stats stop being defined
    keep_floor = jnp.sum(mask & ~bad) >= 2
    return jnp.where(keep_floor, bad, jnp.zeros_like(bad))


# ---------------------------------------------------------------------------
# matrix form
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("config",))
def afa_aggregate(
    updates: jnp.ndarray,  # (K, d)
    n_k: jnp.ndarray,      # (K,) data-point counts
    p_k: jnp.ndarray,      # (K,) reputation means
    mask0: jnp.ndarray | None = None,  # (K,) initial participation
    config: AFAConfig = AFAConfig(),
) -> AFAResult:
    if config.kernel_launch not in ("fused", "chained"):
        raise ValueError(
            f"AFAConfig.kernel_launch={config.kernel_launch!r} invalid; "
            "expected 'fused' or 'chained'"
        )
    if config.variant not in ("iterative", "gram"):
        raise ValueError(
            f"AFAConfig.variant={config.variant!r} invalid; "
            "expected 'iterative' or 'gram'"
        )
    K = updates.shape[0]
    mask0 = jnp.ones((K,), bool) if mask0 is None else mask0
    upd32 = updates.astype(jnp.float32)
    mode = resolve_kernel_mode(config.use_kernels)
    interp = mode == "interpret"

    if config.client_axis is not None and config.client_shards > 1:
        # hierarchical two-stage screening: inputs are the shard-local row
        # block inside a shard_map over config.client_axis
        if config.variant != "iterative":
            raise ValueError(
                "sharded AFA implements the iterative variant only: the "
                "gram variant needs O(K_local * K) gram rows per shard, "
                "which defeats the client sharding; set variant='iterative' "
                f"(got {config.variant!r})"
            )
        return _afa_aggregate_sharded(
            updates, upd32, n_k, p_k, mask0, config, mode, interp
        )

    if config.variant == "gram" and mode != "jnp" and config.kernel_launch == "fused":
        # the fused route: Algorithm 1 as ONE Pallas launch (gram +
        # VMEM-resident screening loop + weighted sum, see kernels/afa_screen)
        agg, good, rounds, sims = _kernel_ops().afa_screen(
            upd32,
            p_k.astype(jnp.float32) * n_k.astype(jnp.float32),
            mask0,
            xi0=config.xi0, delta_xi=config.delta_xi,
            max_rounds=config.max_rounds, ddof=config.ddof,
            interpret=interp,
        )
        return AFAResult(
            aggregate=agg.astype(updates.dtype), good_mask=good,
            rounds=rounds, similarities=sims,
        )

    row_norms = jnp.linalg.norm(upd32, axis=1)

    if config.variant == "gram":
        if mode != "jnp":
            gram = _kernel_ops().gram(upd32, interpret=interp)
        else:
            gram = jnp.matmul(upd32, upd32.T, precision=HIGHEST)  # (K, K)

        def sims(c):
            # row-vector form of G c and c.Gc: the op sequence the fused
            # kernel must use on Mosaic (no 1-D matvec there), kept in
            # lockstep with kernels/afa_screen._screen
            gc = jax.lax.dot_general(
                c[None, :], gram, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=HIGHEST,
            )[0]
            agg_norm = jnp.sqrt(jnp.maximum(jnp.sum(c * gc), EPS))
            return gc / (jnp.maximum(row_norms, EPS) * agg_norm)

    elif mode != "jnp":

        def sims(c):
            k = _kernel_ops()
            return k.cosine_sim(upd32, k.weighted_sum(c, upd32, interpret=interp),
                                interpret=interp)

    else:

        def sims(c):
            agg = jnp.matmul(c, upd32, precision=HIGHEST)  # (d,)
            agg_norm = jnp.linalg.norm(agg)
            return jnp.matmul(upd32, agg, precision=HIGHEST) / (
                jnp.maximum(row_norms, EPS) * jnp.maximum(agg_norm, EPS)
            )

    def cond(state):
        mask, xi, changed, rounds, _ = state
        return changed & (rounds < config.max_rounds)

    def body(state):
        mask, xi, _, rounds, _ = state
        s = sims(_weights(mask, p_k, n_k))
        bad = _mark_bad(s, mask, xi, config.ddof)
        return (mask & ~bad, xi + config.delta_xi, jnp.any(bad), rounds + 1, s)

    # round-0 similarities, NOT zeros, when max_rounds=0: the loop never runs
    # and downstream reputation updates would otherwise see all-zero
    # similarities.  With max_rounds >= 1 the first body iteration computes
    # the identical sims and overwrites s, so the zeros initializer is used
    # there to avoid a redundant O(K d) pass (max_rounds is jit-static).
    s0 = (
        sims(_weights(mask0, p_k, n_k)) if config.max_rounds == 0
        else jnp.zeros((K,), jnp.float32)
    )
    mask, xi, _, rounds, s = jax.lax.while_loop(
        cond, body, (mask0, jnp.float32(config.xi0), jnp.bool_(True), jnp.int32(0), s0)
    )
    w = _weights(mask, p_k, n_k)
    if mode != "jnp":
        agg = _kernel_ops().weighted_sum(w, upd32, interpret=interp).astype(updates.dtype)
    else:
        agg = jnp.matmul(w, upd32, precision=HIGHEST).astype(updates.dtype)
    return AFAResult(aggregate=agg, good_mask=mask, rounds=rounds, similarities=s)


def _afa_aggregate_sharded(updates, upd32, n_k, p_k, mask0, config, mode, interp):
    """Algorithm 1 across a shard_map client axis (matrix form, iterative).

    All inputs carry the SHARD-LOCAL leading axis (K_local rows).  The
    screening state — participation mask, p·n weights, similarities — is K
    replicated scalars: stage 1 computes shard-local statistics (row norms,
    the partial weighted aggregate, the local similarity dots), stage 2
    reduces them with one (d,) ``psum`` + one tiled O(K)-scalar
    ``all_gather`` per iteration and updates the mask replicated, identical
    on every shard.  The O(K log K) screening statistics (the masked
    mean/median/std need a sort of the gathered similarities) run on shard
    0 ONLY and broadcast as a 3-scalar ``psum`` — the other shards
    contribute exact zeros, so the summed stats are bitwise the shard-0
    values; only the elementwise tail test replicates.
    ``good_mask``/``similarities`` return SHARD-LOCAL
    (the engine's trajectory stitches them back to (K,) via out_specs);
    the aggregate returns replicated.

    Under a kernel mode the per-iteration contractions run the PR-4 kernel
    family per shard on the local row block (``weighted_sum`` for the
    partial aggregate, ``cosine_sim`` against the replicated aggregate);
    the PR-6 mega-kernel stays the single-shard fast path — its VMEM
    screening loop is inherently whole-cohort, and with ``client_shards <=
    1`` the dispatch above falls through to it unchanged.
    """
    axis = config.client_axis
    K_local = upd32.shape[0]
    K = K_local * config.client_shards
    i = jax.lax.axis_index(axis)

    row_norms_l = jnp.linalg.norm(upd32, axis=1)
    n_g = jax.lax.all_gather(n_k.astype(jnp.float32), axis, tiled=True)
    p_g = jax.lax.all_gather(p_k.astype(jnp.float32), axis, tiled=True)
    mask0_g = jax.lax.all_gather(mask0, axis, tiled=True)

    def _local(vec):
        return jax.lax.dynamic_slice_in_dim(vec, i * K_local, K_local)

    if mode != "jnp":

        def sims(c):
            k = _kernel_ops()
            w_agg = jax.lax.psum(
                k.weighted_sum(_local(c), upd32, interpret=interp), axis
            )
            s_l = k.cosine_sim(upd32, w_agg, interpret=interp)
            return jax.lax.all_gather(s_l, axis, tiled=True)

    else:

        def sims(c):
            w_agg = jax.lax.psum(
                jnp.matmul(_local(c), upd32, precision=HIGHEST), axis
            )  # (d,)
            agg_norm = jnp.linalg.norm(w_agg)
            s_l = jnp.matmul(upd32, w_agg, precision=HIGHEST) / (
                jnp.maximum(row_norms_l, EPS) * jnp.maximum(agg_norm, EPS)
            )
            return jax.lax.all_gather(s_l, axis, tiled=True)

    def mark_bad_from_shard0(s, mask, xi):
        # _mark_bad's tail test with the O(K log K) stats hoisted to shard 0:
        # mean/median/std of the gathered (K,) similarities need a sort, and
        # repeating that sort on every shard is pure waste (on emulated host
        # devices it serializes x shards; on real chips it burns a core per
        # chip for a scalar triple).  lax.cond runs only the taken branch and
        # neither branch holds a collective, so the psum broadcast is safe —
        # and exact: the other shards contribute 0.0, leaving the summed
        # stats bitwise the shard-0 values.
        def compute(_):
            return jnp.stack([
                masked_mean(s, mask),
                masked_median(s, mask),
                masked_std(s, mask, ddof=config.ddof),
            ])
        stats = jax.lax.psum(
            jax.lax.cond(i == 0, compute,
                         lambda _: jnp.zeros((3,), jnp.float32), None),
            axis,
        )
        mu_hat, mu_bar = stats[0], stats[1]
        sigma = jnp.maximum(stats[2], SIM_TIE_RTOL * jnp.abs(mu_bar))
        low_tail = mask & (s < mu_bar - xi * sigma)
        high_tail = mask & (s > mu_bar + xi * sigma)
        bad = jnp.where(mu_hat < mu_bar, low_tail, high_tail)
        keep_floor = jnp.sum(mask & ~bad) >= 2
        return jnp.where(keep_floor, bad, jnp.zeros_like(bad))

    def cond(state):
        mask, xi, changed, rounds, _ = state
        return changed & (rounds < config.max_rounds)

    def body(state):
        mask, xi, _, rounds, _ = state
        s = sims(_weights(mask, p_g, n_g))
        bad = mark_bad_from_shard0(s, mask, xi)
        return (mask & ~bad, xi + config.delta_xi, jnp.any(bad), rounds + 1, s)

    s0 = (
        sims(_weights(mask0_g, p_g, n_g)) if config.max_rounds == 0
        else jnp.zeros((K,), jnp.float32)
    )
    mask, xi, _, rounds, s = jax.lax.while_loop(
        cond, body,
        (mask0_g, jnp.float32(config.xi0), jnp.bool_(True), jnp.int32(0), s0),
    )
    w_l = _local(_weights(mask, p_g, n_g))
    if mode != "jnp":
        part = _kernel_ops().weighted_sum(w_l, upd32, interpret=interp)
    else:
        part = jnp.matmul(w_l, upd32, precision=HIGHEST)
    agg = jax.lax.psum(part, axis).astype(updates.dtype)
    return AFAResult(
        aggregate=agg, good_mask=_local(mask), rounds=rounds,
        similarities=_local(s),
    )


# ---------------------------------------------------------------------------
# tree form
# ---------------------------------------------------------------------------


def _stacked_weighted_sum(stacked, c):
    """sum_k c_k * u_k over the leading client axis, leafwise."""
    def leaf(l):
        cb = c.reshape((-1,) + (1,) * (l.ndim - 1)).astype(jnp.float32)
        return jnp.sum(cb * l.astype(jnp.float32), axis=0).astype(l.dtype)

    return jax.tree_util.tree_map(leaf, stacked)


def _stacked_dot_with(stacked, vec_tree):
    """(K,) vector of ⟨u_k, v⟩, leafwise-accumulated."""
    tot = None
    for l, v in zip(jax.tree_util.tree_leaves(stacked), jax.tree_util.tree_leaves(vec_tree)):
        part = jnp.sum(
            l.astype(jnp.float32) * v.astype(jnp.float32)[None],
            axis=tuple(range(1, l.ndim)),
        )
        tot = part if tot is None else tot + part
    return tot


def _stacked_gram(stacked):
    """K×K Gram matrix, leafwise-accumulated (lowers to matmul + psum).

    No astype before the dot: ``preferred_element_type`` accumulates in f32
    without materializing an f32 copy of the (K, N) proposals."""
    tot = None
    for l in jax.tree_util.tree_leaves(stacked):
        f = l.reshape(l.shape[0], -1)
        part = jax.lax.dot_general(
            f, f, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=HIGHEST,
        )
        tot = part if tot is None else tot + part
    return tot


def afa_aggregate_tree(
    stacked_updates,           # pytree, every leaf (K, ...)
    n_k: jnp.ndarray,
    p_k: jnp.ndarray,
    mask0: jnp.ndarray | None = None,
    config: AFAConfig = AFAConfig(),
) -> AFAResult:
    if config.variant not in ("iterative", "gram"):
        raise ValueError(
            f"AFAConfig.variant={config.variant!r} invalid; "
            "expected 'iterative' or 'gram'"
        )
    leaves = jax.tree_util.tree_leaves(stacked_updates)
    K = leaves[0].shape[0]
    mask0 = jnp.ones((K,), bool) if mask0 is None else mask0
    row_norms = jnp.sqrt(
        jnp.maximum(tree_dot(stacked_updates, stacked_updates, axes=1), EPS)
    )

    if config.variant == "gram":
        gram = _stacked_gram(stacked_updates)

        def sims(c):
            gc = jnp.matmul(gram, c, precision=HIGHEST)
            agg_norm = jnp.sqrt(jnp.maximum(jnp.matmul(c, gc, precision=HIGHEST), EPS))
            return gc / (row_norms * agg_norm)

    else:

        def sims(c):
            agg = _stacked_weighted_sum(stacked_updates, c)
            dots = _stacked_dot_with(stacked_updates, agg)
            agg_norm = jnp.sqrt(jnp.maximum(tree_dot(agg, agg), EPS))
            return dots / (row_norms * agg_norm)

    def cond(state):
        mask, xi, changed, rounds, _ = state
        return changed & (rounds < config.max_rounds)

    def body(state):
        mask, xi, _, rounds, _ = state
        s = sims(_weights(mask, p_k, n_k))
        bad = _mark_bad(s, mask, xi, config.ddof)
        return (mask & ~bad, xi + config.delta_xi, jnp.any(bad), rounds + 1, s)

    # round-0 similarities (see the matrix form): never all-zero at max_rounds=0
    s0 = (
        sims(_weights(mask0, p_k, n_k)) if config.max_rounds == 0
        else jnp.zeros((K,), jnp.float32)
    )
    mask, xi, _, rounds, s = jax.lax.while_loop(
        cond, body, (mask0, jnp.float32(config.xi0), jnp.bool_(True), jnp.int32(0), s0)
    )
    agg = _stacked_weighted_sum(stacked_updates, _weights(mask, p_k, n_k))
    return AFAResult(aggregate=agg, good_mask=mask, rounds=rounds, similarities=s)


# ---------------------------------------------------------------------------
# registry hookup — AFA dispatches matrix AND native tree form (DESIGN.md §3)
# ---------------------------------------------------------------------------


def _default_p(p_k, K):
    return jnp.full((K,), 0.5, jnp.float32) if p_k is None else p_k


def _afa_matrix_rule(updates, n_k, p_k, mask, opts):
    cfg = opts.afa if opts.afa is not None else AFAConfig(use_kernels=opts.use_kernels)
    return afa_aggregate(
        updates, n_k, _default_p(p_k, updates.shape[0]), mask0=mask, config=cfg
    )


def _afa_tree_rule(stacked, n_k, p_k, mask, opts):
    cfg = opts.afa if opts.afa is not None else AFAConfig()
    K = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    return afa_aggregate_tree(
        stacked, n_k, _default_p(p_k, K), mask0=mask, config=cfg
    )


from repro.core.baselines import register_rule  # noqa: E402  (no cycle: baselines does not import afa)

register_rule("afa", _afa_matrix_rule, _afa_tree_rule, updates_reputation=True)
