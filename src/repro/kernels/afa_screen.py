"""Fused AFA screening mega-kernel: Algorithm 1 in ONE Pallas launch.

The chained kernel route (PR 4) runs AFA's gram variant as a sequence of
launches — gram kernel, host-composed while-loop on scalars, weighted-sum
kernel — bouncing control back to XLA between each.  This kernel fuses the
*entire* screening loop into a single ``pallas_call``:

1. **Gram pass** — accumulate ``G = U U^T`` (and the row norms ``|u_k|^2``)
   from ``(K, BLOCK_D)`` tiles of the packed update matrix, exactly the
   K-resident layout of ``kernels/gram.py``.
2. **Screening** — with ``G`` VMEM-resident, run the full
   ``lax.while_loop`` of Algorithm 1 on-chip: weights from the masked
   reputation vector, cosine similarities via ``G c`` (O(K²), no HBM), the
   masked mean / median / std tail test, mask update, up to ``max_rounds``
   repetitions.  The ``(K, D)`` operand is never re-read.
3. **Aggregate pass** — stream the update tiles once more for the final
   reputation-weighted sum ``w @ U``.

and emits ``(aggregate, good_mask, rounds, similarities)`` from the one
launch.

Two launch geometries, selected by ``ops.afa_screen``:

* **one-pass** (``block_d=None``): the whole ``(K, D)`` operand is a single
  resident tile; gram, screening, and aggregate all happen in one grid step.
  This is the geometry for the interpret route (no tiling constraints → the
  kernel runs on the EXACT unpadded shapes and is BIT-identical (f32) to
  ``afa_aggregate(variant="gram", use_kernels=False)`` — asserted by the
  parity suite) and for ``pallas-gpu`` (no cross-step accumulation, so the
  parallel CUDA grid is safe — but the whole operand becomes one resident
  block, so ``ops.afa_screen`` gates that route on ``GPU_ONEPASS_BUDGET``
  and raises for operands that cannot be block-resident).
* **two-pass** (``block_d=BD``): grid ``(2, D/BD)`` with the d axis
  minor-most.  Pass 0 accumulates gram + norms tile by tile and runs the
  screening at its last step; pass 1 emits the aggregate tiles.  ``G``, the
  norms, and the final weights live in constant-index output blocks, which
  TPU's sequential grid keeps resident across all iterations.  Requires the
  sequential-grid guarantee — TPU / interpret only.

Client-sharded engine (DESIGN.md §4): this mega-kernel is the SINGLE-SHARD
fast path.  The fused screening loop is inherently global — it needs every
client's similarity in one place for the masked median/std tail test — so
the client-sharded route (``core/afa._afa_aggregate_sharded``) cannot call
it per shard.  That route instead runs the hierarchical decomposition:
per-shard ``weighted_sum`` / ``cosine_sim`` kernel launches (the PR 4
primitives, operating on the shard-local ``(K/S, D)`` block) plus two
O(K)-scalar/-(D,) collectives per screening iteration, with the replicated
``_mark_bad`` loop on gathered scalars.  At shard count 1 the sharded
dispatch is bypassed entirely and this kernel runs unchanged.

Bitwise contract (the parity suite's strongest assertion): every float op
below mirrors the jnp reference in ``core/afa.py`` + ``core/stats.py`` —
same primitives, same operand order, same EPS clamps.  The only intentional
deviation is the masked median: ``jnp.sort`` has no Mosaic lowering, so it
is computed by compare-count rank selection (the ``coord_median`` idiom).
That selects the *same two order statistics* the sort would (ties broken by
index pick equal values), so the result is value-identical.

Every contraction sets ``precision=HIGHEST``, as the jnp reference does:
at Mosaic's default an f32 matmul rounds its operands to bf16 on the MXU,
which moved the aggregate ~3e-4 (relative) off the f32 rule on a v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.meta import register_kernel_geometry

EPS = 1e-12  # must match core/afa.py
SIM_TIE_RTOL = 2.0**-20  # must match core/afa.py


def _row_to_col(x, eye):
    """(1, K) row -> (K, 1) column by a masked lane reduction (exact: one
    live term per row).  Mosaic has no cheap relayout of a 1-row vector into
    a column, so this selects through the identity instead."""
    return jnp.sum(jnp.where(eye, x, jnp.zeros_like(x)), axis=1, keepdims=True)


def _col_to_row(x, eye):
    """(K, 1) column -> (1, K) row, the transpose of :func:`_row_to_col`."""
    return jnp.sum(jnp.where(eye, x, jnp.zeros_like(x)), axis=0, keepdims=True)


def _masked_mean(x, m, live):
    """Mirror of core.stats.masked_mean on a (1, K) row; ``live`` is the
    0/1 int32 mask row and ``m`` its sum."""
    return jnp.where(
        m > 0, jnp.sum(jnp.where(live != 0, x, 0.0)) / jnp.maximum(m, 1), 0.0
    )


def _masked_std(x, m, live, ddof):
    """Mirror of core.stats.masked_std."""
    mu = _masked_mean(x, m, live)
    var = jnp.sum(jnp.where(live != 0, (x - mu) ** 2, 0.0)) / jnp.maximum(m - ddof, 1)
    return jnp.sqrt(jnp.maximum(var, 0.0))


def _masked_median_cc(x, m, live, eye, after):
    """core.stats.masked_median by compare-count rank selection.

    ``jnp.sort`` has no Mosaic lowering; ranking each live element against
    the live set (ties broken by index -> a strict total order) and summing
    the one-hot selections of ranks ``(m-1)//2`` and ``m//2`` picks the same
    two order-statistic VALUES the sort-based reference picks, so the
    average is value-identical (O(K^2) compares).  Element i lives on the
    sublane axis (the column copy of ``x``), element k on the lane axis.
    """
    x_col = _row_to_col(x, eye)
    live_col = _row_to_col(live, eye)
    lt = (x < x_col) & (live != 0)
    eq = (x == x_col) & after & (live != 0)
    rank = jnp.sum(lt.astype(jnp.int32) + eq.astype(jnp.int32), axis=1,
                   keepdims=True)
    lo = jnp.maximum((m - 1) // 2, 0)
    hi = jnp.maximum(m // 2, 0)
    v_lo = jnp.sum(jnp.where((live_col != 0) & (rank == lo), x_col, 0.0))
    v_hi = jnp.sum(jnp.where((live_col != 0) & (rank == hi), x_col, 0.0))
    return jnp.where(m > 0, 0.5 * (v_lo + v_hi), 0.0)


def _screen(gram, unorm2, pn, mask0, *, xi0, delta_xi, max_rounds, ddof):
    """Algorithm 1's screening loop on a resident Gram matrix.

    Mirror of the ``variant="gram"`` while-loop in ``core/afa.py`` — any
    change there must land here too (the parity suite asserts bitwise
    equality on the interpret route).  Every operand stays 2-D for Mosaic:
    ``gram`` (K, K), ``unorm2`` a (K, 1) column, ``pn`` a (1, K) f32 row and
    ``mask0`` a (1, K) 0/1 int32 row; masks stay int32 so every select and
    count is integer arithmetic.  Returns ``(weights, mask, rounds, sims)``
    as (1, K) rows, ``weights`` the final normalized reputation weights.
    """
    K = pn.shape[1]
    ii = jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
    kk = jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
    eye, after = ii == kk, ii > kk
    # == jnp.linalg.norm(u, axis=1) bitwise
    row_norms = jnp.sqrt(_col_to_row(unorm2, eye))

    def weights(m):
        c = jnp.where(m != 0, pn, 0.0)
        return c / jnp.maximum(jnp.sum(c), EPS)

    def sims(c):
        gc = jax.lax.dot_general(
            c, gram, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        agg_norm = jnp.sqrt(jnp.maximum(jnp.sum(c * gc), EPS))
        return gc / (jnp.maximum(row_norms, EPS) * agg_norm)

    def mark_bad(s, m, xi):
        count = jnp.sum(m)
        mu_hat = _masked_mean(s, count, m)
        mu_bar = _masked_median_cc(s, count, m, eye, after)
        sigma = jnp.maximum(_masked_std(s, count, m, ddof), SIM_TIE_RTOL * jnp.abs(mu_bar))
        low_tail = jnp.where(s < mu_bar - xi * sigma, m, 0)
        high_tail = jnp.where(s > mu_bar + xi * sigma, m, 0)
        low = (mu_hat < mu_bar).astype(jnp.int32)
        bad = low * low_tail + (1 - low) * high_tail
        keep_floor = (jnp.sum(m * (1 - bad)) >= 2).astype(jnp.int32)
        return bad * keep_floor

    def cond(state):
        m, xi, changed, rounds, _ = state
        return (changed > 0) & (rounds < max_rounds)

    def body(state):
        m, xi, _, rounds, _ = state
        s = sims(weights(m))
        bad = mark_bad(s, m, xi)
        return (m * (1 - bad), xi + delta_xi, jnp.sum(bad), rounds + 1, s)

    s0 = (
        sims(weights(mask0)) if max_rounds == 0
        else jnp.zeros((1, K), jnp.float32)
    )
    mask, _, _, rounds, s = jax.lax.while_loop(
        cond, body,
        (mask0, jnp.float32(xi0), jnp.int32(1), jnp.int32(0), s0),
    )
    return weights(mask), mask, rounds, s


def _afa_screen_onepass_kernel(u_ref, pn_ref, mask_ref, agg_ref, good_ref, rounds_ref,
                    sims_ref, *, xi0, delta_xi, max_rounds, ddof):
    """Single grid step: gram + screening + aggregate on one resident tile."""
    u = u_ref[...].astype(jnp.float32)
    gram = jax.lax.dot_general(
        u, u, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    unorm2 = jnp.sum(u * u, axis=1, keepdims=True)
    w, mask, rounds, s = _screen(
        gram, unorm2, pn_ref[...], mask_ref[...],
        xi0=xi0, delta_xi=delta_xi, max_rounds=max_rounds, ddof=ddof,
    )
    agg_ref[...] = jax.lax.dot_general(
        w, u, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    good_ref[...] = mask
    rounds_ref[...] = jnp.full((1, 1), rounds, jnp.int32)
    sims_ref[...] = s


def _afa_screen_twopass_kernel(u_ref, pn_ref, mask_ref, agg_ref, good_ref, rounds_ref,
                    sims_ref, g_ref, un_ref, w_ref, *, nb, xi0, delta_xi,
                    max_rounds, ddof):
    """Grid (2, nb): pass 0 accumulates gram/norms (+screens at its last
    step), pass 1 emits aggregate tiles.  The cross-step state (``g_ref``,
    ``un_ref``, ``w_ref``) lives in constant-index output blocks that the
    sequential TPU grid keeps resident for the whole launch."""
    p = pl.program_id(0)
    b = pl.program_id(1)

    @pl.when((p == 0) & (b == 0))
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        un_ref[...] = jnp.zeros_like(un_ref)

    @pl.when(p == 0)
    def _accumulate():
        u = u_ref[...].astype(jnp.float32)
        g_ref[...] += jax.lax.dot_general(
            u, u, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        un_ref[...] += jnp.sum(u * u, axis=1, keepdims=True)

    @pl.when((p == 0) & (b == nb - 1))
    def _screen_resident():
        w, mask, rounds, s = _screen(
            g_ref[...], un_ref[...], pn_ref[...], mask_ref[...],
            xi0=xi0, delta_xi=delta_xi, max_rounds=max_rounds, ddof=ddof,
        )
        w_ref[...] = w
        good_ref[...] = mask
        rounds_ref[...] = jnp.full((1, 1), rounds, jnp.int32)
        sims_ref[...] = s

    @pl.when(p == 1)
    def _aggregate():
        u = u_ref[...].astype(jnp.float32)
        agg_ref[...] = jax.lax.dot_general(
            w_ref[...], u, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )


def afa_screen_call(
    updates: jnp.ndarray,   # (K, d) — padded by ops.py for compiled modes
    pn: jnp.ndarray,        # (K,) f32 — reputation * data count (p_k * n_k)
    mask0: jnp.ndarray,     # (K,) int32 — initial participation (0/1)
    *,
    xi0: float,
    delta_xi: float,
    max_rounds: int,
    ddof: int = 0,
    block_d: int | None = None,
    interpret: bool = True,
):
    """One Pallas launch -> ``(aggregate (d,), good_mask (K,) i32, rounds
    scalar i32, sims (K,))``.  ``block_d=None`` selects the one-pass
    geometry; an explicit block selects the two-pass d-tiled grid (d must be
    a block multiple; sequential-grid backends only)."""
    K, d = updates.shape
    screen_kw = dict(xi0=xi0, delta_xi=delta_xi, max_rounds=max_rounds, ddof=ddof)
    out_shapes = (
        jax.ShapeDtypeStruct((1, d), jnp.float32),   # aggregate
        jax.ShapeDtypeStruct((1, K), jnp.int32),     # good_mask
        jax.ShapeDtypeStruct((1, 1), jnp.int32),     # rounds
        jax.ShapeDtypeStruct((1, K), jnp.float32),   # sims
    )
    if block_d is None or block_d >= d:
        agg, good, rounds, sims = pl.pallas_call(
            functools.partial(_afa_screen_onepass_kernel, **screen_kw),
            name="_afa_screen_onepass_kernel",
            grid=(1,),
            in_specs=[
                pl.BlockSpec((K, d), lambda i: (0, 0)),
                pl.BlockSpec((1, K), lambda i: (0, 0)),
                pl.BlockSpec((1, K), lambda i: (0, 0)),
            ],
            out_specs=tuple(
                pl.BlockSpec(s.shape, lambda i: (0, 0)) for s in out_shapes
            ),
            out_shape=out_shapes,
            interpret=interpret,
        )(updates, pn[None, :], mask0[None, :])
        return agg[0], good[0], rounds[0, 0], sims[0]

    assert d % block_d == 0, (d, block_d)
    nb = d // block_d
    resident_shapes = (
        jax.ShapeDtypeStruct((K, K), jnp.float32),   # gram
        jax.ShapeDtypeStruct((K, 1), jnp.float32),   # unorm2
        jax.ShapeDtypeStruct((1, K), jnp.float32),   # final weights
    )
    agg, good, rounds, sims, _, _, _ = pl.pallas_call(
        functools.partial(_afa_screen_twopass_kernel, nb=nb, **screen_kw),
        name="_afa_screen_twopass_kernel",
        grid=(2, nb),
        in_specs=[
            pl.BlockSpec((K, block_d), lambda p, b: (0, b)),
            pl.BlockSpec((1, K), lambda p, b: (0, 0)),
            pl.BlockSpec((1, K), lambda p, b: (0, 0)),
        ],
        out_specs=(
            # pass 0 parks the aggregate window on block 0 (nothing is
            # written there); pass 1 revisits block 0 first, so every block
            # is flushed exactly once, after its pass-1 write
            pl.BlockSpec((1, block_d), lambda p, b: (0, jnp.where(p == 0, 0, b))),
            pl.BlockSpec((1, K), lambda p, b: (0, 0)),
            pl.BlockSpec((1, 1), lambda p, b: (0, 0)),
            pl.BlockSpec((1, K), lambda p, b: (0, 0)),
            pl.BlockSpec((K, K), lambda p, b: (0, 0)),
            pl.BlockSpec((K, 1), lambda p, b: (0, 0)),
            pl.BlockSpec((1, K), lambda p, b: (0, 0)),
        ),
        out_shape=out_shapes + resident_shapes,
        interpret=interpret,
    )(updates, pn[None, :], mask0[None, :])
    return agg[0], good[0], rounds[0, 0], sims[0]


# Declared grid-geometry contracts (kernels/meta.py).  The one-pass geometry
# runs the whole algorithm in a single grid step; the two-pass d-tiled grid
# keeps the gram/weight accumulators resident across steps (pass 0) and is
# therefore sequential-grid only — ops.py forces the one-pass geometry for
# compiled off-TPU launches.
register_kernel_geometry(
    "_afa_screen_onepass_kernel", "single-step", True,
    "grid (1,): gram + screening loop + weighted sum in one step",
)
register_kernel_geometry(
    "_afa_screen_twopass_kernel", "cross-step", False,
    "resident gram/norm/weight accumulators across the (2, nb) grid",
)
