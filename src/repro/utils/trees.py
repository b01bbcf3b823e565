"""Pytree linear-algebra helpers.

The federated layer treats a model update as a vector in R^d, but at scale the
update lives as a sharded pytree.  These helpers implement the handful of
vector-space ops the aggregation rules need (dot products, norms, axpy) without
ever materializing the flattened vector, so parameter shardings are preserved
under pjit.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def tree_dot(a, b, *, axes=None, dtype=jnp.float32):
    """Sum of elementwise products across all leaves.

    If ``axes`` is given (e.g. client axis in a stacked tree), the contraction
    keeps those leading axes: leaves shaped ``(K, ...)`` produce a ``(K,)``
    result.
    """
    total = None
    for la, lb in zip(_leaves(a), _leaves(b)):
        la = la.astype(dtype)
        lb = lb.astype(dtype)
        if axes is None:
            part = jnp.sum(la * lb)
        else:
            keep = axes
            red = tuple(range(keep, la.ndim))
            part = jnp.sum(la * lb, axis=red)
        total = part if total is None else total + part
    return total


def tree_norm(a, *, axes=None, dtype=jnp.float32):
    return jnp.sqrt(tree_dot(a, a, axes=axes, dtype=dtype))


def tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def tree_sub(a, b):
    return jax.tree_util.tree_map(jnp.subtract, a, b)


def tree_scale(s, a):
    return jax.tree_util.tree_map(lambda x: (s * x.astype(jnp.result_type(s, x))).astype(x.dtype), a)


def tree_axpy(s, x, y):
    """y + s * x, leafwise (in y's dtype)."""
    return jax.tree_util.tree_map(
        lambda lx, ly: (ly + s * lx.astype(ly.dtype)).astype(ly.dtype), x, y
    )


def tree_stack(trees):
    """List of identically-structured trees -> one tree with a new leading
    client axis on every leaf (host-side helper for the looped engine)."""
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *trees)


def tree_broadcast_clients(tree, num_clients: int):
    """Broadcast a single tree to a stacked tree with K identical rows."""
    return jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l[None], (num_clients,) + l.shape), tree
    )


def tree_select_rows(mask, a, b):
    """Row-wise select over the leading client axis: ``where(mask[k], a_k,
    b_k)`` leafwise.  The jit-able replacement for Python per-client branching
    (honest vs attacker, trained vs skipped)."""
    return jax.tree_util.tree_map(
        lambda la, lb: jnp.where(
            mask.reshape((-1,) + (1,) * (la.ndim - 1)), la, lb
        ),
        a,
        b,
    )


def tree_scatter_rows(base, rows, tree):
    """``base`` with row ``rows[h]`` of its leading client axis set to row
    ``h`` of ``tree``, leafwise; an index past the last row is dropped."""
    return jax.tree_util.tree_map(
        lambda lb, lt: lb.at[rows].set(lt, mode="drop"), base, tree
    )


def tree_zeros_like(a, dtype=None):
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, dtype or x.dtype), a
    )


def tree_size(a) -> int:
    return int(sum(np.prod(l.shape) for l in _leaves(a)))


# ---------------------------------------------------------------------------
# packed (K, D) layout — the aggregation hot-path representation (DESIGN.md §3)
# ---------------------------------------------------------------------------


class LeafSlot(NamedTuple):
    """One leaf's column slice of the packed buffer."""

    shape: tuple            # per-client leaf shape (no leading client axis)
    dtype: np.dtype         # original leaf dtype, restored by unpack_stack
    offset: int             # first column of this leaf's slice
    size: int               # number of columns (= prod(shape))


class PackSpec(NamedTuple):
    """Static layout of a pytree packed into one contiguous column axis.

    Hashable (treedef + tuples + np.dtype), so it rides through jit as a
    static argument and is cached per (structure, shapes, dtypes) — building
    it for the same model template is free after the first call.

    ``dtype`` is the packed buffer dtype: the jnp promotion of every leaf
    dtype (all-f32 trees pack as f32 bit-for-bit; mixed bf16/f32 promotes to
    f32).  ``unpack_stack`` casts each slot back to its recorded leaf dtype,
    so pack -> unpack round-trips exactly whenever the promoted type can
    represent every leaf value — always true for floating trees, which is
    the model-update case.
    """

    treedef: Any
    slots: tuple            # tuple[LeafSlot, ...] in tree_leaves order
    dim: int                # D = total packed columns
    dtype: np.dtype         # packed buffer dtype (promoted)


@functools.lru_cache(maxsize=512)
def _pack_spec_cached(treedef, shapes, dtypes) -> PackSpec:
    slots, off = [], 0
    for shp, dt in zip(shapes, dtypes):
        n = int(np.prod(shp, dtype=np.int64)) if len(shp) else 1
        slots.append(LeafSlot(shp, np.dtype(dt), off, n))
        off += n
    packed = functools.reduce(jnp.promote_types, dtypes)
    return PackSpec(treedef, tuple(slots), off, np.dtype(packed))


def pack_spec(tree, *, stacked: bool = False) -> PackSpec:
    """Layout of ``tree`` packed along one column axis.

    ``stacked=True`` strips the leading client axis from every leaf shape, so
    the spec describes ONE client row of a stacked proposal tree — the same
    spec then serves ``pack_stack`` on the (K, ...) tree and ``unpack_stack``
    on the (D,) aggregate.
    """
    leaves = _leaves(tree)
    treedef = jax.tree_util.tree_structure(tree)
    shapes = tuple(
        tuple(l.shape[1:]) if stacked else tuple(l.shape) for l in leaves
    )
    dtypes = tuple(np.dtype(l.dtype) for l in leaves)
    return _pack_spec_cached(treedef, shapes, dtypes)


def pack_stack(stacked_tree, spec: PackSpec | None = None) -> jnp.ndarray:
    """Stacked tree (leading client axis K on every leaf) -> one contiguous
    ``(K, D)`` buffer in ``spec.dtype``, columns in ``tree_leaves`` order.

    Pure jnp reshapes + one concatenate — device-resident under jit, no host
    round-trip.  For uniform-f32 trees the buffer is bit-identical to the
    historical per-leaf ``flatten_to_matrix`` concatenation.
    """
    leaves = _leaves(stacked_tree)
    if spec is None:
        spec = pack_spec(stacked_tree, stacked=True)
    K = leaves[0].shape[0]
    cols = [
        jnp.reshape(l, (K, slot.size)).astype(spec.dtype)
        for l, slot in zip(leaves, spec.slots)
    ]
    return jnp.concatenate(cols, axis=1)


def unpack_stack(packed: jnp.ndarray, spec: PackSpec):
    """Inverse of :func:`pack_stack` along the last axis.

    Accepts any leading batch shape: ``(D,)`` unpacks to one client tree (the
    aggregate), ``(K, D)`` to a stacked tree, ``(n_seeds, K, D)`` to a swept
    stack.  Each slot is cast back to its recorded leaf dtype.
    """
    lead = packed.shape[:-1]
    out = [
        jnp.reshape(
            packed[..., slot.offset : slot.offset + slot.size],
            lead + slot.shape,
        ).astype(slot.dtype)
        for slot in spec.slots
    ]
    return jax.tree_util.tree_unflatten(spec.treedef, out)


def flatten_to_matrix(stacked_tree, num_rows: int):
    """Stacked tree with leading client axis K -> dense (K, d) matrix.

    Legacy alias of :func:`pack_stack` (the per-leaf reshape+concat is the
    same op sequence); kept for the leaf-layout reference path and callers
    that do not track a :class:`PackSpec`.
    """
    del num_rows  # shape is read off the leaves; kept for signature compat
    return pack_stack(stacked_tree)


def unflatten_from_vector(vec, template):
    """Inverse of flatten for a single (d,) vector against a template tree
    (legacy alias of :func:`unpack_stack` with an ad-hoc spec)."""
    return unpack_stack(vec, pack_spec(template))
