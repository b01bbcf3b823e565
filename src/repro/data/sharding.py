"""Client shard construction: IID (the paper splits training data equally
across clients) and Dirichlet non-IID (standard fed-learning benchmark),
each as per-client row indices and as shard copies, plus the padded
``(K, n_max, ...)`` stacking the fused round engine samples minibatches from
on device and its inverse, ``compact_stack``, which drops blocked clients
from it.  The fused engines stage the same stacks on the device by a row
index map into a resident pool (``fed/simulator._compact_inputs``,
DESIGN.md §2); these host forms are its reference and serve the workloads
that stack their own shards."""

from __future__ import annotations

import numpy as np


def iid_shard_indices(n: int, num_clients: int, seed: int = 0) -> list:
    """Each client's row indices into the ``n`` training rows under the equal
    random split of :func:`iid_shards`."""
    rng = np.random.default_rng(seed)
    return np.array_split(rng.permutation(n), num_clients)


def iid_shards(x: np.ndarray, y: np.ndarray, num_clients: int, seed: int = 0):
    """Equal random split — the paper's setting ("we split the training data
    equally across all clients")."""
    return [(x[p], y[p]) for p in iid_shard_indices(len(x), num_clients, seed)]


def stack_dtype(a: np.ndarray):
    """Device dtype of a stacked shard: integer features (e.g. token ids)
    stay int32, everything else is cast to float32 (the classification
    path's historical behaviour)."""
    return np.int32 if np.issubdtype(a.dtype, np.integer) else np.float32


def padded_stack(shards):
    """Ragged client shards -> device-ready padded stacks.

    Returns ``(x (K, n_max, *feat), y (K, n_max, *lab), lengths (K,) int32)``
    — the per-example trailing shape is whatever the workload's shards carry
    (``(d,)`` float features for the classification DNN, ``(seq,)`` int32
    token windows for the LM workload; labels are scalar classes or
    ``(seq,)`` next-token targets).  Shard k occupies rows
    ``[0, lengths[k])``; the tail is zero-padded.  The fused engine draws
    minibatch indices on device as ``randint(0, lengths[k])`` per client, so
    padding rows are never sampled — they only buy every client a common
    shape for ``vmap``/``scan``.
    """
    K = len(shards)
    n_max = max(len(x) for x, _ in shards)
    x0 = np.asarray(shards[0][0])
    y0 = np.asarray(shards[0][1])
    x_pad = np.zeros((K, n_max) + x0.shape[1:], stack_dtype(x0))
    y_pad = np.zeros((K, n_max) + y0.shape[1:], np.int32)
    lengths = np.zeros((K,), np.int32)
    for k, (x, y) in enumerate(shards):
        n = len(x)
        x_pad[k, :n] = x
        y_pad[k, :n] = y
        lengths[k] = n
    return x_pad, y_pad, lengths


def compact_stack(x_pad, y_pad, lengths, keep, pad_to: int | None = None):
    """Inverse of :func:`padded_stack` restricted to the kept client rows.

    Gathers rows ``keep`` (an index map of still-live clients, ascending) out
    of the padded ``(K, n_max, ...)`` stacks into a dense ``(K_live, n_max,
    ...)`` layout, optionally re-padded to ``pad_to`` rows (the segmented
    fused engine pads ``K_live`` up to a power-of-two bucket so the segment
    scan re-traces only O(log K) times).  Pad rows carry zero shards with
    ``length = 1`` — the device batch draw is ``randint(0, length)``, which
    needs a non-empty range, and a pad row's gathered batch is all-zeros and
    masked out of every aggregate anyway.

    ``keep`` entries of ``-1`` are *interleaved* pad slots: the sharded
    segmented engine compacts each client shard independently, so pad rows
    land at the tail of every shard's block, not only at the global tail
    (see :func:`shard_compact_plan`).  A ``-1`` slot produces the same zero
    shard / ``length = 1`` row an end-padding slot does.

    Raises ``ValueError`` when ``pad_to`` is smaller than the number of kept
    rows — silently truncating live clients would corrupt the simulation.
    """
    keep = np.asarray(keep, np.int64)
    if pad_to is not None and pad_to < len(keep):
        raise ValueError(
            f"pad_to={pad_to} is smaller than the {len(keep)} kept client "
            f"rows; refusing to truncate live clients"
        )
    live = keep >= 0

    def _gather(stack):
        # mask broadcast against whatever trailing shard shape the workload
        # stacked (features, token windows, ...)
        row = live.reshape((-1,) + (1,) * (stack.ndim - 1))
        return np.where(row, stack[np.maximum(keep, 0)], 0).astype(stack.dtype)

    x_c = _gather(x_pad)
    y_c = _gather(y_pad)
    len_c = np.where(live, np.asarray(lengths)[np.maximum(keep, 0)], 1).astype(
        np.asarray(lengths).dtype
    )
    if pad_to is not None and pad_to > len(keep):
        extra = pad_to - len(keep)
        x_c = np.concatenate([x_c, np.zeros((extra,) + x_c.shape[1:], x_c.dtype)])
        y_c = np.concatenate([y_c, np.zeros((extra,) + y_c.shape[1:], y_c.dtype)])
        len_c = np.concatenate([len_c, np.ones((extra,), len_c.dtype)])
    return x_c, y_c, len_c


def shard_compact_plan(live_ids, num_shards: int, cap_per_shard: int):
    """Per-shard compaction layout for the client-sharded fused engine.

    Distributes the still-live client ids contiguously across ``num_shards``
    equal blocks of ``rows = pow2_bucket(ceil(n_live / num_shards),
    cap_per_shard)`` rows each, padding every block's tail with ``-1``
    sentinels.  Returns ``(keep (num_shards * rows,) int64 with -1 pads,
    rows_per_shard)``.  Every shard gets the same row count (shard_map needs
    equal blocks) and the count is a power-of-two bucket so the segment scan
    re-traces only O(log K) times per shard — the sharded analogue of the
    single-device ``pow2_bucket`` compaction.
    """
    live_ids = np.asarray(live_ids, np.int64)
    n_live = len(live_ids)
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    rows = pow2_bucket(-(-max(n_live, 1) // num_shards), cap_per_shard)
    if rows * num_shards < n_live:
        raise ValueError(
            f"{n_live} live clients do not fit {num_shards} shards of "
            f"cap {cap_per_shard} rows"
        )
    keep = np.full((num_shards * rows,), -1, np.int64)
    for s in range(num_shards):
        chunk = live_ids[s * rows : (s + 1) * rows]
        keep[s * rows : s * rows + len(chunk)] = chunk
    return keep, rows


def pow2_bucket(n_live: int, cap: int) -> int:
    """Smallest power of two >= ``n_live``, clamped to ``[1, cap]``.

    The segmented fused engine sizes its compacted client axis by bucket so
    the number of distinct shapes (and therefore scan retraces) over a whole
    simulation is O(log K), not O(#blocking events).
    """
    b = 1
    while b < n_live:
        b *= 2
    return max(1, min(b, cap))


def dirichlet_shard_indices(
    y: np.ndarray, num_clients: int, alpha: float = 0.5, seed: int = 0
) -> list:
    """Each client's row indices into ``y``'s rows under the label-skewed
    split of :func:`dirichlet_shards`."""
    rng = np.random.default_rng(seed)
    classes = np.unique(y)
    buckets: list[list[int]] = [[] for _ in range(num_clients)]
    for c in classes:
        idx = np.nonzero(y == c)[0]
        rng.shuffle(idx)
        props = rng.dirichlet(np.full(num_clients, alpha))
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for b, part in zip(buckets, np.split(idx, cuts)):
            b.extend(part.tolist())
    out = []
    for b in buckets:
        b = np.asarray(b if b else [int(rng.integers(0, len(y)))])
        rng.shuffle(b)
        out.append(b)
    return out


def dirichlet_shards(
    x: np.ndarray, y: np.ndarray, num_clients: int, alpha: float = 0.5, seed: int = 0
):
    """Label-skewed split: per-class Dirichlet(alpha) allocation over clients.
    Smaller alpha -> more heterogeneous shards (and *unequal* n_k, exercising
    AFA's n_k-weighted aggregation where MKRUM/COMED ignore it)."""
    return [
        (x[b], y[b])
        for b in dirichlet_shard_indices(y, num_clients, alpha=alpha, seed=seed)
    ]
