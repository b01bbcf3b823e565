"""Operations and bytes a federated round requires, counted from shapes.

Counted from what the algorithm needs, whatever implements it: masked or
padded rows, recomputation and a kernel's second pass over its input do not
count.  A share of a peak built on these counts cannot pass 100% unless the
time leaves out part of the work.
"""

from __future__ import annotations


def dnn_dims(sizes) -> list[tuple[int, int]]:
    return list(zip(sizes[:-1], sizes[1:]))


def dnn_param_count(sizes) -> int:
    """D: weights and biases of the fully connected net."""
    return sum(a * b + b for a, b in dnn_dims(sizes))


def dnn_forward_flops(sizes) -> int:
    """Matmul FLOPs of one sample's forward pass."""
    return sum(2 * a * b for a, b in dnn_dims(sizes))


def dnn_train_flops(sizes) -> int:
    """Matmul FLOPs of one training sample: forward, weight gradients, and
    input gradients of every layer but the first (the input needs none)."""
    fwd = dnn_forward_flops(sizes)
    input_grads = sum(2 * a * b for a, b in dnn_dims(sizes)[1:])
    return 2 * fwd + input_grads


def sim_round_work(sizes, honest_live: int, steps: int, batch: int,
                   n_test: int, buffer_live: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one simulated round: local training of the live
    honest clients, the test-set evaluation, and one read of the live rows of
    the (K, D) proposal buffer."""
    D = dnn_param_count(sizes)
    flops = (honest_live * steps * batch * dnn_train_flops(sizes)
             + n_test * dnn_forward_flops(sizes))
    return float(flops), float(buffer_live * D * 4)


def serve_round_work(sizes, buffer_live: int, n_test: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one served round: the evaluation forward pass, the
    mean and the similarities over the live rows (4 K_live D), and one read
    of the live rows of the (K, D) buffer."""
    D = dnn_param_count(sizes)
    flops = n_test * dnn_forward_flops(sizes) + 4 * buffer_live * D
    return float(flops), float(buffer_live * D * 4)


def afa_screen_bytes(rows: int, D: int) -> float:
    """Bytes one screening call must move: one read of its rows and the
    (D,) aggregate written back."""
    return float(rows * D * 4 + D * 4)
