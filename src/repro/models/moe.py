"""Mixture-of-Experts layer: top-k router + capacity-based scatter dispatch.

Dispatch is scatter/gather (not one-hot einsum): building the dispatched
activations ``(B, E, C, d)`` costs O(tokens·d) memory traffic instead of the
O(tokens·E·C·d) FLOPs a dense one-hot dispatch einsum would burn — on TPU the
scatter lowers to dynamic-update-slices and the expert matmuls stay on the
MXU with the expert axis sharded over the *model* mesh axis.

Capacity is per batch row (C = ceil(L·k/E·cf)); overflow tokens are dropped
(slot index pushed out of bounds, ``mode="drop"``), matching Switch/GShard
semantics.  Aux losses: load-balance (Shazeer) + router z-loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


def init_moe(key, d_model: int, d_ff: int, num_experts: int, activation: str, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    gated = activation in ("swiglu", "geglu")
    p = {
        "router": dense_init(k1, (d_model, num_experts), jnp.float32, scale=0.02),
        "down": dense_init(k3, (num_experts, d_ff, d_model), dtype),
    }
    if gated:
        p["gate"] = dense_init(k2, (num_experts, d_model, d_ff), dtype)
        p["up"] = dense_init(k4, (num_experts, d_model, d_ff), dtype)
    else:
        p["up"] = dense_init(k2, (num_experts, d_model, d_ff), dtype)
    return p


def _expert_ffn(p, x, activation):
    """x: (B, E, C, d) with E sharded over *model*."""
    if activation in ("swiglu", "geglu"):
        act = jax.nn.silu if activation == "swiglu" else jax.nn.gelu
        h = act(jnp.einsum("becd,edf->becf", x, p["gate"])) * jnp.einsum(
            "becd,edf->becf", x, p["up"]
        )
    elif activation == "squared_relu":
        h = jnp.square(jax.nn.relu(jnp.einsum("becd,edf->becf", x, p["up"])))
    else:
        h = jax.nn.gelu(jnp.einsum("becd,edf->becf", x, p["up"]))
    return jnp.einsum("becf,efd->becd", h, p["down"])


def apply_moe(p, x, *, num_experts: int, top_k: int, capacity_factor: float, activation: str):
    """x: (B, L, d) -> (y, aux) with aux = (load_balance_loss, z_loss)."""
    b, l, d = x.shape
    e, k = num_experts, top_k
    cap = max(int(l * k / e * capacity_factor), 1)

    logits = (x.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (B,L,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)  # (B,L,k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)

    # slot position of each (token, choice) within its expert, per batch row
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)  # (B,L,k,E)
    flat = onehot.reshape(b, l * k, e)
    pos_in_expert = jnp.cumsum(flat, axis=1) - flat  # (B, L*k, E)
    slot = jnp.sum(pos_in_expert * flat, axis=-1).reshape(b, l, k)  # (B,L,k)
    expert = idx  # (B,L,k)
    # drop overflow: slot >= cap -> out-of-bounds scatter with mode="drop"
    slot = jnp.where(slot < cap, slot, cap)

    # scatter tokens into (B, E, cap+1, d); the +1 row is the drop bin
    buf = jnp.zeros((b, e, cap + 1, d), x.dtype)
    bidx = jnp.arange(b)[:, None, None]
    buf = buf.at[bidx, expert, slot].add(x[:, :, None, :], mode="drop")
    y_exp = _expert_ffn(p, buf[:, :, :cap].astype(x.dtype), activation)
    y_exp = jnp.pad(y_exp, ((0, 0), (0, 0), (0, 1), (0, 0)))  # drop bin reads 0
    # gather back and combine with gate weights
    y_tok = y_exp[bidx, expert, slot]  # (B,L,k,d)
    y = jnp.sum(y_tok * gates[..., None].astype(y_tok.dtype), axis=2)

    # aux losses
    me = jnp.mean(probs, axis=(0, 1))  # mean router prob per expert
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx[..., 0], e), axis=1) / l, axis=0
    )  # fraction of tokens whose top-1 is e
    lb = e * jnp.sum(me * ce)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y.astype(x.dtype), (lb, z)


# ---------------------------------------------------------------------------
# expert share: one chip's contiguous range of experts, no capacity, no drop
# ---------------------------------------------------------------------------


def init_moe_share(key, cfg):
    """Router over all ``cfg.num_experts`` (kept at its published width) and
    SwiGLU experts for the ids ``cfg.held_range`` only."""
    lo, hi = cfg.held_range
    k_router, k_experts = jax.random.split(key)
    p = init_moe(k_experts, cfg.d_model, cfg.d_ff, hi - lo, "swiglu", cfg.pdtype)
    p["router"] = dense_init(k_router, (cfg.d_model, cfg.num_experts), cfg.pdtype)
    return p


def apply_moe_share(p, x, *, top_k: int, held: tuple):
    """This chip's part of a top-k MoE (granite-4.0-h's
    ``GraniteMoeHybridTopKGating``: the top-k router logits, then a softmax
    over those k).  Every token is routed over all experts; the experts
    ``held = (lo, hi)`` compute their part for every token routed to them,
    weighted by its gate, and what absent experts would add is left out.
    The router's logits are float32 accumulations (bf16 operands in a bf16
    model), so near ties are not decided by a rounding of the logits.

    Dense over the held experts: each computes every token and the gate
    (zero where a token did not choose it) selects, so no token is dropped
    and the shape is static under the client vmap and autodiff.  That is
    ``(hi - lo) / (top_k * (hi - lo) / num_experts)`` times the routed
    expert FLOPs (7.2x for 9 of 72 experts at top-10).

    x: (..., d) -> (y, experts): ``experts`` (..., top_k) int32, the ids of
    the experts each token chose, held or not."""
    lo, hi = held
    logits = jnp.einsum("...d,de->...e", x, p["router"], preferred_element_type=jnp.float32)
    vals, idx = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(vals, axis=-1)
    # one_hot of an id outside [0, hi - lo) is a zero row: absent experts
    routed = jax.nn.one_hot(idx - lo, hi - lo, dtype=jnp.float32)  # (..., k, n)
    combine = jnp.einsum("...k,...kn->...n", gates, routed)
    h = jax.nn.silu(jnp.einsum("...d,edf->...ef", x, p["gate"])) * jnp.einsum(
        "...d,edf->...ef", x, p["up"])
    h = h * combine[..., None].astype(h.dtype)
    y = jnp.einsum("...ef,efd->...d", h, p["down"])
    return y.astype(x.dtype), idx
