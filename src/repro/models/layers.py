"""Shared neural-net layers: norms, rotary embeddings, MLPs, initializers.

Pure-JAX, dict-of-arrays parameters.  Layer stacks are built by the model
modules with ``vmap`` over per-layer keys (stacked leaves, leading L axis)
and applied with ``lax.scan`` + ``jax.checkpoint``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _active_mesh_axis_size(mesh_axis: str) -> int:
    """Size of ``mesh_axis`` in whatever mesh context is active (use_mesh's
    abstract mesh, or the legacy `with mesh:` physical mesh), else 0."""
    try:
        am = jax.sharding.get_abstract_mesh()
        if am is not None and not getattr(am, "empty", True):
            return dict(am.shape).get(mesh_axis, 0)
    except Exception:
        pass
    try:  # legacy context manager — what launch/dryrun uses
        pm = jax.interpreters.pxla.thread_resources.env.physical_mesh
        if pm is not None and mesh_axis in getattr(pm, "axis_names", ()):
            return int(pm.shape[mesh_axis])
    except Exception:
        pass
    return 0


def maybe_replicate(x):
    """Pin a tensor fully replicated (used by parallel-q attention to stop
    GSPMD splitting MQA's single kv head's head_dim, which otherwise psums
    partial score tiles every kv block)."""
    from jax.sharding import PartitionSpec as P

    if not _active_mesh_axis_size("model"):
        return x
    return jax.lax.with_sharding_constraint(x, P(*([None] * x.ndim)))


def maybe_shard_axis(x, axis: int, mesh_axis: str = "model"):
    """with_sharding_constraint pinning ``axis`` to ``mesh_axis`` when a mesh
    with that axis is active and sizes divide; otherwise identity.  The §Perf
    activation-sharding lever (see ModelConfig.activation_sharding)."""
    from jax.sharding import PartitionSpec as P

    msize = _active_mesh_axis_size(mesh_axis)
    if not msize or x.shape[axis] % msize or x.shape[axis] < msize:
        return x
    spec = [None] * x.ndim
    spec[axis] = mesh_axis
    return jax.lax.with_sharding_constraint(x, P(*spec))


def dense_init(key, shape, dtype, scale: float | None = None):
    """Truncated-normal fan-in init."""
    fan_in = shape[0] if len(shape) >= 2 else 1
    s = scale if scale is not None else 1.0 / jnp.sqrt(fan_in)
    return (s * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


def linear(x, w):
    """``x @ w``, or for an adapted weight ``{"w": W, "a": A, "b": B}`` (a
    LoRA site with its scaling folded into A; ``fed/workload.attach_lora``)
    the unmerged ``x @ W + (x @ A) @ B``: the frozen W is shared by every
    client, only the rank-r path is per client."""
    if isinstance(w, dict):
        return x @ w["w"] + ((x @ w["a"]) @ w["b"]).astype(x.dtype)
    return x @ w


def rms_norm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + weight.astype(jnp.float32))).astype(dt)


def rope(x, positions, theta: float = 10_000.0):
    """Rotary position embedding.  x: (..., L, H, D) ; positions: (..., L)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., L, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., L, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)
    return out


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, activation: str, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"down": dense_init(k2, (d_ff, d_model), dtype)}
    if activation in ("swiglu", "geglu"):
        p["gate"] = dense_init(k1, (d_model, d_ff), dtype)
        p["up"] = dense_init(k3, (d_model, d_ff), dtype)
    else:
        p["up"] = dense_init(k1, (d_model, d_ff), dtype)
    return p


def apply_mlp(p, x, activation: str):
    if activation == "swiglu":
        h = jax.nn.silu(x @ p["gate"]) * (x @ p["up"])
    elif activation == "geglu":
        h = jax.nn.gelu(x @ p["gate"]) * (x @ p["up"])
    elif activation == "squared_relu":
        h = jnp.square(jax.nn.relu(x @ p["up"]))
    else:  # gelu
        h = jax.nn.gelu(x @ p["up"])
    return h @ p["down"]


def mlp_param_count(d_model: int, d_ff: int, activation: str) -> int:
    return d_model * d_ff * (3 if activation in ("swiglu", "geglu") else 2)
