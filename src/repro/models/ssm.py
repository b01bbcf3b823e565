"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060) block in pure JAX.

The TPU-native adaptation: the SSD *chunked* form turns the recurrence into
(a) per-chunk quadratic attention-like einsums that land on the MXU and
(b) a short `lax.scan` over chunk states — exactly the blocked structure a
Pallas/TPU pipeline wants, instead of the GPU kernel's warp-level scan.

Shapes (single group, g=1, broadcast over heads):
  x:  (B, L, H, P)    — P = ssm_head_dim
  dt: (B, L, H)       — softplus-discretized step
  A:  (H,)            — negative decay rate per head
  B,C:(B, L, N)       — state input/output projections (N = ssm_state)

Decode carries state (B, H, P, N) plus a depthwise-conv ring buffer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, linear


def init_mamba2(key, cfg):
    d, di, h, n, cw = cfg.d_model, cfg.d_inner, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_conv_width
    ks = jax.random.split(key, 4)
    d_xbc = di + 2 * n  # conv runs over [x, B, C]
    return {
        "in_proj": dense_init(ks[0], (d, 2 * di + 2 * n + h), cfg.pdtype),
        "conv_w": dense_init(ks[1], (cw, d_xbc), cfg.pdtype, scale=0.5),
        "conv_b": jnp.zeros((d_xbc,), cfg.pdtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "out_proj": dense_init(ks[2], (di, d), cfg.pdtype),
        "gate_norm_w": jnp.zeros((di,), cfg.pdtype),
    }


def _causal_conv(xbc, w, b):
    """Depthwise causal conv, width cw.  xbc: (B, L, D)."""
    cw = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (cw - 1, 0), (0, 0)))
    out = sum(pad[:, i : i + xbc.shape[1]] * w[i][None, None, :] for i in range(cw))
    return jax.nn.silu(out + b[None, None, :])


def _intra_chunk(a_cs, C, B, xbar):
    """Within a chunk, ``y_i = sum_{j<=i} (C_i . B_j) exp(a_cs_i - a_cs_j)
    xbar_j``; leading axes are batch (and chunk) axes."""
    seg = a_cs[..., :, None, :] - a_cs[..., None, :, :]  # (..., i, j, h)
    iq = jnp.arange(a_cs.shape[-2])
    causal = (iq[:, None] >= iq[None, :])[(None,) * (a_cs.ndim - 2) + (..., None)]
    # mask BEFORE exp: exp of the (positive, growing) anti-causal entries
    # would overflow and poison gradients through the where
    Lmat = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = jnp.einsum("...in,...jn->...ij", C.astype(jnp.float32), B.astype(jnp.float32))
    return jnp.einsum("...ij,...ijh,...jhp->...ihp", scores, Lmat, xbar)


def _ssd_chunked(x, dt, A, B, C, D, chunk: int, chunk_remat: bool = False):
    """Chunked SSD scan.  Returns (y, final_state).  ``chunk_remat`` computes
    the intra-chunk term one checkpointed chunk at a time (a sequential map),
    so training holds one chunk's quadratic tensors instead of all of them."""
    b, l, h, p = x.shape
    n = B.shape[-1]
    pad = (-l) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    nc = x.shape[1] // chunk
    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, 1) if dt.ndim == 2 else dt.reshape(b, nc, chunk, h)
    Bc = B.reshape(b, nc, chunk, n)
    Cc = C.reshape(b, nc, chunk, n)

    a = (A[None, None, None, :] * dtc).astype(jnp.float32)  # (b,nc,q,h) log-decay
    a_cs = jnp.cumsum(a, axis=2)  # within-chunk cumulative
    a_tot = a_cs[:, :, -1]  # (b,nc,h)

    xbar = xc.astype(jnp.float32) * dtc[..., None]

    # intra-chunk (quadratic in the chunk — MXU-friendly)
    if chunk_remat:
        # one chunk at a time, each checkpointed: only a single chunk's
        # (b, i, j, h) decay and score tensors are live, backward as forward
        intra = jax.checkpoint(lambda args: _intra_chunk(*args))
        chunk_major = lambda t: jnp.moveaxis(t, 1, 0)
        y_intra = jnp.moveaxis(jax.lax.map(
            intra, (chunk_major(a_cs), chunk_major(Cc), chunk_major(Bc), chunk_major(xbar))), 0, 1)
    else:
        y_intra = _intra_chunk(a_cs, Cc, Bc, xbar)

    # chunk-state contributions: S_c = sum_j exp(a_tot - a_cs_j) * B_j x_j^T
    w_in = jnp.exp(a_tot[:, :, None, :] - a_cs)  # (b,nc,j,h)
    S_c = jnp.einsum("bcjn,bcjh,bcjhp->bchnp", Bc.astype(jnp.float32), w_in, xbar)

    # inter-chunk recurrence over chunk states
    def step(s, inp):
        sc, atot = inp  # (b,h,n,p), (b,h)
        s_new = s * jnp.exp(atot)[:, :, None, None] + sc
        return s_new, s  # emit the state *entering* the chunk

    s0 = jnp.zeros((b, h, n, p), jnp.float32)
    s_final, s_in = jax.lax.scan(
        step, s0, (S_c.transpose(1, 0, 2, 3, 4), a_tot.transpose(1, 0, 2))
    )
    s_in = s_in.transpose(1, 0, 2, 3, 4)  # (b,nc,h,n,p)

    # inter-chunk output: y_i += C_i · (decay_i * S_in)
    y_inter = jnp.einsum("bcin,bcih,bchnp->bcihp", Cc.astype(jnp.float32), jnp.exp(a_cs), s_in)

    y = (y_intra + y_inter).reshape(b, nc * chunk, h, p)
    y = y + D[None, None, :, None] * x.astype(jnp.float32)
    return y[:, :l].astype(xc.dtype), s_final


def _split_proj(cfg, proj):
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    z, xbc, dt = jnp.split(proj, [di, di + di + 2 * n], axis=-1)
    return z, xbc, dt


def apply_mamba2(p, cfg, u, *, return_state: bool = False, chunk_remat: bool = False):
    """u: (B, L, d_model) -> (B, L, d_model).  ``chunk_remat``: see
    :func:`_ssd_chunked`."""
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    proj = linear(u, p["in_proj"])
    z, xbc_raw, dt_raw = _split_proj(cfg, proj)
    xbc = _causal_conv(xbc_raw, p["conv_w"], p["conv_b"])
    x, B, C = jnp.split(xbc, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"][None, None, :])
    A = -jnp.exp(p["A_log"])
    xh = x.reshape(*x.shape[:2], h, cfg.ssm_head_dim)
    if cfg.activation_sharding:
        # §Perf lever: SSD is head-independent — pin heads to *model* so the
        # chunk scan runs chip-local (B/C are n-dim shared, tiny, replicated)
        from repro.models.layers import maybe_shard_axis

        xh = maybe_shard_axis(xh, 2)
    y, state = _ssd_chunked(xh, dt, A, B, C, p["D"], cfg.ssm_chunk, chunk_remat)
    y = y.reshape(*u.shape[:2], di)
    # gated RMSNorm (mamba2)
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(g * g, axis=-1, keepdims=True)
    g = g * jax.lax.rsqrt(var + cfg.norm_eps) * (1.0 + p["gate_norm_w"].astype(jnp.float32))
    out = linear(g.astype(u.dtype), p["out_proj"])
    if return_state:
        cw = cfg.ssm_conv_width
        # cache keeps the *raw* (pre-conv) xbc tail, matching decode_mamba2
        tail = xbc_raw[:, -(cw - 1) :, :]
        pad = (cw - 1) - tail.shape[1]
        if pad:
            tail = jnp.pad(tail, ((0, 0), (pad, 0), (0, 0)))
        return out, {"state": state, "conv": tail}
    return out


def init_ssm_cache(cfg, batch: int, dtype=jnp.float32):
    h, pdim, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    cw = cfg.ssm_conv_width
    d_xbc = cfg.d_inner + 2 * n
    return {
        "state": jnp.zeros((batch, h, n, pdim), jnp.float32),
        "conv": jnp.zeros((batch, cw - 1, d_xbc), dtype),
    }


def decode_mamba2(p, cfg, u1, cache):
    """Single-token step.  u1: (B, d_model)."""
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    proj = linear(u1, p["in_proj"])
    z, xbc_new, dt_raw = _split_proj(cfg, proj)
    # depthwise conv over ring buffer + current input
    window = jnp.concatenate([cache["conv"], xbc_new[:, None, :]], axis=1)  # (B,cw,D)
    conv = jnp.einsum("bcd,cd->bd", window.astype(jnp.float32), p["conv_w"].astype(jnp.float32))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32)[None, :]).astype(u1.dtype)
    x, B, C = jnp.split(xbc, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"][None, :])  # (B,h)
    A = -jnp.exp(p["A_log"])
    xh = x.reshape(-1, h, cfg.ssm_head_dim).astype(jnp.float32)
    decay = jnp.exp(A[None, :] * dt)  # (B,h)
    inp = jnp.einsum("bn,bh,bhp->bhnp", B.astype(jnp.float32), dt, xh)
    state = cache["state"] * decay[:, :, None, None] + inp
    y = jnp.einsum("bn,bhnp->bhp", C.astype(jnp.float32), state)
    y = y + p["D"][None, :, None] * xh
    y = y.reshape(-1, di)
    g = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(g * g, axis=-1, keepdims=True)
    g = g * jax.lax.rsqrt(var + cfg.norm_eps) * (1.0 + p["gate_norm_w"].astype(jnp.float32))
    out = linear(g.astype(u1.dtype), p["out_proj"])
    new_cache = {
        "state": state,
        "conv": jnp.concatenate([cache["conv"][:, 1:], xbc_new[:, None, :]], axis=1),
    }
    return out, new_cache
