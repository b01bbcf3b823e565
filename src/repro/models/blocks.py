"""Transformer blocks: attention block (+dense or MoE FFN) and layer init.

Per-layer params are created by ``init_block`` and stacked (leading L axis)
by the model module with ``vmap``; ``apply_block`` is the `lax.scan` body.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import (
    decode_attention,
    flash_attention,
    sliding_window_attention,
)
from repro.models.layers import (
    apply_mlp,
    dense_init,
    init_mlp,
    linear,
    maybe_shard_axis,
    rms_norm,
    rope,
)
from repro.models.moe import apply_moe, init_moe
from repro.models.ssm import apply_mamba2, decode_mamba2, init_mamba2, init_ssm_cache


# ---------------------------------------------------------------------------
# attention sub-block
# ---------------------------------------------------------------------------


def init_attn(key, cfg):
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, hq * hd), cfg.pdtype),
        "wk": dense_init(ks[1], (d, hkv * hd), cfg.pdtype),
        "wv": dense_init(ks[2], (d, hkv * hd), cfg.pdtype),
        "wo": dense_init(ks[3], (hq * hd, d), cfg.pdtype),
    }


def _qkv(p, cfg, x, positions, *, head_local: bool = False):
    b, l, _ = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = linear(x, p["wq"]).reshape(b, l, hq, hd)
    k = linear(x, p["wk"]).reshape(b, l, hkv, hd)
    v = linear(x, p["wv"]).reshape(b, l, hkv, hd)
    if head_local:
        # §Perf lever (activation_sharding): repeat kv to full q heads
        # (GQA == repeated-kv MHA) and pin every tensor head-sharded over
        # *model* — the score einsum becomes chip-local instead of GSPMD
        # all-gathering 64MB score tiles inside the kv scan.
        g = hq // hkv
        if g > 1:
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
        q = maybe_shard_axis(q, 2)
        k = maybe_shard_axis(k, 2)
        v = maybe_shard_axis(v, 2)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attn_policy() -> str:
    """Kernel-policy route for the Pallas attention backend.

    ``use_pallas_attention=True`` is an explicit config request, so it is
    honored under ``auto`` (the ops wrapper compiles on TPU and interprets
    elsewhere) — but the process-wide policy still governs:
    ``$REPRO_KERNELS=jnp`` vetoes the Pallas backend (the jnp flash
    attention runs instead) and ``interpret``/``pallas``/``pallas-gpu`` pin
    the execution route, exactly as for the aggregation kernels."""
    from repro.kernels.policy import requested_policy

    return requested_policy()


def apply_attn(p, cfg, x, *, positions, use_window: bool = False):
    q, k, v = _qkv(p, cfg, x, positions, head_local=cfg.activation_sharding)
    if use_window and cfg.sliding_window:
        out = sliding_window_attention(
            q, k, v, window=cfg.sliding_window, block_q=cfg.block_q
        )
    elif cfg.use_pallas_attention and not cfg.prefix_len and _attn_policy() != "jnp":
        from repro.kernels import flash_attention as pallas_flash

        out = pallas_flash(
            q, k, v, causal=cfg.causal,
            block_q=min(cfg.block_q, 128), block_k=min(cfg.block_k, 128),
        )
    else:
        out = flash_attention(
            q,
            k,
            v,
            causal=cfg.causal,
            prefix_len=cfg.prefix_len,
            block_q=cfg.block_q,
            block_k=cfg.block_k,
            parallel_q=cfg.seq_par_attention,
        )
    b, l, _ = x.shape
    return linear(out.reshape(b, l, -1), p["wo"])


def prefill_attn(p, cfg, x, *, positions, cache_size: int, use_window: bool):
    """Attention + return the KV cache (linear or ring layout)."""
    q, k, v = _qkv(p, cfg, x, positions)
    b, l = x.shape[:2]
    if use_window and cfg.sliding_window:
        out = sliding_window_attention(q, k, v, window=cfg.sliding_window, block_q=cfg.block_q)
        # ring layout: slot = pos % cache_size; take the last cache_size kv
        w = cache_size
        kw = k[:, -w:] if l >= w else jnp.pad(k, ((0, 0), (0, w - l), (0, 0), (0, 0)))
        vw = v[:, -w:] if l >= w else jnp.pad(v, ((0, 0), (0, w - l), (0, 0), (0, 0)))
        if l >= w:
            # roll so that slot i holds position with pos % w == i
            shift = l % w
            kw = jnp.roll(kw, shift, axis=1)
            vw = jnp.roll(vw, shift, axis=1)
        k_cache, v_cache = kw, vw
    else:
        out = flash_attention(
            q, k, v, causal=cfg.causal, prefix_len=cfg.prefix_len,
            block_q=cfg.block_q, block_k=cfg.block_k,
        )
        pad = cache_size - l
        k_cache = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    attn_out = linear(out.reshape(b, l, -1), p["wo"])
    return attn_out, (k_cache, v_cache)


def decode_attn(p, cfg, x1, cache_kv, pos, *, ring: bool):
    """x1: (B, d); cache_kv = (k_cache, v_cache) (B, S, Hkv, D); pos (B,)."""
    b = x1.shape[0]
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = linear(x1, p["wq"]).reshape(b, 1, hq, hd)
    k = linear(x1, p["wk"]).reshape(b, 1, hkv, hd)
    v = linear(x1, p["wv"]).reshape(b, 1, hkv, hd)
    q = rope(q, pos[:, None], cfg.rope_theta)[:, 0]
    k = rope(k, pos[:, None], cfg.rope_theta)[:, 0]
    v = v[:, 0]
    k_cache, v_cache = cache_kv
    s = k_cache.shape[1]
    slot = (pos % s) if ring else pos
    bidx = jnp.arange(b)
    k_cache = k_cache.at[bidx, slot].set(k.astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, slot].set(v.astype(v_cache.dtype))
    out = decode_attention(
        q, k_cache, v_cache, pos + 1,
        window=cfg.sliding_window if not ring else 0, ring=ring,
    )
    return linear(out.reshape(b, -1), p["wo"]), (k_cache, v_cache)


# ---------------------------------------------------------------------------
# full block (attn/ssm + ffn)
# ---------------------------------------------------------------------------


def init_block(key, cfg):
    ks = jax.random.split(key, 4)
    if cfg.family in ("ssm", "hybrid"):
        return {
            "norm_ssm": jnp.zeros((cfg.d_model,), cfg.pdtype),
            "mamba": init_mamba2(ks[0], cfg),
        }
    p = {
        "norm_attn": jnp.zeros((cfg.d_model,), cfg.pdtype),
        "attn": init_attn(ks[0], cfg),
        "norm_ffn": jnp.zeros((cfg.d_model,), cfg.pdtype),
    }
    if cfg.family == "moe":
        p["moe"] = init_moe(ks[1], cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.activation, cfg.pdtype)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.activation, cfg.pdtype)
    return p


def apply_block(p, cfg, h, *, positions, use_window: bool):
    """Forward (no cache). Returns (h, aux) with aux = (lb_loss, z_loss)."""
    zero = jnp.zeros((), jnp.float32)
    if cfg.family in ("ssm", "hybrid"):
        h = h + apply_mamba2(p["mamba"], cfg, rms_norm(h, p["norm_ssm"]))
        return h, (zero, zero)
    h = h + apply_attn(p["attn"], cfg, rms_norm(h, p["norm_attn"]), positions=positions, use_window=use_window)
    x = rms_norm(h, p["norm_ffn"])
    if cfg.family == "moe":
        y, (lb, z) = apply_moe(
            p["moe"], x, num_experts=cfg.num_experts, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor, activation=cfg.activation,
        )
        return h + y, (lb, z)
    return h + apply_mlp(p["mlp"], x, cfg.activation), (zero, zero)


def init_block_cache(cfg, batch: int, cache_size: int, dtype):
    if cfg.family in ("ssm", "hybrid"):
        return init_ssm_cache(cfg, batch, dtype)
    hkv, hd = cfg.num_kv_heads, cfg.hd
    return (
        jnp.zeros((batch, cache_size, hkv, hd), dtype),
        jnp.zeros((batch, cache_size, hkv, hd), dtype),
    )


def prefill_block(p, cfg, h, *, positions, cache_size: int, use_window: bool):
    if cfg.family in ("ssm", "hybrid"):
        out, cache = apply_mamba2(p["mamba"], cfg, rms_norm(h, p["norm_ssm"]), return_state=True)
        return h + out, cache
    a, cache = prefill_attn(
        p["attn"], cfg, rms_norm(h, p["norm_attn"]),
        positions=positions, cache_size=cache_size, use_window=use_window,
    )
    h = h + a
    x = rms_norm(h, p["norm_ffn"])
    if cfg.family == "moe":
        y, _ = apply_moe(
            p["moe"], x, num_experts=cfg.num_experts, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor, activation=cfg.activation,
        )
        return h + y, cache
    return h + apply_mlp(p["mlp"], x, cfg.activation), cache


def decode_block(p, cfg, h1, cache, pos, *, ring: bool):
    if cfg.family in ("ssm", "hybrid"):
        out, cache = decode_mamba2(p["mamba"], cfg, rms_norm(h1, p["norm_ssm"]), cache)
        return h1 + out, cache
    a, cache = decode_attn(p["attn"], cfg, rms_norm(h1, p["norm_attn"]), cache, pos, ring=ring)
    h1 = h1 + a
    x = rms_norm(h1, p["norm_ffn"])
    if cfg.family == "moe":
        y, _ = apply_moe(
            p["moe"], x[:, None, :], num_experts=cfg.num_experts, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor, activation=cfg.activation,
        )
        return h1 + y[:, 0], cache
    return h1 + apply_mlp(p["mlp"], x, cfg.activation), cache
