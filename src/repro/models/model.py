"""Model assembly: init / forward / loss / prefill / decode for every family.

Layer stacks are scanned (`lax.scan` over stacked per-layer params) with
`jax.checkpoint` on the block body, so 96-layer archs lower with bounded HLO.

Hybrid (zamba2-style) models scan uniform *segments* of mamba layers and apply
the **shared** attention block (one set of params, its own KV cache per
application point) between segments — giving each application point a real
cache without allocating attention caches for every mamba layer.

Batch conventions (built by ``repro.data`` / ``input_specs``):
  LM families:  {"tokens": (B, L) int32, "labels": (B, L) int32}
  vlm:          + {"patch_embeds": (B, prefix, frontend_dim)}  (stubbed SigLIP)
  audio:        {"frame_embeds": (B, L, frontend_dim), "labels": (B, L)}
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models.blocks import (
    apply_block,
    decode_block,
    init_block,
    init_block_cache,
    prefill_block,
)
from repro.models.config import ModelConfig, validate
from repro.models.layers import dense_init, maybe_shard_axis, rms_norm


class Model(NamedTuple):
    config: ModelConfig
    init: Any           # (key) -> params
    loss_fn: Any        # (params, batch) -> (loss, metrics)
    forward: Any        # (params, batch, use_window=False) -> logits (B, L, V)
    prefill: Any        # (params, batch, cache_size, use_window) -> (logits_last, cache, pos)
    decode_step: Any    # (params, cache, tokens (B,), pos (B,)) -> (logits, cache)
    init_cache: Any     # (batch, cache_size, dtype) -> cache


# ------------------------------ hybrid layout -------------------------------


def _hybrid_segments(cfg: ModelConfig):
    """Uniform segments of `every` mamba layers, shared attn after each; a
    trailing remainder segment (no shared attn after it) if L % every != 0."""
    every = cfg.shared_attn_every
    nseg, tail = divmod(cfg.num_layers, every)
    return nseg, every, tail


# --------------------------------- builder ----------------------------------


def build_model(cfg: ModelConfig) -> Model:
    validate(cfg)
    if cfg.layer_types:
        from repro.models.pattern import build_pattern_model

        return build_pattern_model(cfg)
    L = cfg.num_layers
    is_hybrid = cfg.family == "hybrid" and cfg.shared_attn_every > 0
    attn_cfg = cfg.with_(family="dense") if is_hybrid else cfg  # shared block = attention

    # ----------------------------- init ------------------------------------
    def init(key):
        keys = jax.random.split(key, 6)
        params = {}
        if cfg.frontend == "none" or cfg.family == "vlm":
            params["embed"] = dense_init(keys[0], (cfg.vocab_size, cfg.d_model), cfg.pdtype, scale=0.02)
        if cfg.frontend != "none":
            params["frontend_proj"] = dense_init(
                keys[1], (cfg.frontend_dim, cfg.d_model), cfg.pdtype
            )
        layer_keys = jax.random.split(keys[2], L)
        params["layers"] = jax.vmap(lambda k: init_block(k, cfg))(layer_keys)
        if is_hybrid:
            params["shared"] = init_block(keys[3], attn_cfg)
        params["final_norm"] = jnp.zeros((cfg.d_model,), cfg.pdtype)
        params["head"] = dense_init(keys[4], (cfg.d_model, cfg.vocab_size), cfg.pdtype, scale=0.02)
        return params

    # --------------------------- embedding ----------------------------------
    def _embed_inputs(params, batch):
        if cfg.family == "audio":
            h = batch["frame_embeds"].astype(cfg.cdtype) @ params["frontend_proj"]
        elif cfg.family == "vlm":
            tok = jnp.take(params["embed"], batch["tokens"], axis=0)
            patch = batch["patch_embeds"].astype(cfg.cdtype) @ params["frontend_proj"]
            h = jnp.concatenate([patch, tok], axis=1)
        else:
            h = jnp.take(params["embed"], batch["tokens"], axis=0)
        h = h.astype(cfg.cdtype)
        if cfg.fsdp_activations:
            # §Perf lever: batch -> *model* (per-layer param gathers replace
            # per-layer tensor-parallel activation all-reduces)
            h = maybe_shard_axis(h, 0)
        return h

    # ---------------------------- forward -----------------------------------
    def _stack_forward(params, h, positions, use_window):
        aux_acc = jnp.zeros((2,), jnp.float32)

        @jax.checkpoint
        def body(carry, lp):
            h, aux = carry
            h, (lb, z) = apply_block(lp, cfg, h, positions=positions, use_window=use_window)
            if cfg.fsdp_activations:
                h = maybe_shard_axis(h, 0)
            return (h, aux + jnp.stack([lb, z])), None

        if is_hybrid:
            nseg, every, tail = _hybrid_segments(cfg)

            def seg_slice(lo, n):
                return jax.tree_util.tree_map(
                    lambda x: jax.lax.slice_in_dim(x, lo, lo + n, axis=0), params["layers"]
                )

            for s in range(nseg):
                (h, aux_acc), _ = jax.lax.scan(body, (h, aux_acc), seg_slice(s * every, every))
                h, _ = apply_block(params["shared"], attn_cfg, h, positions=positions, use_window=use_window)
            if tail:
                (h, aux_acc), _ = jax.lax.scan(body, (h, aux_acc), seg_slice(nseg * every, tail))
        else:
            (h, aux_acc), _ = jax.lax.scan(body, (h, aux_acc), params["layers"])
        return h, aux_acc

    def forward(params, batch, use_window: bool = False):
        h = _embed_inputs(params, batch)
        b, l = h.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(l), (b, l))
        h, _ = _stack_forward(params, h, positions, use_window)
        h = rms_norm(h, params["final_norm"])
        return (h @ params["head"]).astype(jnp.float32)

    # ------------------------------ loss ------------------------------------
    def loss_fn(params, batch, use_window: bool = False):
        h = _embed_inputs(params, batch)
        b, l = h.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(l), (b, l))
        h, aux = _stack_forward(params, h, positions, use_window)
        h = rms_norm(h, params["final_norm"])
        if cfg.family == "vlm":
            h = h[:, cfg.prefix_len :]  # loss on text tokens only
        logits = (h @ params["head"]).astype(jnp.float32)
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        labels = jnp.maximum(labels, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        ce = jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        loss = ce + cfg.router_aux_weight * aux[0] + cfg.router_z_weight * aux[1]
        metrics = {"ce": ce, "lb_loss": aux[0], "z_loss": aux[1]}
        return loss, metrics

    # --------------------------- cache / prefill -----------------------------
    def init_cache(batch_size: int, cache_size: int, dtype=None):
        dtype = dtype or cfg.cdtype
        cache = {
            "layers": jax.vmap(
                lambda _: init_block_cache(cfg, batch_size, cache_size, dtype)
            )(jnp.arange(L)),
            "pos": jnp.zeros((batch_size,), jnp.int32),
        }
        if is_hybrid:
            nseg, _, _ = _hybrid_segments(cfg)
            cache["shared"] = jax.vmap(
                lambda _: init_block_cache(attn_cfg, batch_size, cache_size, dtype)
            )(jnp.arange(nseg))
        return cache

    def prefill(params, batch, cache_size: int, use_window: bool = False):
        h = _embed_inputs(params, batch)
        b, l = h.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(l), (b, l))

        def body(h, lp):
            h, c = prefill_block(lp, cfg, h, positions=positions, cache_size=cache_size, use_window=use_window)
            return h, c

        if is_hybrid:
            nseg, every, tail = _hybrid_segments(cfg)
            caches, shared_caches = [], []

            def seg_slice(lo, n):
                return jax.tree_util.tree_map(
                    lambda x: jax.lax.slice_in_dim(x, lo, lo + n, axis=0), params["layers"]
                )

            for s in range(nseg):
                h, c = jax.lax.scan(body, h, seg_slice(s * every, every))
                caches.append(c)
                h, sc = prefill_block(
                    params["shared"], attn_cfg, h,
                    positions=positions, cache_size=cache_size, use_window=use_window,
                )
                shared_caches.append(sc)
            if tail:
                h, c = jax.lax.scan(body, h, seg_slice(nseg * every, tail))
                caches.append(c)
            layer_cache = jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs, axis=0), *caches
            )
            shared_cache = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs, axis=0), *shared_caches
            )
            cache = {"layers": layer_cache, "shared": shared_cache, "pos": jnp.full((b,), l, jnp.int32)}
        else:
            h, layer_cache = jax.lax.scan(body, h, params["layers"])
            cache = {"layers": layer_cache, "pos": jnp.full((b,), l, jnp.int32)}
        h = rms_norm(h, params["final_norm"])
        logits_last = (h[:, -1] @ params["head"]).astype(jnp.float32)
        return logits_last, cache

    # ------------------------------ decode -----------------------------------
    def decode_step(params, cache, tokens, pos=None, *, ring: bool = False):
        """tokens: (B,) int32 -> (logits (B, V), cache)."""
        if cfg.is_encoder:
            raise ValueError(f"{cfg.name} is encoder-only: no decode step")
        pos = cache["pos"] if pos is None else pos
        h1 = jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdtype)

        def body(h1, xs):
            lp, lc = xs
            h1, lc = decode_block(lp, cfg, h1, lc, pos, ring=ring)
            return h1, lc

        if is_hybrid:
            nseg, every, tail = _hybrid_segments(cfg)

            def seg_slice(tree, lo, n):
                return jax.tree_util.tree_map(
                    lambda x: jax.lax.slice_in_dim(x, lo, lo + n, axis=0), tree
                )

            new_layer_caches, new_shared = [], []
            for s in range(nseg):
                h1, c = jax.lax.scan(
                    body, h1,
                    (seg_slice(params["layers"], s * every, every),
                     seg_slice(cache["layers"], s * every, every)),
                )
                new_layer_caches.append(c)
                sc = jax.tree_util.tree_map(lambda x: x[s], cache["shared"])
                h1, sc = decode_block(params["shared"], attn_cfg, h1, sc, pos, ring=ring)
                new_shared.append(sc)
            if tail:
                h1, c = jax.lax.scan(
                    body, h1,
                    (seg_slice(params["layers"], nseg * every, tail),
                     seg_slice(cache["layers"], nseg * every, tail)),
                )
                new_layer_caches.append(c)
            cache = {
                "layers": jax.tree_util.tree_map(
                    lambda *xs: jnp.concatenate(xs, axis=0), *new_layer_caches
                ),
                "shared": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *new_shared),
                "pos": pos + 1,
            }
        else:
            h1, layer_cache = jax.lax.scan(body, h1, (params["layers"], cache["layers"]))
            cache = {"layers": layer_cache, "pos": pos + 1}
        h1 = rms_norm(h1, params["final_norm"])
        logits = (h1 @ params["head"]).astype(jnp.float32)
        return logits, cache

    return Model(cfg, init, loss_fn, forward, prefill, decode_step, init_cache)
