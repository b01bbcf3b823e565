"""Layer-pattern model: each layer's mixer read from ``cfg.layer_types``
(granite-4.0-h: Mamba-2 or NoPE GQA attention), every mixer followed by the
routed experts plus a shared SwiGLU, with the muP-style multipliers:

    h = embed[tokens] * m_emb
    h += m_res * mixer(rms(h))                 # mamba2 (SSD) | attention
    h += m_res * (moe(rms(h)) + shared(rms(h)))
    logits = rms(h) @ embed.T / logits_scaling  # the head is tied

Layers of one kind are stacked (``params["layers"][kind]``, leading axis
over that kind's layers in order) and each run of consecutive layers of one
kind is a ``lax.scan`` with ``jax.checkpoint`` on the layer and on each of
its two halves, so the backward pass holds one half-layer's activations;
the SSD's intra-chunk term is computed one checkpointed chunk at a time and
attention's key tiles are checkpointed (``chunk_remat``, ``remat_tiles``),
which is what fits 16 clients' training of the full-width block on one chip.
The expert layer is this chip's share (``moe.apply_moe_share``); the loss's
metrics carry the experts every token chose in every layer (``experts``,
``(L, batch, seq, top_k)`` uint8 ids over all ``num_experts``).

Training and scoring only: no cache, prefill or decode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import flash_attention
from repro.models.blocks import init_attn
from repro.models.layers import apply_mlp, dense_init, init_mlp, linear, rms_norm
from repro.models.moe import apply_moe_share, init_moe_share
from repro.models.ssm import apply_mamba2, init_mamba2

KINDS = ("mamba", "attention")


def runs(layer_types) -> list[tuple[str, int, int]]:
    """``(kind, first index in that kind's stack, length)`` of each run of
    consecutive layers of one kind, in layer order."""
    out, seen = [], {k: 0 for k in KINDS}
    for kind in layer_types:
        if out and out[-1][0] == kind:
            out[-1][2] += 1
        else:
            out.append([kind, seen[kind], 1])
        seen[kind] += 1
    return [tuple(r) for r in out]


def init_layer(key, cfg, kind: str):
    k_mix, k_moe, k_mlp = jax.random.split(key, 3)
    d = cfg.d_model
    return {
        "norm_mixer": jnp.zeros((d,), cfg.pdtype),
        "mixer": init_mamba2(k_mix, cfg) if kind == "mamba" else init_attn(k_mix, cfg),
        "norm_ffn": jnp.zeros((d,), cfg.pdtype),
        "moe": init_moe_share(k_moe, cfg),
        "shared": init_mlp(k_mlp, d, cfg.shared_d_ff, "swiglu", cfg.pdtype),
    }


def attention_nope(p, cfg, x):
    """Causal GQA with no positional embedding; scores scaled by
    ``cfg.attention_multiplier`` (or 1/sqrt(head_dim))."""
    b, l, _ = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = linear(x, p["wq"]).reshape(b, l, hq, hd)
    k = linear(x, p["wk"]).reshape(b, l, hkv, hd)
    v = linear(x, p["wv"]).reshape(b, l, hkv, hd)
    out = flash_attention(q, k, v, causal=True, block_q=cfg.block_q,
                          block_k=cfg.block_k,
                          scale=cfg.attention_multiplier or None, remat_tiles=True)
    return linear(out.reshape(b, l, -1), p["wo"])


def _mixer_half(p, cfg, kind, h):
    x = rms_norm(h, p["norm_mixer"], cfg.norm_eps)
    if kind == "mamba":
        mix = apply_mamba2(p["mixer"], cfg, x, chunk_remat=True)
    else:
        mix = attention_nope(p["mixer"], cfg, x)
    return h + mix * cfg.residual_multiplier


def _ffn_half(p, cfg, h):
    x = rms_norm(h, p["norm_ffn"], cfg.norm_eps)
    y, experts = apply_moe_share(p["moe"], x, top_k=cfg.top_k, held=cfg.held_range)
    y = y + apply_mlp(p["shared"], x, "swiglu")
    return h + y * cfg.residual_multiplier, experts.astype(jnp.uint8)


def build_pattern_model(cfg):
    from repro.models.model import Model

    present = [k for k in KINDS if k in cfg.layer_types]
    assert cfg.num_experts <= 256  # ids are kept as uint8

    def init(key):
        k_emb, k_layers = jax.random.split(key)
        layer_keys = dict(zip(KINDS, jax.random.split(k_layers, len(KINDS))))
        params = {
            "embed": dense_init(k_emb, (cfg.vocab_size, cfg.d_model), cfg.pdtype, scale=0.02),
            "layers": {
                kind: jax.vmap(lambda k, kind=kind: init_layer(k, cfg, kind))(
                    jax.random.split(layer_keys[kind], cfg.layer_types.count(kind)))
                for kind in present
            },
            "final_norm": jnp.zeros((cfg.d_model,), cfg.pdtype),
        }
        return params

    def _layers(params, h):
        experts = []
        for kind, first, n in runs(cfg.layer_types):
            mixer = jax.checkpoint(lambda p, h, kind=kind: _mixer_half(p, cfg, kind, h))
            ffn = jax.checkpoint(lambda p, h: _ffn_half(p, cfg, h))

            stack = params["layers"][kind]

            @jax.checkpoint
            def body(h, i, stack=stack):
                lp = jax.tree_util.tree_map(
                    lambda x: jax.lax.dynamic_index_in_dim(x, i, keepdims=False), stack)
                return ffn(lp, mixer(lp, h))

            # index the kind's stack inside the scan: a slice of it taken
            # outside would be a copy of those layers' weights
            h, ids = jax.lax.scan(body, h, jnp.arange(first, first + n))
            experts.append(ids)
        return h, jnp.concatenate(experts, axis=0)

    def _logits(params, tokens):
        h = jnp.take(params["embed"], tokens, axis=0) * cfg.embedding_multiplier
        h, experts = _layers(params, h.astype(cfg.cdtype))
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = (h @ params["embed"].T).astype(jnp.float32) / cfg.logits_scaling
        return logits, experts

    def forward(params, batch, use_window: bool = False):
        del use_window
        return _logits(params, batch["tokens"])[0]

    def loss_fn(params, batch, use_window: bool = False):
        del use_window
        logits, experts = _logits(params, batch["tokens"])
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        labels = jnp.maximum(labels, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        ce = jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return ce, {"ce": ce, "experts": experts}

    def _no_cache(*_a, **_k):
        raise NotImplementedError(f"{cfg.name}: the layer-pattern model has no cache or decode")

    return Model(cfg, init, loss_fn, forward, _no_cache, _no_cache, _no_cache)
