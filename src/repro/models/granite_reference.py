"""Plain float32 reference of the granite-4.0-h block with LoRA adapters:
forward, loss and adapter gradients, written from the published equations
(``transformers``' ``modeling_granitemoehybrid.py``) in straightforward
``jax.numpy`` under ``jax.default_matmul_precision("highest")``.  No scan,
no remat, no chunking and no batching tricks: a Python loop over the layers,
the state-space mixer in its quadratic (attention-like) SSD form over the
whole sequence, attention with its full score matrix, and the expert layer
as a loop over the held experts with explicit top-k gates.

It reads the program's parameter tree (``models/pattern.py``: stacks by
layer kind under ``params["layers"]``) and upcasts every weight to float32
as it is used.  Adapters are merged, ``W + scaling * A @ B``, per layer.

Departures from the published description, each shared with the program:

* norms are parametrised ``(1 + w)`` (``models/layers.rms_norm``) where HF
  stores ``w``; the map is ``w_hf = 1 + w``;
* the expert layer is one chip's share: only the experts ``cfg.held_range``
  contribute, and what the absent experts would add is left out;
* the vocabulary is whatever ``cfg.vocab_size`` holds (a slice of the
  published one in a cut configuration), with the head tied to it;
* no router auxiliary loss: the router is frozen under LoRA fine-tuning;
* the dt clamp of HF's mixer (``time_step_limit = (0, inf)``) is a no-op
  and is left out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f(x):
    return jnp.asarray(x, F32)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + _f(w))


def weight(layer, site, adapters, scaling):
    """Weight ``site`` (a key path into the layer) in f32, merged with its
    adapter when one is given."""
    node, anode = layer, adapters
    for k in site:
        node = node[k]
        anode = anode.get(k) if isinstance(anode, dict) else None
    w = _f(node)
    if isinstance(anode, dict) and set(anode) == {"a", "b"}:
        w = w + scaling * (_f(anode["a"]) @ _f(anode["b"]))
    return w


def ssd(x, dt, A, B, C, D):
    """Mamba-2's state-space layer in its quadratic dual form:
    ``y_i = sum_{j<=i} (C_i . B_j) exp(sum_{t=j+1..i} dt_t A) dt_j x_j + D x_i``.
    x: (b, L, H, P); dt: (b, L, H); B, C: (b, L, N)."""
    L = x.shape[1]
    cs = jnp.cumsum(dt * A, axis=1)                      # (b, L, H)
    seg = cs[:, :, None, :] - cs[:, None, :, :]          # (b, i, j, H)
    causal = (jnp.arange(L)[:, None] >= jnp.arange(L)[None, :])[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bin,bjn->bij", C, B)
    y = jnp.einsum("bij,bijh,bjh,bjhp->bihp", cb, decay, dt, x)
    return y + D[None, None, :, None] * x


def mamba(p, cfg, u, ad, s):
    di, n, h, cw = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_conv_width
    proj = u @ weight(p, ("in_proj",), ad, s)
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * n], proj[..., 2 * di + 2 * n:]
    L = u.shape[1]
    pad = jnp.pad(xbc, ((0, 0), (cw - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + L] * _f(p["conv_w"])[i] for i in range(cw)) + _f(p["conv_b"])
    xbc = jax.nn.silu(conv)
    x, B, C = xbc[..., :di], xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + _f(p["dt_bias"]))
    y = ssd(x.reshape(*x.shape[:2], h, cfg.ssm_head_dim), dt, -jnp.exp(_f(p["A_log"])),
            B, C, _f(p["D"])).reshape(*x.shape)
    g = y * jax.nn.silu(z)
    g = rms(g, p["gate_norm_w"], cfg.norm_eps)
    return g @ weight(p, ("out_proj",), ad, s)


def attention(p, cfg, x, ad, s):
    b, L, _ = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = (x @ weight(p, ("wq",), ad, s)).reshape(b, L, hq, hd)
    k = (x @ weight(p, ("wk",), ad, s)).reshape(b, L, hkv, hd)
    v = (x @ weight(p, ("wv",), ad, s)).reshape(b, L, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    scale = cfg.attention_multiplier or 1.0 / np.sqrt(hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, L, hq * hd)
    return out @ weight(p, ("wo",), ad, s)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f(gate)) * (x @ _f(up))) @ _f(down)


def experts(p, cfg, x):
    """Top-k of all ``num_experts`` router logits, a softmax over the k
    chosen, and the held experts' gated sum (a loop over them)."""
    lo, hi = cfg.held_range
    logits = x @ _f(p["router"])
    vals, idx = jax.lax.top_k(logits, cfg.top_k)
    gates = jax.nn.softmax(vals, axis=-1)
    y = jnp.zeros_like(x)
    for e in range(hi - lo):
        g = jnp.sum(jnp.where(idx == lo + e, gates, 0.0), axis=-1)
        y = y + g[..., None] * swiglu(x, p["gate"][e], p["up"][e], p["down"][e])
    return y


def _layer(stack, i):
    return jax.tree_util.tree_map(lambda a: a[i], stack)


def logits(params, cfg, tokens, adapters=None, scaling=1.0):
    """(b, L) int tokens -> (b, L, vocab) f32 logits."""
    with jax.default_matmul_precision("highest"):
        h = _f(params["embed"])[tokens] * cfg.embedding_multiplier
        seen = {"mamba": 0, "attention": 0}
        for kind in cfg.layer_types:
            i = seen[kind]
            seen[kind] += 1
            lp = _layer(params["layers"][kind], i)
            ad = None if adapters is None else _layer(adapters[kind], i)
            x = rms(h, lp["norm_mixer"], cfg.norm_eps)
            mix = (mamba(lp["mixer"], cfg, x, None if ad is None else ad["mixer"], scaling)
                   if kind == "mamba" else
                   attention(lp["mixer"], cfg, x, None if ad is None else ad["mixer"], scaling))
            h = h + cfg.residual_multiplier * mix
            x = rms(h, lp["norm_ffn"], cfg.norm_eps)
            sh = lp["shared"]
            y = experts(lp["moe"], cfg, x) + swiglu(x, sh["gate"], sh["up"], sh["down"])
            h = h + cfg.residual_multiplier * y
        h = rms(h, params["final_norm"], cfg.norm_eps)
        return (h @ _f(params["embed"]).T) / cfg.logits_scaling


def loss(params, cfg, tokens, labels, adapters=None, scaling=1.0):
    """Mean next-token cross entropy over the positions with ``labels >= 0``."""
    z = logits(params, cfg, tokens, adapters, scaling)
    mask = labels >= 0
    gold = jnp.take_along_axis(z, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    ce = jax.nn.logsumexp(z, axis=-1) - gold
    return jnp.sum(jnp.where(mask, ce, 0.0)) / jnp.maximum(jnp.sum(mask), 1)


def adapter_grads(params, cfg, tokens, labels, adapters, scaling):
    """Gradient of :func:`loss` with respect to the adapter tree."""
    return jax.grad(lambda a: loss(params, cfg, tokens, labels, a, scaling))(adapters)
