"""Memory-efficient attention in pure JAX (flash-style online softmax).

Three entry points:

* ``flash_attention`` — full / causal / prefix-LM masked attention, doubly
  blocked (scan over query blocks, inner scan over key blocks) so the score
  matrix never materializes beyond ``(B, Hkv, G, BQ, BK)``.  O(L^2) compute.
* ``sliding_window_attention`` — sub-quadratic: for each query block a
  *static* ``window + BQ`` key slice is taken (the KV stream is left-padded
  by ``window``), so compute is O(L * window) and lowers with static shapes.
* ``decode_attention`` — single-token query against a KV cache (linear or
  ring-buffer layout).

All support GQA: q heads grouped over kv heads.  Shapes:
  q: (B, Lq, Hq, D)   k, v: (B, Lk, Hkv, D)   with G = Hq // Hkv.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _split_heads(q, num_kv):
    b, l, hq, d = q.shape
    return q.reshape(b, l, num_kv, hq // num_kv, d)


def _block_attend(qb, kb, vb, mask, scale):
    """One (BQ x BK) tile. qb: (B,BQ,Hk,G,D); kb/vb: (B,BK,Hk,D);
    mask: broadcastable to (B,Hk,G,BQ,BK).  Returns (m, l, o) stats."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qb.astype(jnp.float32), kb.astype(jnp.float32))
    s = s * scale + jnp.where(mask, 0.0, NEG_INF)
    m = jnp.max(s, axis=-1)  # (B,Hk,G,BQ)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32))
    return m, l, o


def _merge(m1, l1, o1, m2, l2, o2):
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    return m, l1 * a1 + l2 * a2, o1 * a1[..., None] + o2 * a2[..., None]


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    prefix_len: int = 0,
    q_offset=0,
    block_q: int = 512,
    block_k: int = 512,
    parallel_q: bool = False,
    scale: float | None = None,
    remat_tiles: bool = False,
):
    """Blocked attention with online softmax.  ``prefix_len`` makes the first
    ``prefix_len`` key positions visible to every query (prefix-LM / VLM).

    ``parallel_q`` vectorizes over query blocks (vmap) instead of scanning
    them sequentially and pins the block axis to the *model* mesh axis when
    divisible — sequence parallelism for MQA/low-head-count archs whose head
    axis cannot shard the mesh.  Peak memory rises by the number of in-flight
    q blocks; pick ``block_q = Lq / mesh_model`` so each chip owns one block.
    ``scale`` multiplies the scores (default ``1/sqrt(head_dim)``).
    ``remat_tiles`` checkpoints each key-block step, so the backward pass
    recomputes one tile's scores at a time instead of keeping every tile."""
    b, lq, hq, d = q.shape
    _, lk, hkv, _ = k.shape
    g = hq // hkv
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    # pad to block multiples
    pq = (-lq) % block_q
    pk = (-lk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_k
    qs = _split_heads(qp, hkv).reshape(b, nq, block_q, hkv, g, d).transpose(1, 0, 2, 3, 4, 5)
    ks = kp.reshape(b, nk, block_k, hkv, d)
    vs = vp.reshape(b, nk, block_k, hkv, d)
    scale = 1.0 / jnp.sqrt(d) if scale is None else scale

    kpos_all = jnp.arange(nk * block_k).reshape(nk, block_k)
    valid_k = kpos_all < lk

    def q_block(iq, qb):
        qpos = q_offset + iq * block_q + jnp.arange(block_q)

        def kv_step(carry, inputs):
            m, l, o = carry
            kb, vb, kpos, vk = inputs
            mask = vk[None, :]
            if causal:
                allowed = kpos[None, :] <= qpos[:, None]
                if prefix_len:
                    allowed = allowed | (kpos[None, :] < prefix_len)
                mask = mask & allowed
            mask = mask[None, None, None, :, :]
            m2, l2, o2 = _block_attend(qb, kb, vb, mask, scale)
            return _merge(m, l, o, m2, l2, o2), None

        step = jax.checkpoint(kv_step) if remat_tiles else kv_step
        m0 = jnp.full((b, hkv, g, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, block_q), jnp.float32)
        o0 = jnp.zeros((b, hkv, g, block_q, d), jnp.float32)
        (m, l, o), _ = jax.lax.scan(step, (m0, l0, o0), (ks.transpose(1, 0, 2, 3, 4), vs.transpose(1, 0, 2, 3, 4), kpos_all, valid_k))
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return out  # (B,Hk,G,BQ,D)

    if parallel_q:
        from repro.models.layers import maybe_replicate, maybe_shard_axis

        qs = maybe_shard_axis(qs, 0)  # q-block axis -> "model" when divisible
        ks = maybe_replicate(ks)      # kv small (MQA): gather once, not per block
        vs = maybe_replicate(vs)
        outs = jax.vmap(q_block)(jnp.arange(nq), qs)
        outs = maybe_shard_axis(outs, 0)
    else:
        outs = jax.lax.map(lambda args: q_block(*args), (jnp.arange(nq), qs))
    # (nq, b, hk, g, bq, d) -> (b, nq, bq, hk, g, d) -> (b, l, hq, d)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, nq * block_q, hq, d)
    return out[:, :lq].astype(q.dtype)


def sliding_window_attention(
    q,
    k,
    v,
    *,
    window: int,
    q_offset=0,
    block_q: int = 512,
):
    """Causal attention restricted to the last ``window`` keys — O(L*window).

    KV is left-padded by ``window`` so each query block reads a static slice
    ``[iq*BQ : iq*BQ + window + BQ)`` of the padded stream: no dynamic shapes,
    no fully-masked tiles."""
    b, lq, hq, d = q.shape
    _, lk, hkv, _ = k.shape
    g = hq // hkv
    block_q = min(block_q, lq)
    pq = (-lq) % block_q
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    nq = qp.shape[1] // block_q
    # left-pad by window (so every block's slice start is static & in-bounds)
    # and right-pad by the query padding (so the LAST block's slice does not
    # get clamped by dynamic_slice and silently shift its keys)
    kp = jnp.pad(k, ((0, 0), (window, pq), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, pq), (0, 0), (0, 0)))
    span = window + block_q
    qs = _split_heads(qp, hkv).reshape(b, nq, block_q, hkv, g, d).transpose(1, 0, 2, 3, 4, 5)
    scale = 1.0 / jnp.sqrt(d)

    def q_block(iq, qb):
        start = iq * block_q  # into the padded stream
        kb = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=1)
        qpos = q_offset + iq * block_q + jnp.arange(block_q)
        kpos = q_offset + iq * block_q - window + jnp.arange(span)
        allowed = (
            (kpos[None, :] <= qpos[:, None])
            & (qpos[:, None] - kpos[None, :] < window)
            & (kpos[None, :] >= 0)
        )
        mask = allowed[None, None, None, :, :]
        m, l, o = _block_attend(qb, kb, vb, mask, scale)
        return o / jnp.maximum(l, 1e-30)[..., None]

    outs = jax.lax.map(lambda args: q_block(*args), (jnp.arange(nq), qs))
    # (nq, b, hk, g, bq, d) -> (b, nq, bq, hk, g, d) -> (b, l, hq, d)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, nq * block_q, hq, d)
    return out[:, :lq].astype(q.dtype)


def decode_attention(q1, k_cache, v_cache, cache_len, *, window: int = 0, ring: bool = False):
    """Single-step attention.  q1: (B, Hq, D); caches: (B, S, Hkv, D).

    ``ring=True`` means the cache is a ring buffer of size S=window (slot
    ``pos % S``); masking is by *validity* only since every live slot is
    within the window by construction."""
    b, s, hkv, d = k_cache.shape
    hq = q1.shape[1]
    g = hq // hkv
    qs = q1.reshape(b, hkv, g, d)
    scale = 1.0 / jnp.sqrt(d)
    scores = jnp.einsum(
        "bhgd,bshd->bhgs", qs.astype(jnp.float32), k_cache.astype(jnp.float32)
    ) * scale
    slot = jnp.arange(s)
    if ring:
        # slots holding positions [cache_len - S, cache_len) are valid
        valid = slot[None, :] < jnp.minimum(cache_len, s)[..., None]
    else:
        valid = slot[None, :] < cache_len[..., None]
        if window:
            valid = valid & (slot[None, :] >= cache_len[..., None] - window)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q1.dtype)
