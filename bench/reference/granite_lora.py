"""Plain reference of round 1 of a federated LoRA experiment on the
granite-4.0-h block (``granite4h_small_lora_k16``), written from the
published equations (``transformers``' ``modeling_granitemoehybrid.py``) and
the configuration: a copy of ``repro.models.granite_reference`` with the
round around it, importing nothing of the program.

It draws everything the experiment starts from itself: the frozen base
(bf16, from the run's seed, :func:`init_base`), the adapters (f32, from the
experiment's seed, :func:`init_adapters`) and the clients' shards of the
corpus (:func:`shards`).  The driver hands the base and the adapters to the
program, so both sides start from the reference's draw.

* The model, layer by layer in float32 under ``highest`` matmul precision,
  each layer's bf16 weights upcast as it runs (one copy of the base fits
  beside it): the Mamba-2 mixer in its quadratic SSD form over the whole
  sequence (``y_i = sum_{j<=i} (C_i . B_j) exp(sum_{t=j+1..i} dt_t A) dt_j
  x_j + D x_i``), causal NoPE attention with its full score matrix, the held
  experts as a loop with explicit top-k gates, the shared SwiGLU, the
  muP-style multipliers, the tied head over the vocabulary slice; adapters
  merged, ``W + (alpha/r) A @ B``.
* Routing: the experts each token goes to are the program's choices where
  they are given (``choices``), the gates a softmax over the reference's own
  float32 logits at those experts.  A bf16 program and this float32
  reference part on near-tied router logits, and one token sent to another
  expert moves the round's update by more than all the rounding does; so
  the reference follows the program's choices and counts, separately, how
  many of them its own top-k would not have made (``choices_outside_top_k``).
  Without ``choices`` it routes by its own top-k and returns those choices.
* Gradients layer by layer (``jax.vjp`` of one layer at a time, backwards
  from the loss), so no layer's activations outlive it.
* Each honest client's local SGD with momentum over its minibatch draw, the
  byzantine rows ``w_t + N(0, scale^2 I)``, drawn from the streams the
  program's documented key scheme names (per-client keys folded from the
  seed and ``round * K + client``; the attack key folded from the round,
  then the leaf's index in flatten order, then the client).
* AFA's screening in float64 on the host from the rows' Gram matrix
  (computed at ``highest``), the weighted mean of the kept rows in float64
  (the adapters after round 1), and the Beta reputation's blocking rule
  exact in float64 ``scipy``.

Departures from the published description, each shared with the program:
norms parametrised ``(1 + w)``; one chip's share of the experts (what the
absent experts add is left out); the vocabulary slice; no router auxiliary
loss.

``precision`` selects the adapter path's arithmetic: ``float32`` (the
configuration's: adapters, their momentum and their updates in f32) or
``bfloat16`` (the control: adapters and momentum held in bf16, their matmuls
on bf16 operands).  The frozen base is computed in f32 in both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from scipy.special import betainc

BATCH_STREAM = 0x0B47C4
EPS = 1e-12
TIE_RTOL = 2.0**-20
F32 = jnp.float32
BF16 = jnp.bfloat16


def _f(x):
    return jnp.asarray(x, F32)


# ---------------------------------------------------------------------------
# what the experiment starts from
# ---------------------------------------------------------------------------


def model_dims(cfg: dict) -> dict:
    """The sizes the reference reads, from the configuration file."""
    d = cfg["hidden_size"]
    return dict(
        d_model=d, d_inner=cfg["mamba_expand"] * d, d_state=cfg["mamba_d_state"],
        n_heads=cfg["mamba_n_heads"], d_head=cfg["mamba_d_head"], d_conv=cfg["mamba_d_conv"],
        q_heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=d // cfg["num_attention_heads"],
        attention_multiplier=cfg["attention_multiplier"], eps=cfg["rms_norm_eps"],
        experts_held=tuple(cfg["experts_held"]), n_experts=cfg["experts_published"],
        top_k=cfg["num_experts_per_tok"], d_ff=cfg["intermediate_size"],
        shared_d_ff=cfg["shared_intermediate_size"], vocab=cfg["vocab_size"],
        residual=cfg["residual_multiplier"], embedding=cfg["embedding_multiplier"],
        logits_scaling=cfg["logits_scaling"], layer_types=tuple(cfg["layer_types"]),
    )


def _normal(key, shape, fan_in):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, F32) / np.sqrt(fan_in)).astype(BF16)


def _layer_init(key, m, kind):
    d, di, n, h = m["d_model"], m["d_inner"], m["d_state"], m["n_heads"]
    lo, hi = m["experts_held"]
    e, f, fs = hi - lo, m["d_ff"], m["shared_d_ff"]
    ks = iter(jax.random.split(key, 16))
    if kind == "mamba":
        cw, xbc = m["d_conv"], di + 2 * n
        # dt at init log-uniform in [1e-3, 1e-1], stored as softplus^-1 (HF's init)
        dt = jnp.exp(jax.random.uniform(next(ks), (h,), F32, np.log(1e-3), np.log(1e-1)))
        mixer = {
            "in_proj": _normal(next(ks), (d, 2 * di + 2 * n + h), d),
            "conv_w": _normal(next(ks), (cw, xbc), cw),
            "conv_b": jnp.zeros((xbc,), BF16),
            "A_log": jnp.log(jnp.arange(1, h + 1, dtype=F32)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((h,), F32),
            "out_proj": _normal(next(ks), (di, d), di),
            "gate_norm_w": jnp.zeros((di,), BF16),
        }
    else:
        hq, hkv, hd = m["q_heads"], m["kv_heads"], m["head_dim"]
        mixer = {"wq": _normal(next(ks), (d, hq * hd), d),
                 "wk": _normal(next(ks), (d, hkv * hd), d),
                 "wv": _normal(next(ks), (d, hkv * hd), d),
                 "wo": _normal(next(ks), (hq * hd, d), hq * hd)}
    return {
        "norm_mixer": jnp.zeros((d,), BF16),
        "mixer": mixer,
        "norm_ffn": jnp.zeros((d,), BF16),
        "moe": {"router": _normal(next(ks), (d, m["n_experts"]), d),
                "gate": _normal(next(ks), (e, d, f), d),
                "up": _normal(next(ks), (e, d, f), d),
                "down": _normal(next(ks), (e, f, d), f)},
        "shared": {"gate": _normal(next(ks), (d, fs), d), "up": _normal(next(ks), (d, fs), d),
                   "down": _normal(next(ks), (fs, d), fs)},
    }


def init_base(seed: int, m: dict) -> dict:
    """The frozen base, drawn on the device from ``seed`` in the layout the
    program reads (per-kind stacks under ``layers``), bf16 weights (the
    mixer's ``A_log``, ``dt_bias`` and ``D`` in f32)."""
    mkey = _freeze(m)

    @jax.jit
    def draw(key):
        k_emb, k_layers = jax.random.split(key)
        counts = {kind: m["layer_types"].count(kind) for kind in ("mamba", "attention")}
        layer_keys = dict(zip(counts, jax.random.split(k_layers, 2)))
        return {
            "embed": (0.02 * jax.random.truncated_normal(
                k_emb, -2.0, 2.0, (m["vocab"], m["d_model"]), F32)).astype(BF16),
            "layers": {kind: jax.vmap(lambda k, kind=kind: _layer_init(k, dict(mkey), kind))(
                jax.random.split(layer_keys[kind], n)) for kind, n in counts.items() if n},
            "final_norm": jnp.zeros((m["d_model"],), BF16),
        }

    return draw(jax.random.PRNGKey(seed))


def init_adapters(seed: int, base: dict, targets, rank: int) -> dict:
    """LoRA factors at every target leaf of the base's layer stacks, drawn
    from ``seed``: ``a`` ~ N(0, 1/d_in) ``(L, d_in, r)``, ``b`` = 0
    ``(L, r, d_out)``, f32."""
    sites = []

    def walk(node, path):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(node[k], path + (k,))
        elif path[-1] in targets and node.ndim == 3:
            sites.append((path, node.shape))

    walk(base["layers"], ())
    out: dict = {}
    for key, (path, (L, d_in, d_out)) in zip(
            jax.random.split(jax.random.PRNGKey(seed), len(sites)), sites):
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = {"a": jax.random.normal(key, (L, d_in, rank), F32) / np.sqrt(d_in),
                          "b": jnp.zeros((L, rank, d_out), F32)}
    return out


def shards(corpus: np.ndarray, clients: int, per_client: int, n_test: int, seed: int) -> dict:
    """One experiment's data: ``seed`` permutes the corpus of ``(N, seq +
    1)`` sequences; the first ``clients * per_client`` are the clients'
    shards, the next ``n_test`` the held-out batch; inputs ``s[:-1]``,
    next-token labels ``s[1:]``."""
    pick = corpus[np.random.default_rng(seed).permutation(len(corpus))[
        :clients * per_client + n_test]]
    train = pick[:clients * per_client].reshape(clients, per_client, -1)
    return dict(x=train[..., :-1], y=train[..., 1:],
                lengths=np.full((clients,), per_client, np.int64),
                x_test=pick[clients * per_client:, :-1], y_test=pick[clients * per_client:, 1:])


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + _f(w))


def weight(node, name, anode, scaling, adt):
    """The f32 weight ``node[name]`` merged with its adapter, whose factors
    are read in ``adt`` (f32, or bf16 for the control)."""
    w = _f(node[name])
    sub = anode.get(name) if isinstance(anode, dict) else None
    if isinstance(sub, dict):
        a, b = sub["a"].astype(adt), sub["b"].astype(adt)
        w = w + scaling * _f(jnp.matmul(a, b, preferred_element_type=F32))
    return w


def ssd(x, dt, A, B, C, D):
    """Quadratic (dual) form of the state-space layer.  x: (b, L, H, P)."""
    L = x.shape[1]
    cs = jnp.cumsum(dt * A, axis=1)
    seg = cs[:, :, None, :] - cs[:, None, :, :]
    causal = (jnp.arange(L)[:, None] >= jnp.arange(L)[None, :])[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bin,bjn->bij", C, B)
    y = jnp.einsum("bij,bijh,bjh,bjhp->bihp", cb, decay, dt, x)
    return y + D[None, None, :, None] * x


def mamba(p, m, u, ad, s, adt):
    di, n, h, cw = m["d_inner"], m["d_state"], m["n_heads"], m["d_conv"]
    proj = u @ weight(p, "in_proj", ad, s, adt)
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * n], proj[..., 2 * di + 2 * n:]
    L = u.shape[1]
    pad = jnp.pad(xbc, ((0, 0), (cw - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[:, i:i + L] * _f(p["conv_w"])[i] for i in range(cw))
                      + _f(p["conv_b"]))
    x, B, C = xbc[..., :di], xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + _f(p["dt_bias"]))
    y = ssd(x.reshape(*x.shape[:2], h, m["d_head"]), dt, -jnp.exp(_f(p["A_log"])),
            B, C, _f(p["D"])).reshape(*x.shape)
    g = rms(y * jax.nn.silu(z), p["gate_norm_w"], m["eps"])
    return g @ weight(p, "out_proj", ad, s, adt)


def attention(p, m, x, ad, s, adt):
    b, L, _ = x.shape
    hq, hkv, hd = m["q_heads"], m["kv_heads"], m["head_dim"]
    q = (x @ weight(p, "wq", ad, s, adt)).reshape(b, L, hq, hd)
    k = jnp.repeat((x @ weight(p, "wk", ad, s, adt)).reshape(b, L, hkv, hd), hq // hkv, axis=2)
    v = jnp.repeat((x @ weight(p, "wv", ad, s, adt)).reshape(b, L, hkv, hd), hq // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * m["attention_multiplier"]
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, L, hq * hd)
    return out @ weight(p, "wo", ad, s, adt)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f(gate)) * (x @ _f(up))) @ _f(down)


def experts(p, m, x, choices=None):
    """The held experts' part: gates a softmax over the top-k logits (or
    over the logits of the given ``choices``), a loop over the held experts.
    Returns ``(y, choices, outside)``: ``outside`` the number of the
    choices that this layer's own top-k does not hold."""
    lo, hi = m["experts_held"]
    logits = x @ _f(p["router"])
    own = jax.lax.top_k(logits, m["top_k"])[1]
    idx = own if choices is None else choices.astype(jnp.int32)
    gates = jax.nn.softmax(jnp.take_along_axis(logits, idx, axis=-1), axis=-1)
    y = jnp.zeros_like(x)
    for e in range(hi - lo):
        g = jnp.sum(jnp.where(idx == lo + e, gates, 0.0), axis=-1)
        y = y + g[..., None] * swiglu(x, p["gate"][e], p["up"][e], p["down"][e])
    inside = (idx[..., :, None] == own[..., None, :]).any(axis=-1)
    return y, idx, jnp.sum(~inside)


def _freeze(m):
    return tuple(sorted(m.items()))


@functools.lru_cache(maxsize=16)
def _layer_fns(kind, mkey, scaling, adt_name, forced):
    """Jitted (forward, vjp) of one layer of ``kind``; ``forced``: routed by
    given choices."""
    m, adt = dict(mkey), jnp.dtype(adt_name)

    def layer(lp, ad, h, choices):
        with jax.default_matmul_precision("highest"):
            x = rms(h, lp["norm_mixer"], m["eps"])
            mix = (mamba if kind == "mamba" else attention)(
                lp["mixer"], m, x, None if ad is None else ad["mixer"], scaling, adt)
            h = h + m["residual"] * mix
            x = rms(h, lp["norm_ffn"], m["eps"])
            sh = lp["shared"]
            y, idx, outside = experts(lp["moe"], m, x, choices if forced else None)
            y = y + swiglu(x, sh["gate"], sh["up"], sh["down"])
            return h + m["residual"] * y, (idx, outside)

    def vjp(lp, ad, h, choices, g):
        return jax.vjp(lambda a, x: layer(lp, a, x, choices)[0], ad, h)[1](g)

    return jax.jit(layer), jax.jit(vjp)


@functools.lru_cache(maxsize=4)
def _head_fns(mkey):
    m = dict(mkey)

    def logits(embed, norm, h):
        with jax.default_matmul_precision("highest"):
            return (rms(h, norm, m["eps"]) @ _f(embed).T) / m["logits_scaling"]

    def loss(embed, norm, h, labels):
        z = logits(embed, norm, h)
        gold = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(z, axis=-1) - gold)

    def embed_fn(embed, tokens):
        return _f(embed)[tokens] * m["embedding"]

    return jax.jit(embed_fn), jax.jit(jax.value_and_grad(loss, argnums=2))


def _at(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def _walk(m):
    """``(kind, index in its stack)`` of each layer, in order."""
    seen = {"mamba": 0, "attention": 0}
    for kind in m["layer_types"]:
        yield kind, seen[kind]
        seen[kind] += 1


def adapter_grads(base, m, tokens, labels, adapters, scaling, adt_name="float32",
                  choices=None):
    """Loss, its gradient with respect to the adapter tree (one layer's vjp
    at a time from the loss backwards), the choices each layer routed by
    ``(L, b, seq, k)`` and how many of them lie outside the reference's own
    top-k.  ``choices``: the program's, ``(L, b, seq, k)``, or None."""
    mkey = _freeze(m)
    forced = choices is not None
    embed, head_grad = _head_fns(mkey)
    hs, routed, outside, h = [], [], 0, embed(base["embed"], tokens)
    for j, (kind, i) in enumerate(_walk(m)):
        fwd, _ = _layer_fns(kind, mkey, scaling, adt_name, forced)
        hs.append(h)
        c = jnp.asarray(choices[j]) if forced else None
        h, (idx, out) = fwd(_at(base["layers"][kind], i), _at(adapters[kind], i), h, c)
        routed.append(idx)
        outside += int(out)
    loss, g = head_grad(base["embed"], base["final_norm"], h, labels)
    grads = {kind: [] for kind in adapters}
    for j, ((kind, i), h_in) in reversed(list(enumerate(zip(_walk(m), hs)))):
        _, vjp = _layer_fns(kind, mkey, scaling, adt_name, True)
        g_ad, g = vjp(_at(base["layers"][kind], i), _at(adapters[kind], i), h_in, routed[j], g)
        grads[kind].insert(0, g_ad)
    grads = {k: jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *v) for k, v in grads.items()}
    return loss, grads, np.stack([np.asarray(r) for r in routed]), outside


# ---------------------------------------------------------------------------
# round 1
# ---------------------------------------------------------------------------


def flat_row(tree):
    """A proposal tree as one float64 row, leaves in flatten order."""
    return np.concatenate([np.asarray(l, np.float64).ravel()
                           for l in jax.tree_util.tree_leaves(tree)])


def round_one(base, adapters0, data, seed, m, fed, choices=None, precision="float32"):
    """Round 1 of the experiment on ``seed``: each honest client's local SGD
    with momentum, the byzantine rows ``w_0 + N(0, scale^2)``, AFA's
    screening and the weighted mean.  ``fed``: clients, byzantine, steps,
    batch, lr, momentum, scaling, byzantine_scale, alpha0, beta0, xi0,
    delta_xi, afa_max_rounds.  ``choices``: the program's experts of round 1
    ``(K, steps, L, batch, seq, k)``, or None (own routing).

    Returns float64 ``rows`` ``(K, D)``, ``w0``, ``w1`` (the adapters after
    the round), ``kept``, ``sims``, the ``choices`` routed by (as the
    program's; zero in the byzantine rows) and ``choices_outside_top_k``
    (count) over ``choices_total``."""
    adt = "bfloat16" if precision == "bfloat16" else "float32"
    K, n_bad, S = fed["clients"], fed["byzantine"], fed["steps"]
    flat0 = jax.tree_util.tree_leaves(adapters0)
    bkey = jax.random.fold_in(jax.random.PRNGKey(seed), BATCH_STREAM)
    akey = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(adt), t)
    rows, used, outside, total = [], {}, 0, 0
    for k in range(K):
        if k < n_bad:
            rows.append(np.concatenate([np.asarray(l + fed["byzantine_scale"] * jax.random.normal(
                jax.random.fold_in(jax.random.fold_in(akey, i), k), l.shape, F32),
                np.float64).ravel() for i, l in enumerate(flat0)]))
            continue
        idx = np.asarray(jax.random.randint(
            jax.random.fold_in(bkey, k), (S, fed["batch"]), 0, jnp.int32(int(data["lengths"][k]))))
        a = cast(adapters0)
        mu = jax.tree_util.tree_map(jnp.zeros_like, a)
        used[k] = []
        for s in range(S):
            _, g, routed, out = adapter_grads(
                base, m, jnp.asarray(data["x"][k][idx[s]]), jnp.asarray(data["y"][k][idx[s]]),
                a, fed["scaling"], adt, None if choices is None else choices[k][s])
            used[k].append(routed)
            outside += out
            total += routed.size
            mu = jax.tree_util.tree_map(
                lambda v, gr: (fed["momentum"] * _f(v) + gr).astype(adt), mu, g)
            a = jax.tree_util.tree_map(
                lambda p, v: (_f(p) - fed["lr"] * _f(v)).astype(adt), a, mu)
        rows.append(flat_row(a))
    rows = np.stack(rows)
    # round 1: every client live, reputation at its prior mean
    pn = fed["alpha0"] / (fed["alpha0"] + fed["beta0"]) * np.asarray(data["lengths"], np.float64)
    kept, w, sims = afa_screen(gram(rows), pn, fed["xi0"], fed["delta_xi"],
                               fed["afa_max_rounds"])
    routed = np.zeros((K, S) + used[n_bad][0].shape, np.uint8)
    for k, steps in used.items():
        routed[k] = np.stack(steps)
    return dict(rows=rows, w0=flat_row(adapters0), w1=w @ rows, kept=kept, sims=sims,
                choices=routed, choices_outside_top_k=outside, choices_total=total)


def gram(rows: np.ndarray) -> np.ndarray:
    r = jnp.asarray(rows, F32)
    return np.asarray(jax.jit(lambda r: jnp.matmul(r, r.T, precision="highest"))(r),
                      np.float64)


def afa_screen(G, pn, xi0=2.0, delta_xi=0.5, max_rounds=8):
    """Algorithm 1's screening loop on the Gram matrix of the live rows, in
    float64.  Returns ``(kept, weights, similarities)``."""
    G = np.asarray(G, np.float64)
    pn = np.asarray(pn, np.float64)
    norms = np.sqrt(np.maximum(np.diag(G), 0.0))
    kept = np.ones(len(pn), bool)
    xi, s = float(xi0), np.zeros(len(pn))
    for _ in range(max_rounds):
        c = np.where(kept, pn, 0.0)
        c = c / max(c.sum(), EPS)
        gc = G @ c
        s = gc / (np.maximum(norms, EPS) * np.sqrt(max(c @ gc, EPS)))
        sk = s[kept]
        mean, med = sk.mean(), np.median(sk)
        sd = max(sk.std(), TIE_RTOL * abs(med))
        if mean < med:
            bad = kept & (s < med - xi * sd)
        else:
            bad = kept & (s > med + xi * sd)
        if (kept & ~bad).sum() < 2:
            bad[:] = False
        kept &= ~bad
        xi += delta_xi
        if not bad.any():
            break
    c = np.where(kept, pn, 0.0)
    return kept, c / max(c.sum(), EPS), s


def blocked_rounds(kept, alpha0, beta0, delta):
    """The Beta reputation over given per-round kept sets ``(T, K)``: each
    client's 1-indexed blocking round (-1: never), the rule
    ``I_0.5(alpha, beta) > delta`` evaluated exactly."""
    T, K = kept.shape
    alpha, beta = np.full(K, float(alpha0)), np.full(K, float(beta0))
    blocked, out = np.zeros(K, bool), np.full(K, -1, np.int64)
    for r in range(T):
        live = ~blocked
        alpha += live & kept[r]
        beta += live & ~kept[r]
        newly = ~blocked & (betainc(alpha, beta, 0.5) > delta)
        blocked |= newly
        out[newly] = r + 1
    return out
