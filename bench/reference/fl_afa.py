"""Plain reference of the paper's federated round (arXiv:1909.05125,
Algorithm 1 with the Beta reputation of eqs. 3-6), written from the paper
and the configuration alone: it imports nothing of the program and takes
nothing the program made.  It draws the same random streams the program's
documented key scheme names (per-client keys folded from the seed and
``round * K + client``, the attack key folded from the round), so that the
same seed gives the same experiment.

* local update: SGD with momentum over ``epochs * n_k / batch`` minibatches
  drawn with replacement, dropout on the hidden layers, softmax cross
  entropy; honest live clients only (byzantine clients send
  ``w_t + N(0, scale^2 I)`` and blocked clients send nothing);
* screening and aggregate: the Gram matrix of the live rows on the device,
  Algorithm 1's screening loop on the host in float64, the weighted mean
  of the kept rows on the device;
* reputation: Beta(alpha, beta) posteriors, blocking when
  ``I_0.5(alpha, beta) > delta``, the paper's rule, evaluated exactly
  (float64 ``scipy`` on the host).

``precision`` selects the local update's arithmetic: ``default`` (float32
params, activations and momentum, matmuls at the platform's default
precision: what the configuration states, the reference) or ``bfloat16``
(bf16 params, activations and momentum: the control).  Aggregation
contractions run at full float32 precision in both, as the configuration
states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from scipy.special import betainc

CLIENT_STREAM = 0xC11E47
BATCH_STREAM = 0x0B47C4
EPS = 1e-12
TIE_RTOL = 2.0**-20

_P = jax.lax.Precision
# (local-training matmul precision, training dtype, aggregation precision)
PRECISIONS = {
    "default": (_P.DEFAULT, jnp.float32, _P.HIGHEST),
    "bfloat16": (_P.DEFAULT, jnp.bfloat16, _P.HIGHEST),
}


def leaf_names(sizes) -> list[str]:
    """Parameter names in the order rows are packed (sorted, as a pytree
    of a dict flattens)."""
    n = len(sizes) - 1
    return sorted([f"w{i}" for i in range(n)] + [f"b{i}" for i in range(n)])


def init_params(seed: int, sizes) -> dict:
    keys = jax.random.split(jax.random.PRNGKey(seed), len(sizes) - 1)
    p = {}
    for i, (k, a, b) in enumerate(zip(keys, sizes[:-1], sizes[1:])):
        p[f"w{i}"] = jax.random.normal(k, (a, b)) * jnp.sqrt(2.0 / a)
        p[f"b{i}"] = jnp.zeros((b,), jnp.float32)
    return p


def pack_host(params: dict, sizes) -> np.ndarray:
    """:func:`pack` of host arrays, in float64, on the host."""
    return np.concatenate([np.asarray(params[n], np.float64).ravel()
                           for n in leaf_names(sizes)])


def pack(params: dict, sizes) -> jnp.ndarray:
    """``(..., D)`` rows from a (possibly stacked) params dict."""
    names = leaf_names(sizes)
    lead = params[names[0]].shape[:-1]
    return jnp.concatenate(
        [params[n].reshape(lead + (-1,)).astype(jnp.float32) for n in names],
        axis=-1)


def unpack(row, sizes) -> dict:
    out, off = {}, 0
    shapes = {f"w{i}": (a, b) for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:]))}
    shapes.update({f"b{i}": (b,) for i, b in enumerate(sizes[1:])})
    for n in leaf_names(sizes):
        size = int(np.prod(shapes[n]))
        out[n] = row[off:off + size].reshape(shapes[n])
        off += size
    return out


def _logits(p, x, key, prec, n_layers, dropout):
    h = x
    for i in range(n_layers):
        h = jnp.matmul(h, p[f"w{i}"], precision=prec) + p[f"b{i}"]
        if i < n_layers - 1:
            h = jnp.where(h >= 0, h, 0.1 * h)
            if key is not None:
                key, sub = jax.random.split(key)
                keep = jax.random.bernoulli(sub, 1.0 - dropout, h.shape)
                h = jnp.where(keep, h / (1.0 - dropout), 0.0).astype(h.dtype)
    return h


def _gather(stack, idx):
    """``stack[k, idx[k]]`` for every row ``k``, moving the float32 rows as
    32-bit words: a float gather lets the TPU compiler narrow the whole
    stack to bf16 ahead of it, a fusion that halts a v5e (it reads out of
    range of the copy it keeps in VMEM).  Moving bits is exact."""
    words = jax.lax.bitcast_convert_type(stack, jnp.uint32)
    got = jax.vmap(lambda w, i: w[i])(words, idx)
    return jax.lax.bitcast_convert_type(got, jnp.float32)


@functools.lru_cache(maxsize=8)
def _train_fn(sizes, steps, batch, lr, momentum, dropout, precision):
    prec, dt, _ = PRECISIONS[precision]
    n_layers = len(sizes) - 1

    def loss(p, x, y, key):
        z = _logits(p, x, key, prec, n_layers, dropout).astype(jnp.float32)
        gold = jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(z, axis=-1) - gold)

    def one_client(p, xs, ys, key):
        mu = jax.tree_util.tree_map(jnp.zeros_like, p)

        def step(carry, mb):
            p, mu, key = carry
            key, sub = jax.random.split(key)
            g = jax.grad(loss)(p, mb[0], mb[1], sub if dropout else None)
            mu = jax.tree_util.tree_map(lambda m, g: momentum * m + g, mu, g)
            p = jax.tree_util.tree_map(lambda a, m: a - lr * m, p, mu)
            return (p, mu, key), None

        (p, _, _), _ = jax.lax.scan(step, (p, mu, key), (xs, ys))
        return p

    @jax.jit
    def train(params, shard_x, shard_y, lengths, ids, seed, rnd, num_clients):
        base = jax.random.PRNGKey(seed)
        offs = rnd.astype(jnp.uint32) * num_clients.astype(jnp.uint32) + ids
        bbase = jax.random.fold_in(base, BATCH_STREAM)
        cbase = jax.random.fold_in(base, CLIENT_STREAM)
        bkeys = jax.vmap(lambda o: jax.random.fold_in(bbase, o))(offs)
        ckeys = jax.vmap(lambda o: jax.random.fold_in(cbase, o))(offs)
        idx = jax.vmap(lambda k, n: jax.random.randint(k, (steps, batch), 0, n))(
            bkeys, lengths)
        xs = _gather(shard_x, idx).astype(dt)
        ys = jax.vmap(lambda y, i: y[i])(shard_y, idx)
        p = jax.tree_util.tree_map(lambda a: a.astype(dt), params)
        out = jax.vmap(one_client, in_axes=(None, 0, 0, 0))(p, xs, ys, ckeys)
        return pack(out, sizes)

    return train


@functools.lru_cache(maxsize=8)
def _byzantine_fn(sizes, scale):
    names = leaf_names(sizes)

    @jax.jit
    def rows(params, ids, seed, rnd):
        akey = jax.random.fold_in(jax.random.PRNGKey(seed), rnd)
        cols = []
        for i, n in enumerate(names):
            leaf = params[n]
            lkey = jax.random.fold_in(akey, i)
            noise = jax.vmap(lambda c: scale * jax.random.normal(
                jax.random.fold_in(lkey, c), leaf.shape, jnp.float32))(ids)
            cols.append((leaf[None] + noise).reshape(ids.shape[0], -1))
        return jnp.concatenate(cols, axis=1)

    return rows


@functools.lru_cache(maxsize=8)
def _agg_fns(precision):
    agg_prec = PRECISIONS[precision][2]
    gram = jax.jit(lambda r: jnp.matmul(r, r.T, precision=agg_prec))
    wsum = jax.jit(lambda w, r: jnp.matmul(w[None], r, precision=agg_prec)[0])
    return gram, wsum


@functools.lru_cache(maxsize=8)
def _error_fn(sizes, precision):
    prec, dt, _ = PRECISIONS[precision]

    @jax.jit
    def err(params, x, y):
        p = jax.tree_util.tree_map(lambda a: a.astype(dt), params)
        z = _logits(p, x.astype(dt), None, prec, len(sizes) - 1, 0.0)
        return jnp.mean((jnp.argmax(z, axis=-1) != y).astype(jnp.float32))

    return err


def afa_screen(gram, pn, xi0=2.0, delta_xi=0.5, max_rounds=8):
    """Algorithm 1's screening loop on the Gram matrix of the live rows, in
    float64.  ``pn`` are the rows' reputation x sample-count weights.
    Returns ``(kept, weights, similarities)`` over the live rows."""
    G = np.asarray(gram, np.float64)
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


class Reputation:
    """Beta posteriors, the blocked set, and the 1-indexed round of each
    client's blocking (-1: never)."""

    def __init__(self, K, alpha0, beta0, delta):
        self.alpha = np.full(K, float(alpha0))
        self.beta = np.full(K, float(beta0))
        self.blocked = np.zeros(K, bool)
        self.blocked_round = np.full(K, -1, np.int64)
        self.delta = delta

    def p_good(self):
        return self.alpha / (self.alpha + self.beta)

    def absorb(self, rnd, live, kept):
        """``live``/``kept``: (K,) bool of this round's participants and the
        rows screening kept."""
        self.alpha += live & kept
        self.beta += live & ~kept
        newly = ~self.blocked & (betainc(self.alpha, self.beta, 0.5) > self.delta)
        self.blocked |= newly
        self.blocked_round[newly] = rnd + 1


def run_experiment(data: dict, cfg: dict, seed: int, rounds: int,
                   precision: str = "default", client_block: int = 128) -> dict:
    """One simulated experiment of ``rounds`` rounds on seed ``seed``.
    Returns per-round test error (percent), kept sets and similarities
    (``(T, K)``, blocked clients read False / 0), each client's blocked
    round, and the initial and final params as float64 vectors."""
    sizes = tuple(cfg["model"]["sizes"])
    K = cfg["clients"]
    n_bad = int(round(cfg["bad_frac"] * K))
    bad = np.arange(K) < n_bad
    x, y = data["x_train"], data["y_train"]
    parts = np.array_split(np.random.default_rng(seed).permutation(len(x)), K)
    lengths = np.array([len(p) for p in parts], np.int32)
    n_max = int(lengths.max())
    steps = cfg["local_epochs"] * max(int(lengths.mean()) // cfg["batch_size"], 1)
    batch = min(cfg["batch_size"], n_max)
    shard_x = np.zeros((K, n_max, x.shape[1]), np.float32)
    shard_y = np.zeros((K, n_max), np.int32)
    for k, p in enumerate(parts):
        shard_x[k, :len(p)], shard_y[k, :len(p)] = x[p], y[p]

    train = _train_fn(sizes, steps, batch, float(cfg["lr"]), float(cfg["momentum"]),
                      float(cfg["model"]["dropout"]), precision)
    byz = _byzantine_fn(sizes, float(cfg["byzantine_scale"]))
    gram, wsum = _agg_fns(precision)
    err = _error_fn(sizes, precision)
    x_test, y_test = jnp.asarray(data["x_test"]), jnp.asarray(data["y_test"])

    params = init_params(seed, sizes)
    p0 = np.asarray(pack(params, sizes), np.float64)
    rep = Reputation(K, cfg["alpha0"], cfg["beta0"], cfg["delta_block"])
    seed_u = jnp.uint32(seed)
    out_err = np.zeros(rounds)
    out_kept = np.zeros((rounds, K), bool)
    out_sims = np.zeros((rounds, K), np.float32)
    for r in range(rounds):
        live = ~rep.blocked
        honest = np.nonzero(live & ~bad)[0]
        forged = np.nonzero(live & bad)[0]
        blocks = []
        for i in range(0, len(honest), client_block):
            ids = honest[i:i + client_block]
            blocks.append(train(
                params, jnp.asarray(shard_x[ids]), jnp.asarray(shard_y[ids]),
                jnp.asarray(lengths[ids]), jnp.asarray(ids, jnp.uint32),
                seed_u, jnp.int32(r), jnp.int32(K)))
        if len(forged):
            blocks.append(byz(params, jnp.asarray(forged, jnp.uint32), seed_u,
                              jnp.int32(r)))
        order = np.concatenate([honest, forged])
        rows = jnp.concatenate(blocks)[np.argsort(order)]
        ids = np.sort(order)
        kept, w, s = afa_screen(
            gram(rows), rep.p_good()[ids] * lengths[ids],
            cfg["xi0"], cfg["delta_xi"], cfg.get("afa_max_rounds", 8))
        params = unpack(wsum(jnp.asarray(w, jnp.float32), rows), sizes)
        kept_k = np.zeros(K, bool)
        kept_k[ids] = kept
        rep.absorb(r, live, kept_k)
        out_kept[r], out_sims[r, ids] = kept_k, s
        out_err[r] = float(err(params, x_test, y_test)) * 100.0
        del rows, blocks
    return dict(
        test_error=out_err, kept=out_kept, sims=out_sims,
        blocked_round=rep.blocked_round,
        params=np.asarray(pack(params, sizes), np.float64), params0=p0,
    )
