"""Client-side local training (paper setting: SGD momentum, batch 200,
E epochs per round before sending w_{t+1}^k back)."""

from __future__ import annotations

import functools

import jax

from repro.optim import sgd_momentum


@functools.partial(
    jax.jit, static_argnames=("loss_fn", "lr", "momentum", "dropout")
)
def local_sgd(
    loss_fn,
    params,
    batches,           # pytree of (S, b, ...) — S prebuilt minibatches
    rng,
    *,
    lr: float = 0.1,
    momentum: float = 0.9,
    dropout: bool = True,
):
    """Run S SGD steps; returns the client's proposed parameters w_{t+1}^k."""
    opt = sgd_momentum(lr, momentum)
    opt_state = opt.init(params)

    def step(carry, xs):
        p, s, key = carry
        mb = xs
        key, sub = jax.random.split(key)
        g = jax.grad(
            lambda q: loss_fn(q, mb, dropout_rng=sub if dropout else None)
        )(p)
        upd, s = opt.update(g, s, p)
        p = jax.tree_util.tree_map(lambda a, u: a + u.astype(a.dtype), p, upd)
        return (p, s, key), None

    (params, _, _), _ = jax.lax.scan(step, (params, opt_state, rng), batches)
    return params


@functools.partial(
    jax.jit, static_argnames=("loss_fn", "lr", "momentum", "dropout")
)
def local_sgd_frozen(
    loss_fn,
    frozen,            # pytree held fixed through local training (traced arg)
    params,            # the trainable pytree — what the client proposes
    batches,           # pytree of (S, b, ...) — S prebuilt minibatches
    rng,
    *,
    lr: float = 0.1,
    momentum: float = 0.9,
    dropout: bool = True,
):
    """:func:`local_sgd` for delta workloads: gradients flow only through
    ``params`` while ``frozen`` (e.g. a LoRA workload's base transformer) is
    a *traced* argument — not a Python closure — so the jit identity of the
    step is stable across reconstruction and the frozen tree is never baked
    into the executable as a constant.  The RNG stream is spelled exactly
    like :func:`local_sgd`'s (one split per step, dropout or not).  The loss
    returns ``(loss, aux)``; the result is ``(params, aux)`` with ``aux``
    stacked over the steps."""
    opt = sgd_momentum(lr, momentum)
    opt_state = opt.init(params)

    def step(carry, xs):
        p, s, key = carry
        mb = xs
        key, sub = jax.random.split(key)
        g, aux = jax.grad(
            lambda q: loss_fn(frozen, q, mb, dropout_rng=sub if dropout else None),
            has_aux=True,
        )(p)
        upd, s = opt.update(g, s, p)
        p = jax.tree_util.tree_map(lambda a, u: a + u.astype(a.dtype), p, upd)
        return (p, s, key), aux

    (params, _, _), aux = jax.lax.scan(step, (params, opt_state, rng), batches)
    return params, aux
