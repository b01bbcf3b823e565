"""Inputs made from the run's ``--seed``: sub-seeds, and the MNIST-scale
classification data (a Gaussian mixture squashed to [-1, 1], the shape of
``repro.data.make_mnist_like``), generated on the device in one call and
held on the host, where the program's entry point takes it."""

from __future__ import annotations

import functools

import numpy as np


def kernel_mode(cfg: dict):
    """The configuration's kernel route as ``KernelPlan.mode`` takes it:
    ``"auto"`` (Pallas kernels on the TPU, the jnp route elsewhere) is
    ``True``; a mode string pins the route."""
    mode = cfg["kernel_mode"]
    return True if mode == "auto" else mode


def sub_seeds(seed: int, n: int, salt: int = 0) -> list[int]:
    """``n`` seeds below 2**31 drawn from ``seed`` (any whole number)."""
    words = np.random.SeedSequence([int(seed), int(salt)]).generate_state(n)
    return [int(w) & 0x7FFFFFFF for w in words]


@functools.lru_cache(maxsize=4)
def _make_fn(n_train, n_test, dim, classes, sep):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        kp, ktr, kte = jax.random.split(key, 3)
        protos = jax.random.normal(kp, (classes, dim), jnp.float32)
        protos *= sep * np.sqrt(dim) / jnp.linalg.norm(protos, axis=1, keepdims=True)

        def sample(k, n):
            ky, kx = jax.random.split(k)
            y = jax.random.randint(ky, (n,), 0, classes, jnp.int32)
            x = jnp.tanh(protos[y] + jax.random.normal(kx, (n, dim), jnp.float32))
            return x, y

        return sample(ktr, n_train) + sample(kte, n_test)

    return make


def classification(seed: int, n_train: int, n_test: int, dim: int,
                   classes: int, sep: float) -> dict:
    """``{"x_train", "y_train", "x_test", "y_test"}`` as host arrays."""
    import jax

    make = _make_fn(n_train, n_test, dim, classes, float(sep))
    out = jax.device_get(make(jax.random.PRNGKey(sub_seeds(seed, 1, salt=1)[0])))
    return dict(zip(("x_train", "y_train", "x_test", "y_test"),
                    (np.asarray(a) for a in out)))
