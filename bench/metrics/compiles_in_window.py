"""Backend compiles (``jax.monitoring``) inside the measured window."""


def read(r):
    return float(r.compiles_in_window)
