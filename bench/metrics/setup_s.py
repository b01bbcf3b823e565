"""Set-up: process start to the window (imports, data, warm-up, compiles)."""


def read(r):
    return r.setup_s
