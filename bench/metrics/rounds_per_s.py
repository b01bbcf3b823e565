"""Rounds completed over all the time of the window."""


def read(r):
    return r.rounds / r.window_s
