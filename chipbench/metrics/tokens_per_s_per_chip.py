"""Tokens trained in the window, over its wall time, over the chips."""


def read(r):
    return r.window_steps * r.tokens_per_step / r.window_s / r.chips
