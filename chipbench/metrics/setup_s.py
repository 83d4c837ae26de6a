"""Seconds from process start to the first timed step."""


def read(r):
    return r.setup_s
