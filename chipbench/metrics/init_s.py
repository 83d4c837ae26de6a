"""Seconds of the program's ``core.mics.init_state``: it draws the step's
state and places it on the devices, ending in ``block_until_ready``."""


def read(r):
    return r.init_s
