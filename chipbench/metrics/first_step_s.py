"""Seconds to compile the step (or load it from the compilation cache) and
run the first step, loss read included."""


def read(r):
    return r.first_step_s
