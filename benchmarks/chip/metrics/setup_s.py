"""Seconds from process start to the window's opening: weights, compiles
or cache loads, warm-up, pool fill and lead-in traffic."""


def read(run):
    return run.setup_s
