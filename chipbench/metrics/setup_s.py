"""Set-up: process start to the window's start (loading, weights, compiles, warm-up)."""


def read(run):
    return run.setup_s
