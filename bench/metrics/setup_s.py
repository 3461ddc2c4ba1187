"""Set-up time: process start to the window's start (imports, weights,
Amber scales, engine, warm-up and whatever compiles)."""


def read(run):
    return run.setup_s
