"""Set-up: process start to the window's start (generation, load,
compilation or cache loads, warm-up, cache pre-fill)."""


def read(run):
    return run.setup_s
