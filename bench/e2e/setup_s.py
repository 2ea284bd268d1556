"""Seconds from the process's start to the window's: JAX start-up,
compiles (or persistent-cache loads), input generation and warm-up."""


def read(run):
    return run.setup_s
