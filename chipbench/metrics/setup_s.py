"""Seconds from process start to the window: JAX and TPU start-up, host
preparation, compile-cache loads or compilation, and the warm call."""


def read(run):
    return run.setup_s
