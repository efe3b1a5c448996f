"""Seconds from process start to the first timed request: table
generation and write, JAX start, server start and warm-up."""


def read(run):
    return run.setup_s
