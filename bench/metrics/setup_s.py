"""Seconds from the start of the process until the window could open:
starting JAX, making the weights, building the engine, compiling or
loading every step program from the cache, warming up the shapes."""


def read(run):
    return run.setup_s
