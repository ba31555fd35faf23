"""Set-up: process start to window start, in s (JAX start, weights, engine
build and compiles, the traffic's documents, every shape warmed)."""


def read(ctx):
    return ctx["setup_s"]
