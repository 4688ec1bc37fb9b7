"""Seconds jax spent tracing, lowering and compiling (or loading from
the cache) inside the window, from jax's own monitoring events."""


def read(run, args):
    return run.get("compile_s")
