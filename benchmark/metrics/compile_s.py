"""Seconds of jax's backend-compile events during set-up (compilation, or
the retrieval from the persistent cache when it hits)."""


def read(ctx):
    return ctx["compile_s"]
