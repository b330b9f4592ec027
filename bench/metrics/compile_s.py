"""Seconds JAX spent compiling during set-up (s): tracing, lowering, the
backend's compile and loading from the persistent cache, summed from
`jax.monitoring`'s duration events."""


def read(ctx):
    return ctx["compile_s"]
