"""Seconds JAX spent in set-up tracing, lowering and compiling programs
or reading them from the persistent cache (``jax.monitoring`` events)."""


def read(ctx, suffix):
    return ctx["compile_s"]
