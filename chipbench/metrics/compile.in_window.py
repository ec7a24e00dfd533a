"""Executables JAX built inside the window: ``jax.monitoring``'s backend
compile events, each a compile or a load from the persistent cache."""


def read(ctx):
    return ctx["compiles"]
