"""Seconds of backend compiles before the window, as `jax.monitoring`
reports them, persistent-cache loads included (moves `setup_s`)."""


def read(r):
    return r.get("compile_s")
