"""Programs JAX compiled, or fetched from its persistent cache, inside the
measured window (``jax.monitoring``; sees the eager solve too). 0 when
the warm-up fit warmed every shape."""


def read(ctx):
    return ctx["compiles_in_window"]
