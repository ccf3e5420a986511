"""Executables compiled or loaded from the persistent cache inside the
window and its drain (JAX's own compile events).  0 when warm-up covered
every shape the traffic used."""


def read(ctx):
    return ctx.window_compiles
