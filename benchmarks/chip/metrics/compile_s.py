"""Seconds of backend compilation (persistent-cache reads included) during
set-up, from JAX's compile events."""


def read(ctx):
    return ctx.compile_s
