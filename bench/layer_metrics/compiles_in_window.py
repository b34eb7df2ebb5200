"""Compilation (JAX): backend compiles inside the measured window."""


def read(ctx):
    return ctx.compiles
