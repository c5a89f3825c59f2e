"""Seconds of set-up in which JAX traced, lowered or compiled (the
union of its compile events before the window)."""


def read(ctx):
    return ctx["setup_compile_s"]
