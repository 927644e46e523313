"""Seconds in backend compiles during set-up (benchmark's own listener on JAX's
compile events; a persistent-cache hit counts with the short time it took)."""


def read(ctx):
    return ctx["setup_events"]["compile_s"]
