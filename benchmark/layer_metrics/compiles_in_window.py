"""Backend compiles inside the timed window (must be 0; ``correct`` is false otherwise)."""


def read(ctx):
    return ctx["compiles_in_window"]
