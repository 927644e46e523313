"""Share of the device's busy time inside Pallas custom calls, from the trace."""


def read(ctx):
    return ctx["trace"]["pallas_pct"]
