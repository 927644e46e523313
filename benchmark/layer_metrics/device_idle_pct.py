"""1 - union of the device-op intervals over the traced window, in percent."""


def read(ctx):
    return ctx["trace"]["idle_pct"]
