"""Time in which a collective runs on a device and no other operation does, over
the traced window, in percent; nothing to read on one chip."""


def read(ctx):
    if ctx["cell"].chips < 2:
        return None
    return ctx["trace"]["collective_exposed_pct"]
