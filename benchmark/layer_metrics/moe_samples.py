"""Shared by the ``moe_*`` readers: the expert layers' loads that the program
published during the window (``mxnet_tpu.parallel.moe.load_samples()``: one
sample a dispatch on which the cost database had already blocked).  Where the
window holds none, the newest sample before it; ``None`` where the program has
no such record (an older program) or published nothing."""


def window_samples(ctx):
    try:
        from mxnet_tpu.parallel import moe
    except ImportError:
        return None
    if not hasattr(moe, "load_samples"):
        return None
    samples = moe.load_samples()
    first, last = ctx["samples"][0][0], ctx["samples"][-1][2]
    inside = [s for t, s in samples if first <= t <= last]
    return inside or [s for _t, s in samples[-1:]] or None


def assignments(sample):
    """``[[held experts' assignment counts] for each expert layer]`` of one sample."""
    return [layer["assignments"] for layer in sample.values()]
