"""Nearest-rank 95th percentile over the window's dispatches of (wall time of one
dispatch, fetch included) / steps in it, in milliseconds.  The dispatches that ran
under the profiler are left out where others remain.  A tail of some tens of
samples on the host's clock: not steady enough for a bound (PERF.md, PR 23)."""
import math


def read(ctx):
    samples = ctx["samples"][len(ctx["traced_samples"]):] or ctx["samples"]
    ms = sorted((c - a) / ctx["steps_per_dispatch"] * 1e3 for a, _b, c, _l in samples)
    return ms[math.ceil(0.95 * len(ms)) - 1] if ms else None
