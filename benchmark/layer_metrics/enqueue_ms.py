"""Median milliseconds of the benchmark's span around the runner's dispatch call,
up to its return and before the fetch: the host's share of a dispatch."""
import statistics


def read(ctx):
    spans = [(b - a) * 1e3 for a, b, _c, _l in ctx["samples"]]
    return statistics.median(spans) if spans else None
