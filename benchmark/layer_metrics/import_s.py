"""Seconds of ``import mxnet_tpu``, first to last line of its ``__init__``: the
program's own ``mxnet_tpu.import`` span record, which ended before the window.
``None`` where the program keeps no span records (an older program)."""


def read(ctx):
    from mxnet_tpu.telemetry import spans
    if not hasattr(spans, "records"):
        return None
    recs = spans.records("mxnet_tpu.import", until=ctx["samples"][0][0])
    return sum(r.end - r.start for r in recs) if recs else None
