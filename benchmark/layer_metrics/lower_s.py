"""Seconds of set-up spent tracing and lowering the program's own programs (the
graph passes run at trace time are inside): the sum of the ``program.lower`` span
records of ``telemetry.memory.planned_executable`` that ended before the window.
``compile_s`` counts what follows each (backend compile or cache load).  ``None``
where the program keeps no span records (an older program)."""


def read(ctx):
    from mxnet_tpu.telemetry import spans
    if not hasattr(spans, "records"):
        return None
    recs = spans.records("program.lower", until=ctx["samples"][0][0])
    return sum(r.end - r.start for r in recs) if recs else None
