"""Shared by the readers that split ``setup_s`` by the program's span records
(``mxnet_tpu.telemetry.spans``): set-up is what ended before the window's first
dispatch, ``samples[0][0]``; the session is the part of it from the first
``model.build`` record after the last ``mxnet_tpu.import`` (``runner.open`` builds
the model first, so the reference's seconds stay out wherever the package was
imported) to the end of its thread's last record that ended before the window (a
traced run starts the profiler after that: no set-up)."""


#: the spans of the one seam the trainer's own programs compile through
PLANNED = ("program.lower", "program.compile")


def setup_records(ctx, prefix=None):
    """The records that ended before the window, oldest first; ``None`` where the
    program keeps no span records (an older program)."""
    from mxnet_tpu.telemetry import spans
    if not hasattr(spans, "records"):
        return None
    return spans.records(prefix, until=ctx["samples"][0][0])


def seconds(ctx, name):
    """Summed seconds of set-up's records called ``name``; ``None`` without one."""
    recs = [r for r in setup_records(ctx, name) or () if r.name == name]
    return sum(r.end - r.start for r in recs) if recs else None


def session(ctx):
    """``(records of the session, its start, its end, its thread)`` or ``None``."""
    recs = setup_records(ctx)
    imported = max((r.end for r in recs or () if r.name == "mxnet_tpu.import"),
                   default=float("-inf"))
    first = min((r for r in recs or () if r.name == "model.build" and r.start >= imported),
                key=lambda r: r.start, default=None)
    if first is None:
        return None
    inside = [r for r in recs if r.start >= first.start]
    end = max(r.end for r in inside if r.thread == first.thread)
    return inside, first.start, end, first.thread


def under(rec, by_id, names):
    """Whether ``rec`` has an ancestor called one of ``names`` (``by_id``: id -> record)."""
    seen = by_id.get(rec.parent)
    while seen is not None and seen.name not in names:
        seen = by_id.get(seen.parent)
    return seen is not None


def union_seconds(recs):
    """Seconds that the records cover together: nested or overlapping ones once."""
    total, upto = 0.0, float("-inf")
    for r in sorted(recs, key=lambda r: r.start):
        if r.end > upto:
            total += r.end - max(r.start, upto)
            upto = r.end
    return total
