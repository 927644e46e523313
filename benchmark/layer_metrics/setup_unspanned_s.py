"""Seconds of the session, on its own thread, that no span record covers
(``telemetry.spans.uncovered``, summed): the harness's waits for the device
(``block_until_ready``, the fetches of the first 1 + chain steps), its
comparison, and whatever nobody knows of yet.  ``None`` where the program cannot
say (an older program) or no session is found."""
from layer_metrics import setup_spans


def read(ctx):
    from mxnet_tpu.telemetry import spans
    found = setup_spans.session(ctx)
    if found is None or not hasattr(spans, "uncovered"):
        return None
    _recs, start, end, thread = found
    return sum(g.end - g.start for g in spans.uncovered(start, end, thread))
