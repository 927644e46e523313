"""Seconds in ``ShardedTrainer.__init__`` during set-up, all of it: the program's
``trainer.build`` span records (its children split it; ``init_params_s`` is two of
them).  ``None`` where the program keeps no span records (an older program)."""


def read(ctx):
    from mxnet_tpu.telemetry import spans
    if not hasattr(spans, "records"):
        return None
    recs = [r for r in spans.records("trainer.build", until=ctx["samples"][0][0])
            if r.name == "trainer.build"]
    return sum(r.end - r.start for r in recs) if recs else None
