"""Seconds of the trainer's build spent drawing parameters on the host and putting
them, the optimizer's state and the aux state on the device: the program's
``trainer.build.init_params`` and ``trainer.build.place`` span records.  ``None``
where the program keeps no span records (an older program)."""

NAMES = ("trainer.build.init_params", "trainer.build.place")


def read(ctx):
    from mxnet_tpu.telemetry import spans
    if not hasattr(spans, "records"):
        return None
    recs = [r for r in spans.records("trainer.build.", until=ctx["samples"][0][0])
            if r.name in NAMES]
    return sum(r.end - r.start for r in recs) if recs else None
