"""Median milliseconds, over the window's dispatches, of everything ``run_steps``
does before the executable is called (staging, the rebuild check, the lr schedule,
the key split, two small arrays, the cost database's begin): the program's
``trainer.run_steps.prepare`` span records inside the window.  ``enqueue_ms`` times
the whole call from outside.  ``None`` where the program keeps no span records (an
older program)."""
import statistics


def read(ctx):
    from mxnet_tpu.telemetry import spans
    if not hasattr(spans, "records"):
        return None
    first, last = ctx["samples"][0][0], ctx["samples"][-1][2]
    ms = [(r.end - r.start) * 1e3
          for r in spans.records("trainer.run_steps.prepare", since=first, until=last)]
    return statistics.median(ms) if ms else None
