"""Median milliseconds, over the window's dispatches, of everything ``run_steps``
does after the executable returned (the cost database's binding, the segment
split, ``telemetry.step_end``): the program's ``trainer.run_steps.account`` span
records inside the window, less the ``trainer.run_steps.sync`` record inside one
(a wait for the device, counted by ``host_syncs_in_window``).  ``None`` where the
program keeps no span records (an older program)."""
import statistics


def read(ctx):
    from mxnet_tpu.telemetry import spans
    if not hasattr(spans, "records"):
        return None
    first, last = ctx["samples"][0][0], ctx["samples"][-1][2]
    recs = spans.records("trainer.run_steps.", since=first, until=last)
    waited = {}
    for r in recs:
        if r.name == "trainer.run_steps.sync":
            waited[r.parent] = waited.get(r.parent, 0.0) + (r.end - r.start)
    ms = [(r.end - r.start - waited.get(r.id, 0.0)) * 1e3
          for r in recs if r.name == "trainer.run_steps.account"]
    return statistics.median(ms) if ms else None
