"""Dispatches of the window after which the program itself blocked on the device
(the cost database's sampled ``block_until_ready``): the number of
``trainer.run_steps.sync`` span records inside the window.  ``None`` where the
program keeps no span records (an older program)."""


def read(ctx):
    from mxnet_tpu.telemetry import spans
    if not hasattr(spans, "records"):
        return None
    first, last = ctx["samples"][0][0], ctx["samples"][-1][2]
    return len(spans.records("trainer.run_steps.sync", since=first, until=last))
