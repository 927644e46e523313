"""Bytes of recurrent state that the state-space layers' backward keeps, all
layers of the step traced last, in GB
(``mxnet_tpu.ops.ssd.last_plan_summary()``: ``state_bytes``, from the shapes the
program traced: one float32 state a head for each group of chunks).  A state a
token would read hundreds of times higher.  ``None`` where the program has no
such op (an older program) or traced no such layer."""


def read(ctx):
    try:
        from mxnet_tpu.ops import ssd
    except ImportError:
        return None
    plan = ssd.last_plan_summary()
    return None if plan is None else plan.get("state_bytes", 0) / 1e9
