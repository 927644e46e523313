"""Bytes of recurrent state that the linear-attention layers' backward keeps,
all layers of the step traced last, in GB
(``mxnet_tpu.ops.delta_rule.last_plan_summary()``: ``state_bytes``, from the
shapes the program traced: one float32 state a head for each group of chunks).
A state a token would read thousands of times higher.  ``None`` where the
program has no such op (an older program) or traced no such layer."""


def read(ctx):
    try:
        from mxnet_tpu.ops import delta_rule
    except ImportError:
        return None
    plan = delta_rule.last_plan_summary()
    return None if plan is None else plan.get("state_bytes", 0) / 1e9
