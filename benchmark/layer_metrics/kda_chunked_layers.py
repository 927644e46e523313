"""Linear-attention layers of the step traced last whose timed form is the
chunked scan (``mxnet_tpu.ops.delta_rule.last_plan_summary()``:
``chunked_layers``, the program's own record of what each layer lowered to).  A
layer that fell back to a token-by-token recurrence would read lower than the
model's linear-attention layers.  ``None`` where the program has no such op (an
older program) or traced no such layer."""


def read(ctx):
    try:
        from mxnet_tpu.ops import delta_rule
    except ImportError:
        return None
    plan = delta_rule.last_plan_summary()
    return None if plan is None else plan.get("chunked_layers")
