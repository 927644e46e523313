"""State-space layers of the step traced last that ran the chunked scan
(``mxnet_tpu.ops.ssd.last_plan_summary()``: ``chunked_layers``, the layers the
program's own record holds).  The scan has one lowering and nothing to fall
back to, so this reads the number of the model's Mamba-2 mixers.  ``None`` where the
program has no such op (an older program) or traced no such layer."""


def read(ctx):
    try:
        from mxnet_tpu.ops import ssd
    except ImportError:
        return None
    plan = ssd.last_plan_summary()
    return None if plan is None else plan.get("chunked_layers")
