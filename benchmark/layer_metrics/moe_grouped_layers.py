"""Expert layers whose products the compiled step runs as grouped matmuls over
the sorted assignments: the program counts the ``ragged-dot-*`` custom calls in
the text of the step it compiled, nine to a trained layer
(``mxnet_tpu.parallel.moe.last_plan_summary()``: ``grouped_layers``).  A backend
or a lowering that multiplies densely and masks reads lower than the model's
expert layers (the CPU reads 0)."""


def read(ctx):
    try:
        from mxnet_tpu.parallel import moe
    except ImportError:
        return None
    summary = moe.last_plan_summary() if hasattr(moe, "last_plan_summary") else None
    return None if summary is None else summary.get("grouped_layers")
