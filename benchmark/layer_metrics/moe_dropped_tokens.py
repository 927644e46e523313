"""Assignments to a held expert that the expert layers could not have computed:
what the window's published samples held beyond the rows of the sorted buffer
that the grouped products run over (``buffer_rows`` of
``mxnet_tpu.parallel.moe.last_plan_summary()``, the traced program's own shape),
summed over the layers and the samples.  0 as long as that buffer has tokens x
experts a token rows; a lowering with a capacity would read above it."""
from layer_metrics import moe_samples


def read(ctx):
    samples = moe_samples.window_samples(ctx)
    if samples is None:
        return None
    from mxnet_tpu.parallel import moe
    plan = moe.last_plan_summary() if hasattr(moe, "last_plan_summary") else None
    if plan is None or any("buffer_rows" not in layer for layer in plan["layers"]):
        return None
    rows = min(layer["buffer_rows"] for layer in plan["layers"])
    return sum(max(sum(c) - rows, 0.0) for s in samples for c in moe_samples.assignments(s))
