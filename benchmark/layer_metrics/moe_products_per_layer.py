"""Grouped products a trained step runs for an expert layer, the largest over
the expert layers of the step traced last
(``mxnet_tpu.parallel.moe.last_plan_summary()``: each layer's
``products_trained``): 9 for gated experts (three matrices an expert: three
products forward, each one's two backward), 6 for ungated ones (two matrices).
A layer of ungated experts that read 9 would run a product it has no matrix
for.  ``None`` where the program's plan does not say (an older program) or
traced no expert layer."""


def read(ctx):
    try:
        from mxnet_tpu.parallel import moe
    except ImportError:
        return None
    summary = moe.last_plan_summary() if hasattr(moe, "last_plan_summary") else None
    counts = [layer["products_trained"] for layer in (summary or {}).get("layers", ())
              if "products_trained" in layer]
    return max(counts) if counts else None
