"""Rows of the expert layers' sorted buffer (what their gathers and grouped
products run over) as a share of tokens x experts a token, in percent, the
largest over the layers of the step traced last
(``mxnet_tpu.parallel.moe.last_plan_summary()``: ``buffer_rows`` and, where the
program records it, ``even_rows``, which is tokens x experts a token x held /
router width).  100 where a quarter or more of the experts is held; 12.5 for 8
of 256.  ``None`` where the program keeps no such plan or its layers do not say
what even routing would send (an older program)."""


def read(ctx):
    try:
        from mxnet_tpu.parallel import moe
    except ImportError:
        return None
    plan = moe.last_plan_summary() if hasattr(moe, "last_plan_summary") else None
    if plan is None or any("even_rows" not in layer for layer in plan["layers"]):
        return None
    return max(100.0 * layer["buffer_rows"] * layer["experts_held"]
               / (layer["even_rows"] * layer["num_experts"])
               for layer in plan["layers"])
