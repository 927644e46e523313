"""Score elements the step's causal flash attention kernels compute, over the
``t * t`` of a head, in percent: the largest over the kernels of the step traced
last (``mxnet_tpu.ops.pallas_kernels.last_causal_plan()``: the program's own
count from each kernel's blocks and static ranges).  The mask leaves 50 plus
half a Q block's share; 100 is the whole square, masked half included.  ``None``
where the program keeps no such plan (an older program) or traced no causal
flash kernel."""


def read(ctx):
    try:
        from mxnet_tpu.ops import pallas_kernels
    except ImportError:
        return None
    if not hasattr(pallas_kernels, "last_causal_plan"):
        return None
    plan = pallas_kernels.last_causal_plan()
    return None if plan is None else plan.get("scores_computed_pct")
