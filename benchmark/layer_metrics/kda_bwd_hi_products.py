"""Products at the matmuls' highest precision (float32 operands, six passes
of the MXU each) in the body of ``mxtpu_kda_bwd`` as the step traced last
traced it (``mxnet_tpu.ops.delta_rule.last_plan_summary()``:
``bwd_hi_products``, the program's own count of the ``dot_general``s at
``Precision.HIGHEST`` in the backward kernel's traced body, the largest over
the layers).  They were two fifths of the kernels' time when the kernels came
in; fewer is better, at equal results.  ``None`` where the program has no such
op, traced no layer on the kernels, or keeps no such record (an older
program)."""


def read(ctx):
    try:
        from mxnet_tpu.ops import delta_rule
    except ImportError:
        return None
    plan = delta_rule.last_plan_summary()
    return None if plan is None else plan.get("bwd_hi_products")
