"""Linear-attention layers of the step traced last whose chunked scan lowered
to the two Pallas kernels, ``mxtpu_kda_fwd`` / ``mxtpu_kda_bwd``
(``mxnet_tpu.ops.delta_rule.last_plan_summary()``: ``kernel_layers``, the
program's own record of each layer's ``lowering``).  A layer on the
``jax.numpy`` form (another backend, a width that is no whole lane tile,
another chunk) is not counted.  ``None`` where the program has no such op,
traced no such layer, or keeps no such record (an older program)."""


def read(ctx):
    try:
        from mxnet_tpu.ops import delta_rule
    except ImportError:
        return None
    plan = delta_rule.last_plan_summary()
    return None if plan is None else plan.get("kernel_layers")
