"""Attention layers of the step traced last that ran the block-diffusion flash
kernels, ``mxtpu_flash_fwd_blockdiff`` / ``mxtpu_flash_bwd_blockdiff``
(``mxnet_tpu.ops.pallas_kernels.last_causal_plan()``: ``diffusion_layers``, the
program's own count of its forward kernels under that mask).  A layer that fell
back to the plain formula under a dense mask is not counted.  ``None`` where the
program keeps no such count (an older program) or traced no flash kernel that
records a plan."""


def read(ctx):
    try:
        from mxnet_tpu.ops import pallas_kernels
    except ImportError:
        return None
    plan = getattr(pallas_kernels, "last_causal_plan", lambda: None)()
    return None if plan is None else plan.get("diffusion_layers")
