"""Rows of the smallest Q block over the step's causal and windowed flash
attention kernels (``mxnet_tpu.ops.pallas_kernels.last_causal_plan()``:
``q_block_rows``, from the blocks each kernel of the step traced last was given).
A Q block's fixed work is paid once a block whatever its rows: 512 where the
program chose every causal call's Q block from its shape, 128 while one kernel
still runs the old constant.  A program whose plan has no such key (an older one)
is read from the plan's kernels, each of which says its ``block_q``.  ``None``
where the program keeps no plan or traced no causal flash kernel."""


def read(ctx):
    try:
        from mxnet_tpu.ops import pallas_kernels
    except ImportError:
        return None
    plan = getattr(pallas_kernels, "last_causal_plan", lambda: None)()
    if plan is None:
        return None
    if "q_block_rows" in plan:
        return plan["q_block_rows"]
    return min((k["block_q"] for k in plan.get("kernels", ())), default=None)
