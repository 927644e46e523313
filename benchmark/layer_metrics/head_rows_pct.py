"""Rows a document puts through the final norm and the head over the rows it
puts through every layer, in percent, of the block-diffusion graph built last
(``mxnet_tpu.models.sdar_moe.last_plan_summary()``: ``head_rows`` and
``layer_rows``, the builder's own record).  50 where only the noised copy reaches
the head; 100 would say the clean copy's logits are made and thrown away.
``None`` where the program has no such model (an older program) or built no such
graph."""


def read(ctx):
    try:
        from mxnet_tpu.models import sdar_moe
    except ImportError:
        return None
    plan = sdar_moe.last_plan_summary()
    if not plan or not plan.get("layer_rows"):
        return None
    return 100.0 * plan["head_rows"] / plan["layer_rows"]
