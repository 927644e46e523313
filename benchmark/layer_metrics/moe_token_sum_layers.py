"""Expert layers whose two sums of the sorted buffer's rows into token order
(the forward combine and the transpose of the dispatch gather) run as the
Pallas kernel ``mxtpu_moe_token_sum`` and not as XLA scatter-adds: the program's
own count over the plan of the step traced last
(``mxnet_tpu.parallel.moe.last_plan_summary()``: ``token_sum_layers``, the layers
whose ``token_sum`` says ``"kernel"``; the rule follows the shapes and the
platform).  ``None`` where the program traced no expert layer or keeps no such
count (an older program)."""


def read(ctx):
    try:
        from mxnet_tpu.parallel import moe
    except ImportError:
        return None
    summary = moe.last_plan_summary() if hasattr(moe, "last_plan_summary") else None
    return None if summary is None else summary.get("token_sum_layers")
