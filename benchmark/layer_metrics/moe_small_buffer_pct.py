"""How often the expert layers ran over their smaller sorted buffer: the share,
in percent, of the window's (published sample, expert layer) pairs whose held
assignments fit the layer's ``small_rows`` (twice the even load of the held
experts; ``mxnet_tpu.parallel.moe.last_plan_summary()``), which is when
``topk_moe``'s ``lax.cond`` takes the branch over that many rows and not the one
over ``buffer_rows``.  The program says it sample by sample (``small_buffer`` of
a layer's sample, 1 or 0, beside the gauge ``mxtpu_moe_small_buffer``).  100
where every step holds about its even share; ``None`` where no layer of the
plan has a second size or the program does not say (an older program)."""
from layer_metrics import moe_samples


def read(ctx):
    samples = moe_samples.window_samples(ctx)
    if samples is None:
        return None
    from mxnet_tpu.parallel import moe
    plan = moe.last_plan_summary() if hasattr(moe, "last_plan_summary") else None
    if plan is None or all(layer.get("small_rows") is None for layer in plan["layers"]):
        return None
    fits = [layer["small_buffer"] for s in samples for layer in s.values()
            if "small_buffer" in layer]
    return 100.0 * sum(fits) / len(fits) if fits else None
