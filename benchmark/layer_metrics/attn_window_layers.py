"""Attention layers of the step traced last whose sliding window the streamed
flash kernels honour by skipping tiles, ``mxtpu_flash_fwd_window`` /
``mxtpu_flash_bwd_window`` (``mxnet_tpu.ops.pallas_kernels.last_causal_plan()``:
``window_layers``, the program's own count of its windowed forward kernels).  A
sliding layer that fell back to the causal kernels and a mask, or to the plain
formula, is not counted.  ``None`` where the program keeps no such count (an
older program) or traced no causal flash kernel."""


def read(ctx):
    try:
        from mxnet_tpu.ops import pallas_kernels
    except ImportError:
        return None
    plan = getattr(pallas_kernels, "last_causal_plan", lambda: None)()
    return None if plan is None else plan.get("window_layers")
