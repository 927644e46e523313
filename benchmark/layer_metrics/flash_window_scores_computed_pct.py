"""Score elements the step's windowed flash attention kernels compute, over the
``t * t`` of a head, in percent: the largest over the windowed kernels of the step
traced last (``mxnet_tpu.ops.pallas_kernels.last_causal_plan()``:
``window_scores_computed_pct``, the program's own count over the tiles of the band
each Q block runs).  The band itself needs 21.9 at 8192 positions and a window of
2048; the causal kernels would read 53.125.  ``None`` where the program keeps no
such count (an older program) or traced no windowed kernel."""


def read(ctx):
    try:
        from mxnet_tpu.ops import pallas_kernels
    except ImportError:
        return None
    plan = getattr(pallas_kernels, "last_causal_plan", lambda: None)()
    return None if plan is None else plan.get("window_scores_computed_pct")
