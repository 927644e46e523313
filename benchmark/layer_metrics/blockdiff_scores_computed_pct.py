"""Score elements the step's block-diffusion flash kernels compute, over the
``2L x 2L`` rows and keys of a head (both copies of a document), in percent: the
largest over those kernels of the step traced last
(``mxnet_tpu.ops.pallas_kernels.last_causal_plan()``:
``diffusion_scores_computed_pct``, the program's own count over the tiles it
runs).  The mask needs ``100 (L^2 + L B) / (2L)^2``, 25.02 at L 4096 and B 4; the
causal kernels over the same rows would compute 53.1 (and the wrong answer).
``None`` where the program keeps no such count (an older program) or traced no
such kernel."""


def read(ctx):
    try:
        from mxnet_tpu.ops import pallas_kernels
    except ImportError:
        return None
    plan = getattr(pallas_kernels, "last_causal_plan", lambda: None)()
    return None if plan is None else plan.get("diffusion_scores_computed_pct")
