"""The step program's share of its compute roofline: the least time the chip could
take for the operations one step needs (``configs/<configuration>.py step_flops``
over the bf16 peak of ``peaks.json``, per chip) over the device's busy time per
step in the traced window.  Compute-bound by construction: the bytes a whole
step must move are far under its operations over the ridge."""


def read(ctx):
    steps = len(ctx["traced_samples"]) * ctx["steps_per_dispatch"]
    busy = ctx["trace"]["busy_s"]
    if not steps or busy <= 0:
        return None
    least = ctx["step_flops"] / ctx["cell"].chips / ctx["peak"]["bf16_flops"]
    return 100.0 * least / (busy / steps)
