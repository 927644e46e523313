"""Blocks of the fusion plan that run as Pallas kernels (``trainer.fusion_summary()``)."""


def read(ctx):
    fusion = (ctx["counters"] or {}).get("fusion")
    if not fusion or "pallas_blocks" not in fusion:
        return None
    return fusion["pallas_blocks"]
