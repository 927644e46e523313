"""Parameters the multi-token-prediction module shares with the main path: the
distinct names in the module's note
(``mxnet_tpu.telemetry.plan.last("mxtpu.block.mtp")``: ``shared``, written by the
builder that hands one variable to both consumers).  2 in GLM-4.7-Flash's cell:
``embed_weight`` and ``lm_head_weight``, each one master and one Adam state; a
module with a head of its own would read 1 and hold 0.6 GB more
(``hbm_peak_gb.tok`` would say so).  ``None`` where the step has no module."""
from layer_metrics.mtp_modules import notes


def read(ctx):
    found = notes()
    if not found:
        return None
    return len(set(found[-1].get("shared", ())))
