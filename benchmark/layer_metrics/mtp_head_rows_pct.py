"""Rows a sequence puts through the output head over the rows it puts through a
layer, in percent, from the multi-token-prediction module's note
(``mxnet_tpu.telemetry.plan.last("mxtpu.block.mtp")``: ``head_rows``, both heads
together, and ``layer_rows``).  200 in GLM-4.7-Flash's cell: the main head and the
module's, each over all T rows (the module's last row carries no loss and is
computed all the same).  ``None`` where the step has no module."""
from layer_metrics.mtp_modules import notes


def read(ctx):
    found = notes()
    if not found or not found[-1].get("layer_rows"):
        return None
    return 100.0 * found[-1]["head_rows"] / found[-1]["layer_rows"]
