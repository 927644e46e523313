"""Latent-attention layers of the step traced last that have a query latent and
a rotary part (``mxnet_tpu.telemetry.plan.last("mxtpu.block.mla")``: one note a
layer from ``models.decoder_blocks.latent_attention``, with ``q_lora_rank`` and
``rope_dims``).  GLM-4.7-Flash's cell reads 6: five decoder layers and the
multi-token-prediction module's.  A layer built without either option (Kimi
Linear's) does not count.  ``None`` where the program keeps no such plan (an
older program) or traced no latent attention."""


def read(ctx):
    try:
        from mxnet_tpu.telemetry import plan
    except ImportError:
        return None
    layers = plan.last("mxtpu.block.mla")
    if layers is None:
        return None
    return sum(1 for layer in layers
               if layer.get("q_lora_rank") and layer.get("rope_dims"))
