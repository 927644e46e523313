"""Seconds of set-up spent building the model's Symbol from its configuration:
the program's ``model.build`` span records (``models.resnet.resnet``,
``train_lm.gpt_symbol``, the three decoder builders' ``get_symbol``).  ``None``
where the program's builder leaves no such record."""
from layer_metrics import setup_spans


def read(ctx):
    return setup_spans.seconds(ctx, "model.build")
