"""Seconds of the trainer's build spent planning the step (the fusion plan, the
step's and the chain's functions, nothing compiled yet): the program's
``trainer.build.plan`` span records of set-up.  ``None`` where the program has no
such record."""
from layer_metrics import setup_spans


def read(ctx):
    return setup_spans.seconds(ctx, "trainer.build.plan")
