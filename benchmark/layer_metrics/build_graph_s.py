"""Seconds of the trainer's build spent on what it works out from the symbol and
the mesh before any array exists (variables, shape inference, the optimizer's
rule, tensor-parallel rules, the SPMD check, the shardings): the program's
``trainer.build.graph`` span records of set-up.  With ``init_params_s`` and
``build_plan_s`` it splits ``build_s``.  ``None`` where the program has no such
record."""
from layer_metrics import setup_spans


def read(ctx):
    return setup_spans.seconds(ctx, "trainer.build.graph")
