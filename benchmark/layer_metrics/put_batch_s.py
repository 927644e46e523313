"""Seconds of set-up spent staging the batch from the host onto the mesh
(``ShardedTrainer.put_batch``: the cast, the transfer, the on-device transpose's
enqueue; not the wait for the device after it): the program's
``trainer.put_batch`` span records.  ``None`` where the program has no such
record (an older program)."""
from layer_metrics import setup_spans


def read(ctx):
    return setup_spans.seconds(ctx, "trainer.put_batch")
