"""What set-up's dispatches (the first call of the 1-step program and of the
chain program) cost the host beyond tracing and compiling or loading: the
program's ``trainer.run_steps`` span records of set-up less the ``program.lower``
and ``program.compile`` records below them.  What is left is ``.prepare``,
``program.plan``, the executable's first call (``.launch``) and ``.account`` with
its ``.sync``; a dispatch of the window costs 4-10 ms.  ``None`` where the
program has no such record."""
from layer_metrics import setup_spans

def read(ctx):
    recs = setup_spans.setup_records(ctx)
    calls = [r for r in recs or () if r.name == "trainer.run_steps"]
    if not calls:
        return None
    by_id = {r.id: r for r in recs}
    planned = [r for r in recs if r.name in setup_spans.PLANNED
               and setup_spans.under(r, by_id, ("trainer.run_steps",))]
    return sum(r.end - r.start for r in calls) - setup_spans.union_seconds(planned)
