"""Seconds of the session that JAX spent tracing, lowering and compiling or
loading programs outside the trainer's planned ones: the program's ``jax.trace``
/ ``jax.lower`` / ``jax.compile`` span records (one for every such event in the
process, whoever called ``jit``) that lie under no ``program.lower`` /
``program.compile`` record, nested ones counted once.  These are the init
program and its key programs, ``jax.random``'s programs, the staging programs and
the harness's own ``jit`` calls.  ``lower_s`` + the planned programs'
``jax.compile`` + this is all of the session's compile work.  ``None`` where the
program leaves no ``jax.*`` record (an older program) or no session is found."""
from layer_metrics import setup_spans

def read(ctx):
    found = setup_spans.session(ctx)
    if found is None:
        return None
    recs = found[0]
    by_id = {r.id: r for r in recs}
    jitted = [r for r in recs if r.name.startswith("jax.")]
    if not jitted:
        return None
    return setup_spans.union_seconds(
        r for r in jitted if not setup_spans.under(r, by_id, setup_spans.PLANNED))
