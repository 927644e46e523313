"""Assignments that fell to a held expert, as a share of all the step's
(tokens x experts a token), mean over the expert layers, median over the
window's published samples: 25 when routing over the router's width is even
and a quarter of the experts is held.  Stands beside ``step_flops``, which
counts the experts at that expected load."""
import statistics

from layer_metrics import moe_samples


def read(ctx):
    samples = moe_samples.window_samples(ctx)
    if samples is None:
        return None
    cell = ctx["cell"]
    total = cell.cfgmod.units_per_step(cell.cfg, cell.mix, cell.chips) \
        * cell.cfg["num_experts_per_tok"]
    return statistics.median(
        100.0 * statistics.fmean(sum(c) for c in moe_samples.assignments(s)) / total
        for s in samples)
