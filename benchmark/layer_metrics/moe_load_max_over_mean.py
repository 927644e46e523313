"""The imbalance the step carried: the largest held expert's assignments over
the held experts' mean, in the worst expert layer, median over the window's
published samples (1.0 is even routing)."""
import statistics

from layer_metrics import moe_samples


def read(ctx):
    samples = moe_samples.window_samples(ctx)
    if samples is None:
        return None
    worst = [max(max(c) / max(statistics.fmean(c), 1e-9)
                 for c in moe_samples.assignments(s)) for s in samples]
    return statistics.median(worst)
