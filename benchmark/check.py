"""The comparison that decides ``correct``.

The program's first steps are held against the plain reference's
(``references/<configuration>.py`` through ``references/common.follow``), both
from the same seeded weights and batch.  Each number compared has a limit of its
own in the configuration's file (``limits``), with the readings it was set from
and the reason for it; every run prints each number beside its limit.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

LOSS_STEPS = 3


def leaf_gaps(prog, ref):
    """By leaf: the gap between the program's norm and the reference's, against the
    reference's norm of that leaf or of the median leaf, whichever is larger (some
    gradients are all but zero)."""
    median = statistics.median(ref.values())
    return {k: abs(prog[k] - r) / max(r, median, 1e-30) for k, r in ref.items()}


def median_leaf_gap(prog, ref):
    """``(median gap, worst gap, worst leaf)``.  The median is what is held to a
    limit: on the chip the worst leaf of a sound bf16 run reads 0.15-0.48 (a batch
    norm's gain or shift, whose gradient is what is left of sums that cancel) and
    the control's reads no more, so the worst leaf separates nothing (PERF.md 6)."""
    gaps = leaf_gaps(prog, ref)
    if not all(math.isfinite(g) for g in gaps.values()):
        return float("inf"), float("inf"), None
    leaf = max(gaps, key=gaps.get)
    return statistics.median(gaps.values()), gaps[leaf], leaf


def sample_error(prog, ref):
    """Relative error of the first gradient, element by element, over the sampled
    elements of every weight leaf: |program - reference| / |reference| (2-norms over
    all samples).  Rounding in a lower precision moves elements, not norms: this is
    the number that tells fp8 operands from bf16."""
    num = den = 0.0
    for k, r in ref.items():
        num += float(np.sum(np.square(np.asarray(prog[k], np.float64) - r)))
        den += float(np.sum(np.square(np.asarray(r, np.float64))))
    return math.sqrt(num / den) if den > 0 and math.isfinite(num) else float("inf")


def numbers(prog, ref):
    """``[(name, value, note)]``: the numbers compared, from the two sides' first steps."""
    n = min(LOSS_STEPS, len(ref["losses"]), len(prog["losses"]))
    loss_gap = max(abs(p - r) for p, r in zip(prog["losses"][:n], ref["losses"][:n]))
    g, g_worst, g_leaf = median_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    d, d_worst, d_leaf = median_leaf_gap(prog["delta_norms"], ref["delta_norms"])
    return [
        ("loss_gap", loss_gap, "largest |loss - reference's| over the first %d steps" % n),
        ("grad_sample_err", sample_error(prog["grad_samples"], ref["grad_samples"]),
         "first gradient, sampled elements of the weight leaves"),
        ("grad_norm_gap", g, "first gradient's norm, median leaf; worst %.3g at %s"
         % (g_worst, g_leaf)),
        ("delta_norm_gap", d, "norm of the parameters' change after %d steps, median leaf; "
         "worst %.3g at %s" % (len(ref["losses"]), d_worst, d_leaf)),
    ]


def compare(prog, ref, limits, say=print):
    """True if every number is inside its limit; prints each beside its limit."""
    ok = True
    for name, value, note in numbers(prog, ref):
        limit = float(limits[name]["limit"])
        inside = math.isfinite(value) and value <= limit
        ok = ok and inside
        say("check %s = %.6g (limit %.6g) %s  [%s]"
            % (name, value, limit, "ok" if inside else "OUTSIDE", note))
    falls = prog["losses"][1] < prog["losses"][0]
    say("check second loss %.6g < first %.6g %s" % (prog["losses"][1], prog["losses"][0],
                                                   "ok" if falls else "OUTSIDE"))
    return ok and falls
