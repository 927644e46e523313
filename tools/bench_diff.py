#!/usr/bin/env python
"""bench_diff — the BENCH-trajectory regression guard.

The ``BENCH_r*.json`` series is the repo's perf ground truth, and until
now nothing machine-checked it — a regression would land silently in a
flat-looking trajectory.  This tool compares a series of bench
artifacts under a noise threshold and exits nonzero when the newest
valid run regresses against the best earlier valid run.

Input formats (auto-detected per file):

* the raw one-line JSON ``bench.py`` prints
  (``{"metric", "value", "unit", "valid", ...}``);
* the round wrapper the repo commits
  (``{"n", "cmd", "rc", "tail", "parsed": {...}}``).

A run is **skipped** (never treated as a 0-throughput regression) when
it is errored: nonzero wrapper ``rc``, an ``error`` field,
``"valid": false`` (bench.py marks its CPU dry run so), a
missing/non-numeric value, or a value <= 0.

Runs carrying the serving block (``{"serving": {...}}``, bench.py's
``--serve`` leg) are additionally guarded on its two SLO-facing
numbers, both lower-is-better:

* ``p99_ms`` — the newest value must not rise more than the relative
  noise band above the best (lowest) earlier value;
* ``shed_rate`` — an ABSOLUTE slack (``--shed-slack``, default +0.05)
  over the best earlier rate: a healthy baseline sheds 0.0, where any
  relative band would make every nonzero shed either a regression or
  a free pass.

Stdlib-only.  Usage::

    python tools/bench_diff.py FILE [FILE...] [--threshold 0.1]
                               [--metric NAME] [--json]

Files are compared in the given order (pass them oldest-first, e.g.
``BENCH_r0*.json``).  ``--threshold`` is the relative noise band
(default 0.10 = 10%): the newest valid value must not fall more than
that fraction below the best earlier valid value.

Exit codes: 0 no regression — including a series with fewer than two
comparable runs (a young or all-errored series has nothing to guard
yet; the printed skip report says why), 1 regression detected, 2 usage
errors (bad threshold, no matching files).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

DEFAULT_THRESHOLD = 0.10
#: absolute shed-rate slack — relative bands degenerate at a 0.0
#: baseline (see the module docstring)
DEFAULT_SHED_SLACK = 0.05


def load_run(path):
    """One bench artifact -> normalized run dict
    ``{"path", "metric", "value", "valid", "reason"}``.
    Never raises: unreadable/unparseable files become invalid runs
    with the reason recorded."""
    run = {"path": path, "metric": None, "value": None,
           "valid": False, "reason": None, "serving": None}
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        run["reason"] = "unreadable (%s)" % e
        return run
    if not isinstance(doc, dict):
        run["reason"] = "not a JSON object"
        return run
    rc = doc.get("rc")
    payload = doc.get("parsed") if isinstance(doc.get("parsed"), dict) \
        else doc
    run["metric"] = payload.get("metric")
    value = payload.get("value")
    if rc not in (None, 0):
        run["reason"] = "wrapper rc=%s" % rc
    elif payload.get("error"):
        run["reason"] = "errored: %s" % payload["error"]
    elif payload.get("valid") is False:
        run["reason"] = "marked valid=false"
    elif not isinstance(value, (int, float)) or isinstance(value, bool):
        run["reason"] = "missing/non-numeric value"
    elif value <= 0:
        run["reason"] = "non-positive value"
    else:
        run["valid"] = True
        run["value"] = float(value)
    if run["valid"] and isinstance(payload.get("serving"), dict):
        run["serving"] = payload["serving"]
    return run


def _serving_diff(valid, threshold, shed_slack):
    """The serving-block guard: lower-is-better p99 under the relative
    band, shed rate under the absolute slack.  Returns the report
    sub-dict (``comparable`` false below two serving runs)."""
    runs = [r for r in valid
            if isinstance((r["serving"] or {}).get("p99_ms"),
                          (int, float))]
    out = {"comparable": False, "regression": False,
           "runs": len(runs)}
    if len(runs) < 2:
        return out
    last, earlier = runs[-1], runs[:-1]
    best_p99 = min(float(r["serving"]["p99_ms"]) for r in earlier)
    p99 = float(last["serving"]["p99_ms"])
    ceiling = best_p99 * (1.0 + threshold)
    out.update({
        "comparable": True,
        "p99_ms": {"latest": p99, "best_earlier": best_p99,
                   "ceiling": round(ceiling, 6),
                   "regression": p99 > ceiling},
    })
    sheds = [float(r["serving"]["shed_rate"]) for r in earlier
             if isinstance(r["serving"].get("shed_rate"),
                           (int, float))]
    if sheds and isinstance(last["serving"].get("shed_rate"),
                            (int, float)):
        best_shed = min(sheds)
        shed = float(last["serving"]["shed_rate"])
        shed_ceiling = best_shed + shed_slack
        out["shed_rate"] = {
            "latest": shed, "best_earlier": best_shed,
            "ceiling": round(shed_ceiling, 6),
            "regression": shed > shed_ceiling}
    out["regression"] = any(
        out.get(k, {}).get("regression")
        for k in ("p99_ms", "shed_rate"))
    return out


def diff(runs, threshold=DEFAULT_THRESHOLD, metric=None,
         shed_slack=DEFAULT_SHED_SLACK):
    """Compare the series; returns the report dict.

    ``regression`` is true when the LAST valid run's value falls more
    than ``threshold`` below the best earlier valid value of the same
    metric, OR when the serving-block guard trips (p99 above its
    relative ceiling / shed rate above its absolute slack).  Fewer
    than two comparable runs -> ``comparable`` false (no regression
    claim either way)."""
    valid = [r for r in runs if r["valid"]
             and (metric is None or r["metric"] == metric)]
    # the serving guard runs over every valid run carrying the block,
    # BEFORE the dominant-metric filter: in a mixed directory the
    # throughput metric may dominate, but a serving series must still
    # be guarded
    serving = _serving_diff(valid, threshold, shed_slack)
    report = {
        "schema": "mxtpu-benchdiff/2",
        "threshold": threshold,
        "runs": len(runs),
        "valid_runs": len(valid),
        "skipped": [{"path": r["path"], "reason": r["reason"]}
                    for r in runs if not r["valid"]],
        "comparable": False,
        "regression": serving["regression"],
        "serving": serving,
    }
    if metric is None and valid:
        # single-metric series expected; mixed series compare the
        # dominant (most frequent, first-seen on ties) metric and note
        # the rest as skipped — anchoring on the FIRST run's metric
        # would silently disable the guard after a mid-series rename
        counts = {}
        for r in valid:
            counts[r["metric"]] = counts.get(r["metric"], 0) + 1
        metric = max(counts, key=lambda m: counts[m])
        mixed = [r for r in valid if r["metric"] != metric]
        valid = [r for r in valid if r["metric"] == metric]
        report["skipped"].extend(
            {"path": r["path"],
             "reason": "metric %r != %r" % (r["metric"], metric)}
            for r in mixed)
    report["metric"] = metric
    if len(valid) < 2:
        return report
    last = valid[-1]
    earlier = valid[:-1]
    best = max(earlier, key=lambda r: r["value"])
    floor = best["value"] * (1.0 - threshold)
    change = last["value"] / best["value"] - 1.0
    report.update({
        "comparable": True,
        "series": [{"path": r["path"], "value": r["value"]}
                   for r in valid],
        "latest": {"path": last["path"], "value": last["value"]},
        "best_earlier": {"path": best["path"], "value": best["value"]},
        "floor": round(floor, 6),
        "change_frac": round(change, 6),
        "regression": last["value"] < floor or serving["regression"],
    })
    return report


def _expand(paths):
    out = []
    for p in paths:
        hits = sorted(glob.glob(p)) if any(c in p for c in "*?[") \
            else [p]
        out.extend(hits)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="bench_diff",
        description="compare a BENCH_*.json series; exit 1 on "
                    "regression beyond the noise threshold")
    ap.add_argument("files", nargs="+",
                    help="bench artifacts, oldest first (globs ok)")
    ap.add_argument("--threshold", type=float,
                    default=DEFAULT_THRESHOLD,
                    help="relative noise band (default 0.10)")
    ap.add_argument("--metric", default=None,
                    help="compare only this metric name")
    ap.add_argument("--shed-slack", type=float,
                    default=DEFAULT_SHED_SLACK,
                    help="absolute shed-rate slack for the serving "
                         "guard (default 0.05)")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)

    if not (0.0 <= args.threshold < 1.0):
        print("bench_diff: --threshold must be in [0, 1)",
              file=sys.stderr)
        return 2
    files = _expand(args.files)
    if not files:
        print("bench_diff: no files match", file=sys.stderr)
        return 2
    if args.shed_slack < 0:
        print("bench_diff: --shed-slack must be >= 0", file=sys.stderr)
        return 2
    runs = [load_run(p) for p in files]
    report = diff(runs, threshold=args.threshold, metric=args.metric,
                  shed_slack=args.shed_slack)

    if args.as_json:
        print(json.dumps(report, sort_keys=True))
    else:
        for s in report["skipped"]:
            print("skip %s: %s" % (os.path.basename(s["path"]),
                                   s["reason"]))
        srv = report["serving"]
        if srv["comparable"]:
            p99 = srv["p99_ms"]
            print("serving p99 %.2fms vs best earlier %.2fms "
                  "(ceiling %.2fms): %s"
                  % (p99["latest"], p99["best_earlier"],
                     p99["ceiling"],
                     "REGRESSION" if p99["regression"] else "ok"))
            if "shed_rate" in srv:
                sr = srv["shed_rate"]
                print("serving shed rate %.3f vs best earlier %.3f "
                      "(+%.2f slack -> ceiling %.3f): %s"
                      % (sr["latest"], sr["best_earlier"],
                         args.shed_slack, sr["ceiling"],
                         "REGRESSION" if sr["regression"] else "ok"))
        if not report["comparable"]:
            print("bench_diff: %d valid run(s) of metric %r — nothing "
                  "to compare" % (report["valid_runs"],
                                  report["metric"]))
        else:
            for r in report["series"]:
                print("%-20s %12.2f" % (os.path.basename(r["path"]),
                                        r["value"]))
            print("latest %.2f vs best earlier %.2f (%+.1f%%), floor "
                  "%.2f at threshold %.0f%%"
                  % (report["latest"]["value"],
                     report["best_earlier"]["value"],
                     100.0 * report["change_frac"], report["floor"],
                     100.0 * args.threshold))
            print("REGRESSION" if report["regression"] else "ok")
    if report["regression"]:
        return 1
    if not report["comparable"]:
        # not a failure: a young series (or an all-errored one) has
        # nothing to guard yet, and CI must stay green on it — the
        # skipped list above says why
        return 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
