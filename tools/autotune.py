#!/usr/bin/env python
"""autotune — tune Pallas block configs against real measurements and
fit the learned cost model over the costdb ground truth.

The driver for :mod:`mxnet_tpu.autotune` (ROADMAP item 2).  Modes:

**Tuning** — enumerate + measure candidates for explicit keys::

    python tools/autotune.py --op flash_fwd  --shapes 2x2176x8x64,2x3200x8x64
    python tools/autotune.py --op flash_bwd  --shapes 2x2176x8x64 --causal

Shapes are ``BxTxHxD``.  Winners
commit to the persistent tuning cache (``--cache`` or
``MXNET_TPU_TUNE_CACHE``); every candidate measurement also lands in
the cost database (``--costdb`` or ``MXNET_TPU_COSTDB``) as the cost
model's training data.  Keys already cached are skipped (all-hit
second runs are the CI contract) unless ``--force``.

**Cost model** — fit/report::

    python tools/autotune.py --fit-model costmodel.json
    python tools/autotune.py --report [--cost-model costmodel.json]

``--report`` renders the tuned-vs-heuristic A/B per cached key (the
winner is never worse than the heuristic on the measured run — the
heuristic is always in the candidate set) and the cost model's
predicted-vs-measured calibration.  ``--json`` emits one
machine-readable document (schema ``mxtpu-autotune/1``).

Exit codes: 0 ok, 1 a requested tuning/fit failed, 2 usage.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

#: default flash tuning set: the bench/test attention shapes plus the
#: ADVICE r5 cliff lengths (2176 = 128*17 with no larger divisor,
#: 3200 -> 5x640) — small batch/head counts so interpret-mode CPU
#: tuning stays tractable; block choice is governed by (T, D)
DEFAULT_FLASH_SHAPES = ((1, 2048, 2, 64), (1, 2176, 2, 64),
                        (1, 3200, 2, 64))


def _parse_shapes(spec):
    out = []
    for part in spec.split(","):
        dims = tuple(int(x) for x in part.lower().split("x") if x)
        if len(dims) != 4:
            raise ValueError("flash shape %r must have 4 dims (BxTxHxD)"
                             % (part,))
        out.append(dims)
    return out


def _cached(op, shapes, dtypes, extra=None):
    from mxnet_tpu import autotune
    return autotune.lookup(op, shapes, dtypes, extra=extra)


def _runner(args, say, results, skipped, failed):
    """The probe-cache / skip / tune / report-failure step."""
    def run(label, probe, fn):
        entry = None if args.force else probe()
        if entry is not None:
            say("autotune: %-44s cached (wall %.3g ms)"
                % (label, 1e3 * (entry.get("wall_s") or 0)))
            skipped.append({"key": label, "entry": entry})
            return
        try:
            rep = fn()
        except Exception as e:  # mxlint: allow-broad-except(the CLI reports per-key failures and exits nonzero instead of dying on the first unmeasurable key)
            say("autotune: %-44s FAILED: %s" % (label, e))
            failed.append({"key": label, "error": str(e)})
            return
        best, heur = rep["best"], rep["heuristic"]
        delta = ""
        if heur and heur["wall_s"]:
            delta = " (%+.1f%% vs heuristic %s)" % (
                100.0 * (best["wall_s"] - heur["wall_s"])
                / heur["wall_s"], _fmt_cfg(heur["config"]))
        say("autotune: %-44s -> %s  %.3g ms%s"
            % (label, _fmt_cfg(best["config"]),
               1e3 * best["wall_s"], delta))
        results.append(rep)
    return run


def tune_keys(args, say):
    """Run the requested tunings; returns (results, skipped, failed)."""
    from mxnet_tpu import autotune

    results, skipped, failed = [], [], []
    run = _runner(args, say, results, skipped, failed)

    which = args.op.rsplit("_", 1)[1]
    shapes = (_parse_shapes(args.shapes) if args.shapes
              else list(DEFAULT_FLASH_SHAPES))
    for shp in shapes:
        op = "flash_attention_%s" % which
        label = "%s %s causal=%d" % (op, "x".join(map(str, shp)),
                                     args.causal)
        run(label,
            lambda shp=shp, op=op: _cached(
                op, [shp], [args.dtype],
                extra={"causal": bool(args.causal)}),
            lambda shp=shp: autotune.tune_flash(
                shp, dtype=args.dtype, causal=args.causal,
                which=which, repeats=args.repeats,
                max_candidates=args.max_candidates,
                interpret=args.interpret))
    return results, skipped, failed


def _fmt_cfg(cfg):
    if not cfg:
        return "-"
    return ",".join("%s=%s" % (k, v) for k, v in sorted(cfg.items()))


def report(args, say):
    """Tuned-vs-heuristic deltas per cached key + cost-model
    calibration.  Returns (doc, ok)."""
    from mxnet_tpu import autotune
    from mxnet_tpu.telemetry import costdb

    cache_path = args.cache or autotune.cache_dir()
    doc = {"schema": "mxtpu-autotune/1", "report": True,
           "cache": cache_path, "keys": [], "calibration": None}
    entries = []
    if cache_path and os.path.exists(cache_path):
        entries, _skipped = autotune.read_entries(cache_path)
    say("tuning cache: %d entr%s under %r"
        % (len(entries), "y" if len(entries) == 1 else "ies",
           cache_path))
    if entries:
        say("%-24s %-28s %10s %10s %8s" % (
            "op", "tuned config", "tuned", "heuristic", "delta"))
    regressions = 0
    for e in sorted(entries, key=lambda e: (e["op"],
                                            json.dumps(e["shapes"]))):
        tw, hw = e.get("wall_s"), e.get("heuristic_wall_s")
        delta = None
        if tw and hw:
            delta = (hw - tw) / hw
            if tw > hw * (1 + 1e-9):
                regressions += 1
        doc["keys"].append({
            "op": e["op"], "shapes": e["shapes"],
            "dtypes": e["dtypes"], "extra": e.get("extra"),
            "config": e["config"], "wall_s": tw,
            "heuristic_config": e.get("heuristic_config"),
            "heuristic_wall_s": hw,
            "delta_frac": delta, "source": e.get("source"),
        })
        say("%-24s %-28s %10s %10s %8s" % (
            e["op"][:24], _fmt_cfg(e["config"])[:28],
            "%.3gms" % (tw * 1e3) if tw else "-",
            "%.3gms" % (hw * 1e3) if hw else "-",
            "%+.1f%%" % (100 * delta) if delta is not None else "-"))
    doc["tuned_never_worse"] = regressions == 0

    # calibration: a saved model, or fit fresh on the costdb records
    db = args.costdb or costdb.db_dir()
    records = []
    if db and os.path.exists(db):
        records, _sk = costdb.read_records(db)
    model = None
    if args.cost_model:
        model = autotune.load_model(args.cost_model)
    elif records:
        try:
            model = autotune.fit_cost_model(records=records)
        except ValueError as e:
            say("cost model: %s" % e)
    if model is not None and records:
        cal = model.calibration(records)
        cal.pop("rows", None)
        doc["calibration"] = cal
        say("cost model calibration: n=%d  geo err x%.2f  mae(log)="
            "%.3f  fit r2=%.3f"
            % (cal["n"], cal.get("geo_err_factor", float("nan")),
               cal.get("mae_log", float("nan")),
               (cal.get("fit") or {}).get("r2", float("nan"))))
        for w in cal.get("worst", []):
            say("  worst: %-28s measured %.3gms predicted %.3gms "
                "(x%.2f)" % (str(w["name"])[:28], w["measured_s"] * 1e3,
                             w["predicted_s"] * 1e3, w["err_factor"]))
    return doc, regressions == 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="autotune",
        description="tune Pallas block configs; fit/report the "
                    "learned cost model")
    ap.add_argument("--op", choices=("flash_fwd", "flash_bwd"))
    ap.add_argument("--shapes", default=None,
                    help="comma-separated BxTxHxD; defaults to the "
                         "bench + ADVICE-cliff set")
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--repeats", type=int, default=3,
                    help="min-of-N timing repeats per candidate")
    ap.add_argument("--max-candidates", type=int, default=8)
    ap.add_argument("--interpret", action="store_true", default=None,
                    help="force Pallas interpreter mode (default: "
                         "auto — interpret off-TPU)")
    ap.add_argument("--force", action="store_true",
                    help="re-tune keys already in the cache")
    ap.add_argument("--cache", default=None,
                    help="tuning-cache directory (sets "
                         "MXNET_TPU_TUNE_CACHE for this run)")
    ap.add_argument("--costdb", default=None,
                    help="cost-database directory (sets "
                         "MXNET_TPU_COSTDB for this run)")
    ap.add_argument("--fit-model", default=None, metavar="OUT",
                    help="fit the learned cost model on the costdb "
                         "records and save it here")
    ap.add_argument("--cost-model", default=None, metavar="PATH",
                    help="use this saved model for --report instead "
                         "of fitting fresh")
    ap.add_argument("--report", action="store_true",
                    help="render tuned-vs-heuristic deltas + the "
                         "cost-model calibration")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)

    if not (args.op or args.fit_model or args.report):
        # argparse.error raises SystemExit(2)
        ap.error("nothing to do: give --op, --fit-model or --report")
    if args.cache:
        os.environ["MXNET_TPU_TUNE_CACHE"] = args.cache
    if args.costdb:
        os.environ["MXNET_TPU_COSTDB"] = args.costdb

    say = (lambda s: None) if args.as_json \
        else (lambda s: print(s, file=sys.stderr))

    from mxnet_tpu import autotune
    from mxnet_tpu.telemetry import costdb as costdb_mod
    autotune.reload_cache()

    doc = {"schema": "mxtpu-autotune/1", "tuned": 0, "cached": 0,
           "failed": 0, "keys": []}
    ok = True
    if args.op:
        results, skipped, failed = tune_keys(args, say)
        doc["tuned"] = len(results)
        doc["cached"] = len(skipped)
        doc["failed"] = len(failed)
        doc["failures"] = failed
        for rep in results:
            doc["keys"].append({
                "op": rep["op"], "shapes": rep["shapes"],
                "config": rep["best"]["config"],
                "wall_s": rep["best"]["wall_s"],
                "heuristic_wall_s": (rep["heuristic"] or
                                     {}).get("wall_s"),
            })
        for s in skipped:
            doc["keys"].append({
                "op": s["entry"]["op"], "shapes": s["entry"]["shapes"],
                "config": s["entry"]["config"],
                "wall_s": s["entry"].get("wall_s"), "cached": True,
            })
        ok = ok and not failed
        # the candidate measurements are the cost model's food
        costdb_mod.flush()

    if args.fit_model:
        try:
            model = autotune.fit_cost_model(costdb_path=args.costdb)
            model.save(args.fit_model)
            doc["model"] = {"path": args.fit_model,
                            "stats": model.stats}
            say("cost model: fit on %d record(s), r2=%.3f -> %s%s"
                % (model.stats.get("n", 0),
                   model.stats.get("r2", float("nan")),
                   args.fit_model,
                   "  (UNDERDETERMINED: fewer records than features "
                   "— collect more before trusting MXG010)"
                   if model.stats.get("underdetermined") else ""))
        except (ValueError, OSError) as e:
            say("cost model fit FAILED: %s" % e)
            doc["model"] = {"error": str(e)}
            ok = False

    if args.report:
        rep_doc, rep_ok = report(args, say)
        doc.update(rep_doc)
        ok = ok and rep_ok

    if args.as_json:
        print(json.dumps(doc, sort_keys=True))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
