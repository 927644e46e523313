"""One run of a benchmark cell with its set-up laid out as a table of span records.

    chiprun -- python3 tools/setup_timeline.py --workload resnet50-fused-b128 --seed 77 --trace 1
    python3 tools/setup_timeline.py --read chiprun_out/setup_timeline/resnet50-fused-b128.json
    python3 tools/setup_timeline.py --workload smoke-resnet --seed 7 --smoke   # CPU, toy size

Runs ``benchmark/run.py``'s ``run_cell`` in this process (the harness's own clock
``T0`` starts at its import, as under ``python3 benchmark/run.py``), keeps what
the harness knows and hands to no reader (``T0``, the reference's seconds, the
instant ``setup_s`` is taken, the window's first dispatch), and dumps them with
the span ring to ``<out>/<workload>.json``.  The table is a function of the
dump: ``setup_s`` = the seconds before the session (read by hand from the
harness's marks) + what the per-layer readers of ``benchmark/layer_metrics``
give for the session + the seconds after its last record.  PERF.md section 5
holds the six cells' tables.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

READERS = ("import_s", "model_build_s", "build_s", "build_graph_s", "init_params_s",
           "build_plan_s", "put_batch_s", "lower_s", "first_dispatch_s", "jit_unplanned_s",
           "setup_unspanned_s")


def run_and_dump(args):
    import run   # the harness: its T0 starts here
    said, kept = {}, {}
    say, measure = run.say, run.measure

    def keep_say(tag, **fields):
        import time
        said[tag], kept["said_" + tag] = fields, time.perf_counter()
        say(tag, **fields)

    def keep_measure(*a, **kw):
        import time
        kept["setup_mark"] = time.perf_counter()   # setup_s was taken just before
        out = measure(*a, **kw)
        kept["first_dispatch"], kept["window_end"] = out[1][0][0], out[1][-1][2]
        return out

    run.say, run.measure = keep_say, keep_measure
    if args.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
        cells = run.load_json(run.HERE, "smoke_cells.json")
        cell = run.Cell(args.workload, dict(run.load_json(run.ROOT, "BENCHMARK.json"), **cells))
    else:
        cell = run.Cell(args.workload)
    result = run.run_cell(cell, args.seed, args.seconds, args.trace, on_chip=not args.smoke)
    result.pop("breakdown", None)
    from mxnet_tpu.telemetry import spans
    dump = {"workload": args.workload, "seed": args.seed, "t0": run.T0,
            "reference_s": said["reference"]["seconds"], "built_s": said["built"]["seconds"],
            "setup_s": said["window"].get("setup_s"), "result": result, **kept,
            "records": [list(r) for r in spans.records()]}
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, args.workload + ".json")
    with open(path, "w") as f:
        json.dump(dump, f)
    print("setup_timeline wrote %s (%d records)" % (path, len(dump["records"])))
    return dump


def table(dump):
    """The rows ``(name, seconds, note)`` of one dump, from the committed readers."""
    import run
    from mxnet_tpu.telemetry import spans
    from layer_metrics import setup_spans
    spans.clear()
    spans._ring.extend(spans.Record(*r) for r in dump["records"])
    ctx = {"samples": [(dump["first_dispatch"],)]}
    v = {n: run.load_module("layer_metrics", n).read(ctx) for n in READERS}
    recs = setup_spans.setup_records(ctx)
    by_id = {r.id: r for r in recs}
    t0, ref_s, mark = dump["t0"], dump["reference_s"], dump["setup_mark"]
    setup_s = dump["setup_s"] if dump["setup_s"] is not None else mark - t0 - ref_s
    rows = [("setup_s", setup_s, "the harness's: T0 to the mark before the window, less the reference")]
    found = setup_spans.session(ctx)
    if found is None:
        return rows + [("(no session: no model.build record)", None, "")]
    inside, start, end, thread = found

    def named(name, among=inside):
        return [r for r in among if r.name == name]

    def total(rs):
        return sum(r.end - r.start for r in rs)

    before = start - t0 - ref_s
    rows.append(("before the session", before, "hand: T0 to the first model.build, less reference_s"))
    # the harness's marks and the import's record, in the order they came
    marks = [(t0, "T0"), (dump["said_start"], "start line (jax imported, runtime up)"),
             (dump["said_reference"] - ref_s, "reference begins (the harness's batch made)"),
             (dump["said_reference"], "reference ends"), (start, "model.build")]
    for r in named("mxnet_tpu.import", recs)[-1:]:
        marks += [(r.start, "import begins"), (r.end, "import ends (import_s)")]
    marks.sort()
    for (a, _was), (b, now) in zip(marks, marks[1:]):
        if now != "reference ends":
            rows.append(("  to " + now, b - a, ""))
    pre = named("process.before_import", recs)
    if pre:
        rows.append(("  (process start to T0)", t0 - pre[0].start,
                     "not in setup_s; %s" % json.dumps(pre[0].attrs)))
    lower, compiled = setup_spans.PLANNED
    planned = [r for r in inside if r.name in setup_spans.PLANNED
               and setup_spans.under(r, by_id, ("trainer.run_steps",))]
    top = [r for r in inside if r.thread == thread and r.name.startswith("jax.")
           and by_id.get(r.parent) is None]
    parts = [("model_build_s", v["model_build_s"], ""),
             ("mesh.build", total(named("mesh.build")), "no reader"),
             ("build_s", v["build_s"],
              "graph %.3f + init_params_s %.3f + plan %.3f + own" % tuple(
                  x or 0.0 for x in (v["build_graph_s"], v["init_params_s"], v["build_plan_s"]))),
             ("put_batch_s", v["put_batch_s"], json.dumps([r.attrs for r in named("trainer.put_batch")])),
             ("lower_s (planned, in dispatches)", total([r for r in planned if r.name == lower]),
              "lower_s reads %.3f" % (v["lower_s"] or 0.0)),
             ("program.compile (the planned jax.compile)",
              total([r for r in planned if r.name == compiled]), ""),
             ("first_dispatch_s", v["first_dispatch_s"],
              "of it program.plan %.3f, .launch %.3f, .sync %.3f" % (
                  total(named("program.plan")), total(named("trainer.run_steps.launch")),
                  total(named("trainer.run_steps.sync")))),
             ("jit at top level", setup_spans.union_seconds(top),
              "jit_unplanned_s reads %.3f; the rest lies in build_s, put_batch_s, first_dispatch_s"
              % (v["jit_unplanned_s"] or 0.0)),
             ("setup_unspanned_s", v["setup_unspanned_s"], "")]
    rows += parts
    rows.append(("after the session", mark - end, "hand: last record's end to the mark (the comparison)"))
    covered = before + sum(x or 0.0 for _n, x, _ in parts) + (mark - end)
    rows.append(("residue", setup_s - covered, "setup_s less the rows above; %.1f%%"
                 % (100.0 * (setup_s - covered) / setup_s)))
    gaps = spans.uncovered(start, end, thread)[:3] if hasattr(spans, "uncovered") else []
    for g in gaps:
        rows.append(("  uncovered %.3f" % (g.end - g.start), g.end - g.start,
                     "after %s, before %s" % (g.before, g.after)))
    n = sum(1 for r in dump["records"] if r[2] <= dump["first_dispatch"])
    rows.append(("records before the window", n, "x 3.9 us = %.2f ms" % (n * 3.9e-3)))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--smoke", action="store_true", help="a toy cell of smoke_cells.json, on the CPU")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "setup_timeline"))
    ap.add_argument("--read", help="print the table of a dump and run nothing")
    a = ap.parse_args(argv)
    if a.read:
        with open(a.read) as f:
            dump = json.load(f)
    else:
        dump = run_and_dump(a)
    print("setup timeline of %s (seed %s)" % (dump["workload"], dump["seed"]))
    for name, secs, note in table(dump):
        print("%-44s %10s  %s" % (name, "-" if secs is None else "%.3f" % secs, note))
    return 0


if __name__ == "__main__":
    sys.exit(main())
