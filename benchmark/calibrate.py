"""Readings that the limits of ``correct`` are set from, at a cell's own size.

    python3 benchmark/calibrate.py --workload <name> --seeds 12 --control-seeds 3

In one process: for every seed the plain reference's first steps, for the first
``--control-seeds`` of them the control's (the reference computed in the
configuration's ``control_precision``, put in the program's place), then the
program's first steps on every seed through one session (``restart`` between
seeds, so it compiles once).  Prints every number compared for both, the sound
runs' largest, the control's smallest and their ratio.  Not part of a run of the
benchmark; ``PERF.md`` quotes its output beside each limit.
"""
from __future__ import annotations

import argparse
import json
import sys

import run
from run import say


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1001)
    ap.add_argument("--dump", default=None, help="write every leaf's norms of every run here")
    ap.add_argument("--cpu", action="store_true", help="rehearsal at toy size (smoke cells)")
    a = ap.parse_args(argv)

    bench = None
    if a.cpu:
        bench = dict(run.load_json(run.ROOT, "BENCHMARK.json"),
                     **run.load_json(run.HERE, "smoke_cells.json"))
    cell = run.Cell(a.workload, bench)
    import check
    import traffic
    if not a.cpu:
        run.place_cache()
    devices, _peak = run.chips_or_die(cell.chips, allow_cpu=a.cpu)
    steps = 1 + int(cell.mix["chain"])
    seeds = [a.first_seed + 7919 * i for i in range(a.seeds)]
    refs, controls = {}, {}
    for i, seed in enumerate(seeds):
        hb = traffic.host_batch(cell.cfg, cell.mix, cell.chips, seed)
        refs[seed] = run.reference_first_steps(cell, seed, hb, steps, devices)
        say("reference", seed=seed, losses=refs[seed]["losses"], footprint_bytes=refs[seed]["footprint_bytes"])
        if i < a.control_seeds:
            controls[seed] = run.reference_first_steps(
                cell, seed, hb, steps, devices, quant=cell.cfg["control_precision"])
            say("control", seed=seed, losses=controls[seed]["losses"],
                numbers={n: v for n, v, _ in check.numbers(controls[seed], refs[seed])})

    session, sound, progs = None, {}, {}
    for seed in seeds:
        hb = traffic.host_batch(cell.cfg, cell.mix, cell.chips, seed)
        if session is None:
            session = cell.runner.open(cell.cfg, cell.cfgmod, cell.mix, devices, seed,
                                       lambda key: cell.refmod.init_params(cell.cfg, key),
                                       run.seed_key(seed), hb)
        else:
            session.restart(run.seed_key(seed), hb)
        prog = progs[seed] = session.first_steps()
        sound[seed] = {n: v for n, v, _ in check.numbers(prog, refs[seed])}
        say("program", seed=seed, losses=prog["losses"][:3], ref_losses=refs[seed]["losses"][:3],
            numbers=sound[seed], peak_bytes=run.peak_bytes(devices))
    session.close()

    summary = {}
    for name in next(iter(sound.values())):
        hi = max(v[name] for v in sound.values())
        ctl = [check_numbers[name] for check_numbers in
               ({n: v for n, v, _ in check.numbers(c, refs[s])} for s, c in controls.items())]
        lo = min(ctl) if ctl else None
        summary[name] = {"sound_largest": hi, "control_smallest": lo,
                         "ratio": (lo / hi if lo is not None and hi > 0 else None),
                         "sound_all": [v[name] for v in sound.values()], "control_all": ctl}
    if a.dump:
        with open(a.dump, "w") as f:
            slim = lambda runs: {s: {k: v for k, v in r.items() if k != "grad_samples"}  # noqa: E731
                                 for s, r in runs.items()}
            json.dump({"refs": slim(refs), "controls": slim(controls), "programs": slim(progs)}, f)
    print("calibrate %s %s" % (cell.name, json.dumps(summary)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
