"""The harness at toy size on the CPU: ``python3 benchmark/smoke.py``.

Drives ``run.run_cell`` (reference, session, first steps, comparison, window)
for a toy copy of each configuration, the four-chip mix on four virtual
devices.  Prints counts only: no time, rate or device metric comes from a CPU.
"""
from __future__ import annotations

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4").strip()

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402


def main():
    cells = run.load_json(HERE, "smoke_cells.json")
    bench = dict(run.load_json(run.ROOT, "BENCHMARK.json"), **cells)
    ok = True
    for w in cells["workloads"]:
        result = run.run_cell(run.Cell(w["name"], bench), seed=2 ** 31 + 17, seconds=0.5,
                              trace=0, on_chip=False)
        good = result["correct"] and result["failed"] == 0 and result["attempted"] > 0
        print("smoke %s: correct=%s attempted=%d failed=%d devices=%d"
              % (w["name"], result["correct"], result["attempted"], result["failed"],
                 result["device"]["count"]))
        ok = ok and good
    print("smoke %s" % ("passed" if ok else "FAILED"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
