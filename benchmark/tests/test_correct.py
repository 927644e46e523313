"""``correct`` comes out false when it should: the control and a broken timed path.

Toy size on the CPU (``smoke_cells.json``), held to the limits of the real cells:
the numbers compared are relative gaps, which do not depend on the size.

1. The control: the plain reference computed in the configuration's
   ``control_precision`` (fp8 operands), put in the program's place, is refused by
   the limits of the real configuration.
2. The harness, its look for a chip skipped, drives a whole run with the timed path
   broken underneath (a chain that returns the trainer's state unchanged) and
   reports ``correct`` false; unbroken, it reports true.

Run: ``JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q``.
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import check  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402

SEED = 2 ** 31 + 4242
PAIRS = [("smoke-resnet", "resnet50-v2"), ("smoke-opt", "opt-1.3b")]


def smoke_cell(name):
    bench = dict(run.load_json(run.ROOT, "BENCHMARK.json"),
                 **run.load_json(run.HERE, "smoke_cells.json"))
    return run.Cell(name, bench)


@pytest.mark.parametrize("toy,real", PAIRS)
def test_control_is_refused_by_the_real_limits(toy, real):
    import jax
    cell = smoke_cell(toy)
    limits = run.load_json(run.HERE, "configs", real + ".json")["limits"]
    hb = traffic.host_batch(cell.cfg, cell.mix, 1, SEED)
    steps = 1 + int(cell.mix["chain"])
    dev = jax.devices()[:1]
    ref = run.reference_first_steps(cell, SEED, hb, steps, dev)
    again = run.reference_first_steps(cell, SEED, hb, steps, dev)
    control = run.reference_first_steps(cell, SEED, hb, steps, dev,
                                        quant=cell.cfg["control_precision"])
    lines = []
    assert check.compare(again, ref, limits, say=lines.append)
    assert not check.compare(control, ref, limits, say=lines.append), lines


@pytest.mark.parametrize("broken", [False, True])
def test_run_with_a_chain_that_leaves_the_state_unchanged(monkeypatch, broken):
    cell = smoke_cell("smoke-resnet")
    if broken:
        real_open = cell.runner.open

        def open_broken(*args):
            session = real_open(*args)
            trainer = session.trainer
            real_run = trainer.run_steps

            def run_steps(batch, n):
                keep = (trainer.params, trainer.opt_state, trainer.aux)
                copies = [__import__("jax").tree_util.tree_map(lambda a: a.copy(), k) for k in keep]
                losses = real_run(batch, n)
                trainer.params, trainer.opt_state, trainer.aux = copies
                return losses

            monkeypatch.setattr(trainer, "run_steps", run_steps)
            return session

        monkeypatch.setattr(cell.runner, "open", open_broken)
    result = run.run_cell(cell, SEED, 0.3, 0, on_chip=False)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"] is (not broken)
