"""``moe_small_buffer_pct.tok`` on hand-made plans and samples, as the other
``moe_*`` readers are held (``tests/test_lfm2_moe.py``): a plan with and without
a second size, samples inside and before the window, an older program; and
``moe.note_compiled`` counting the expert layers of a text that holds both
branches' grouped products.

Run: ``JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q``.
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run  # noqa: E402
from mxnet_tpu.parallel import moe  # noqa: E402

CTX = {"samples": [(100.0, 100.1, 100.8, [1.0]), (100.8, 100.9, 101.6, [1.0])]}


def read(ctx=CTX):
    return run.load_module("layer_metrics", "moe_small_buffer_pct").read(ctx)


def sample(*fits):
    return {"layer%d" % i: dict({"assignments": [8.0] * 4, "tokens_unrouted": 0.0},
                                **({} if fit is None else {"small_buffer": fit}))
            for i, fit in enumerate(fits)}


def plan(*small_rows):
    return {"grouped_layers": None,
            "layers": [{"buffer_rows": 128, "small_rows": rows} for rows in small_rows]}


def test_share_of_the_windows_pairs_that_fit(monkeypatch):
    monkeypatch.setattr(moe, "_LAST_SUMMARY", plan(64, 64))
    monkeypatch.setattr(moe, "_LOAD_SAMPLES", [
        (50.0, sample(0.0, 0.0)), (100.5, sample(1.0, 1.0)), (101.0, sample(1.0, 0.0)),
        (200.0, sample(0.0, 0.0))])
    assert read() == 75.0                      # three of the window's four pairs
    monkeypatch.setattr(moe, "_LOAD_SAMPLES", [(100.5, sample(1.0, 1.0))])
    assert read() == 100.0
    # no sample inside the window: the newest one before it
    monkeypatch.setattr(moe, "_LOAD_SAMPLES", [(20.0, sample(1.0, 1.0)),
                                               (50.0, sample(0.0, 1.0))])
    assert read() == 50.0


def test_a_layer_with_one_size_is_no_pair(monkeypatch):
    monkeypatch.setattr(moe, "_LOAD_SAMPLES", [(100.5, sample(1.0, None)),
                                               (101.0, sample(0.0, None))])
    monkeypatch.setattr(moe, "_LAST_SUMMARY", plan(64, None))
    assert read() == 50.0
    # no layer of the plan has a second size: nothing to say
    monkeypatch.setattr(moe, "_LAST_SUMMARY", plan(None, None))
    assert read() is None


def test_none_on_an_older_program(monkeypatch):
    monkeypatch.setattr(moe, "_LOAD_SAMPLES", [(100.5, sample(None, None))])
    # the parent's plan: no ``small_rows`` key, no ``small_buffer`` in a sample
    monkeypatch.setattr(moe, "_LAST_SUMMARY", {"layers": [{"buffer_rows": 128}] * 2})
    assert read() is None
    monkeypatch.setattr(moe, "_LAST_SUMMARY", None)
    assert read() is None
    monkeypatch.setattr(moe, "_LOAD_SAMPLES", [])
    assert read() is None
    monkeypatch.delattr(moe, "last_plan_summary")
    assert read() is None
    monkeypatch.delattr(moe, "load_samples")
    assert read() is None


class _Text:
    def __init__(self, products):
        self.products = products

    def as_text(self):
        return "\n".join(
            ["  %%ragged-dot-none.%d = bf16[16384,1792]{1,0:T(8,128)(2,1)} custom-call(%%a, "
             "%%b), custom_call_target=\"tpu_custom_call\"" % i for i in range(self.products)]
            + ["  %ragged-dot-metadata.4 = (s32[9]{0}, s32[39]{0}) custom-call(%g)",
               "  %conditional.2 = (bf16[8192,2048]{1,0}) conditional(%p, %a, %b), "
               "branch_computations={%region_10.32, %region_12.47}"])


@pytest.mark.parametrize("products_trained,products,layers", [
    (9, 72, 4), (9, 144, 4), (9, 54, 3), (9, 36, 2), (6, 48, 4), (6, 47, 3)])
def test_note_compiled_counts_a_two_size_layer_by_both_branches(products_trained, products,
                                                                layers):
    """Four layers with two sizes: the step's text holds each branch's calls (a 2-step
    chain unrolled holds them twice), and a layer is covered by twice its
    ``products_trained``."""
    with moe.plan_recording():
        for _ in range(4):
            moe.note_layer(buffer_rows=32768, small_rows=16384,
                           products_trained=products_trained)
    moe.note_compiled(_Text(products))
    summary = moe.last_plan_summary()
    assert (summary["grouped_products"], summary["grouped_layers"]) == (products, layers)
    grouped = run.load_module("layer_metrics", "moe_grouped_layers")
    assert grouped.read(CTX) == layers
    per_layer = run.load_module("layer_metrics", "moe_products_per_layer")
    assert per_layer.read(CTX) == products_trained     # what a step runs, not what it compiled
