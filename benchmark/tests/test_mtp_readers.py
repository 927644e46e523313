"""``mla_query_latent_layers.tok``, ``mtp_modules.tok``, ``mtp_head_rows_pct.tok``
and ``mtp_shared_params.tok`` on recorded plans: what a traced step of
``glm47flash-fused-s4096`` notes (six latent-attention layers with both options,
one module that shares two parameters), Kimi Linear's plan (latent attention with
neither option, no module), a module whose head is a copy, and a program that
keeps no plan.

Run: ``JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q``.
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run  # noqa: E402
from mxnet_tpu.telemetry import plan  # noqa: E402

READERS = ("mla_query_latent_layers", "mtp_modules", "mtp_head_rows_pct",
           "mtp_shared_params")
MLA = {"q_lora_rank": 768, "rope_dims": 64, "dk": 256, "dv": 256, "heads": 20}
MTP = {"depth": 1, "layer_rows": 4096, "head_rows": 8192, "loss_weight": 0.3,
       "shared": ["embed_weight", "lm_head_weight"]}
#: what Kimi Linear's one latent-attention layer notes, and its expert layers
KIMI = {"mxtpu.block.mla": ([dict(MLA, q_lora_rank=None, rope_dims=0, dk=192,
                                  dv=128, heads=32)], {}),
        "mxtpu.block.moe": ([{"buffer_rows": 8192}] * 4, {})}


def read_all():
    return tuple(run.load_module("layer_metrics", name).read({}) for name in READERS)


@pytest.mark.parametrize("recorded,want", [
    ({"mxtpu.block.mla": ([MLA] * 6, {}), "mxtpu.block.mtp": ([MTP], {})},
     (6, 1, 200.0, 2)),
    (KIMI, (0, 0, None, None)),
    ({"mxtpu.block.mla": ([MLA] * 5, {}),
      "mxtpu.block.mtp": ([dict(MTP, shared=["embed_weight"])], {})},
     (5, 1, 200.0, 1)),
    ({"mxtpu.block.mtp": ([dict(MTP, head_rows=4096 + 4095)], {})},
     (None, 1, 100.0 * 8191 / 4096, 2)),
    ({"mxtpu.block.moe": ([{"buffer_rows": 8192}], {})}, (None, 0, None, None)),
    ({}, (None, None, None, None)),
], ids=["glm47flash", "kimi-linear", "a-copied-head", "sliced-last-row",
        "no-latent-attention", "nothing-traced"])
def test_readers_on_a_recorded_plan(monkeypatch, recorded, want):
    monkeypatch.setattr(plan, "_LAST", recorded)
    assert read_all() == want


def test_every_new_reader_is_an_entry_of_the_new_cell_alone():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        entry = entries[name + ".tok"]
        assert entry["workloads"] == ["glm47flash-fused-s4096"]
        assert (entry["source"], entry["moves"]) == ("program_counter",
                                                     "tokens_per_s_chip")
    assert entries["mtp_head_rows_pct.tok"]["better"] == "lower"
