"""mxnet_tpu.autotune — Pallas autotuner, tuning cache, learned cost
model (ROADMAP item 2; arXiv:1802.04799 + arXiv:2008.01040).

The first subsystem that *acts* on the perf ground truth the cost
database (``telemetry.costdb``) collects, instead of only recording it.
Three parts (see docs/api/autotune.md for the full contract):

* **search harness** (:mod:`.search`) — enumerate block-config
  candidates for a tunable kernel key ``(op, shape signature, dtypes,
  mesh, backend)``, measure each with the shared synchronized
  min-of-N runner (compile excluded, ``interpret=True`` keeps the real
  Pallas path exercisable on CPU CI), and commit the winner;
* **persistent tuning cache** (:mod:`.cache`) — JSONL schema
  ``mxtpu-tunecache/1`` under ``MXNET_TPU_TUNE_CACHE``, merged on load
  (best measured wall wins) so caches from multiple hosts/runs
  compose.  The trace-time consumer — ``ops/pallas_kernels`` flash
  fwd/bwd — consults it first and falls back to the built-in heuristic
  on a miss, emitting ``mxtpu_tune_cache_{hit,miss}_total`` and a
  ``tune_lookup`` flight event; ``MXNET_TPU_AUTOTUNE=off|cache|search``
  gates the behavior (``search`` turns a miss into a bounded inline
  search);
* **learned cost model** (:mod:`.model`) — a numpy ridge regression of
  ``log(wall)`` over roofline-normalized features fit on the costdb
  records, with ``fit``/``predict``/``save``/``load`` and a
  calibration report; analysis rule MXG010
  (:mod:`mxnet_tpu.analysis.perf`) uses it to name predicted-slow
  graph nodes before compile.

Driver: ``tools/autotune.py`` (per-op tuning, zoo-model mode,
``--fit-model``, ``--report`` with tuned-vs-heuristic deltas).
"""
from __future__ import annotations

from .cache import (SCHEMA, TuneCache, CACHE, autotune_mode, cache_dir,
                    key_sig, kernel_config, lookup, put,
                    read_entries, reload_cache, summary, reset_stats)
from .search import (measure, divisors, candidate_flash_configs,
                     tune_flash, inline_search, same_config)
from .model import (CostModel, FEATURES, featurize, fit_cost_model,
                    load_model)

__all__ = [
    "SCHEMA", "TuneCache", "CACHE", "autotune_mode", "cache_dir",
    "key_sig", "kernel_config", "lookup", "put",
    "read_entries", "reload_cache", "summary", "reset_stats",
    "measure", "divisors", "candidate_flash_configs",
    "tune_flash", "inline_search", "same_config",
    "CostModel", "FEATURES", "featurize", "fit_cost_model",
    "load_model",
]
