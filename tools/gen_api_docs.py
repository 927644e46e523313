"""Generate markdown API docs into docs/api/.

Reference counterpart: the sphinx-generated `docs/api/python/*` tree.
Two sources, both introspected from the live package so the docs cannot
drift from the code:

* the operator registry — every op with its argument names, attrs (with
  defaults), and docstring summary (one page for nd/sym, since one
  registration feeds both surfaces);
* the python modules — public classes/functions with signatures and
  docstring summaries.

Usage: python tools/gen_api_docs.py   (writes docs/api/*.md)
"""
from __future__ import annotations

import inspect
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

OUT = os.path.join(os.path.dirname(__file__), "..", "docs", "api")

MODULES = [
    ("ndarray", "mxnet_tpu.ndarray"),
    ("symbol", "mxnet_tpu.symbol"),
    ("executor", "mxnet_tpu.executor"),
    ("module", "mxnet_tpu.module"),
    ("model", "mxnet_tpu.model"),
    ("io", "mxnet_tpu.io"),
    ("image", "mxnet_tpu.image"),
    ("recordio", "mxnet_tpu.recordio"),
    ("optimizer", "mxnet_tpu.optimizer"),
    ("initializer", "mxnet_tpu.initializer"),
    ("metric", "mxnet_tpu.metric"),
    ("kvstore", "mxnet_tpu.kvstore"),
    ("lr_scheduler", "mxnet_tpu.lr_scheduler"),
    ("autograd", "mxnet_tpu.autograd"),
    ("operator (CustomOp)", "mxnet_tpu.operator"),
    ("rnn", "mxnet_tpu.rnn"),
    ("parallel", "mxnet_tpu.parallel"),
    ("monitor", "mxnet_tpu.monitor"),
    ("profiler", "mxnet_tpu.profiler"),
    ("visualization", "mxnet_tpu.visualization"),
    ("callback", "mxnet_tpu.callback"),
    ("random", "mxnet_tpu.random"),
    ("context", "mxnet_tpu.context"),
    ("rtc", "mxnet_tpu.rtc"),
    ("predictor (deployment inference)", "mxnet_tpu.predictor"),
]

# hand-written pages kept alongside the generated ones (never
# overwritten here, only indexed): title -> filename
HAND_WRITTEN = [
    ("language-model ops (RMSNorm, rotary, short convolution, "
     "grouped-query, latent and sliding-window flash attention, gated "
     "delta rule, "
     "top-k expert layer)",
     "language_model_ops.md"),
    ("resilience", "resilience.md"),
    ("analysis (static verifier + mxlint)", "analysis.md"),
    ("telemetry (metrics, spans, run reports)", "telemetry.md"),
    ("fusion (block-granularity fusion + layout planning)", "fusion.md"),
    ("autotune (Pallas autotuner, tuning cache, learned cost model)",
     "autotune.md"),
    ("plansearch (cost-model-guided whole-graph plan search)",
     "plansearch.md"),
    ("reshard (elastic training: checkpoint resharding, rank "
     "join/leave)", "reshard.md"),
    ("overlap (bucketed async gradient allreduce overlapped with "
     "backward, double-buffered staging)", "overlap.md"),
    ("io_resume (exactly-once data plane: durable iterator state, "
     "elastic cursor remap, backpressure)", "io_resume.md"),
    ("memlive (static memory-liveness: bind-time peak-HBM prediction, "
     "remat ranking, donation/ZeRO audit)", "memlive.md"),
    ("serving (production predict path: batch-ladder AOT, continuous "
     "batching, deadline scheduling, load shedding)", "serving.md"),
]

# cross-links appended to generated pages (page key = module filename
# stem): the generator owns these files, so hand-edits would be lost —
# declare the links here instead
SEE_ALSO = {
    "predictor": ["[serving](serving.md) — the production predict "
                  "path over Predictor handles: the batch ladder AOT-"
                  "compiles one `reshaped()` rung per batch size at "
                  "startup, the continuous batcher pads coalesced "
                  "requests with `pad_batch` (the same helper "
                  "`set_input` uses for its pad-and-slice partial-"
                  "batch contract), and nothing compiles on the "
                  "request path",
                  "[telemetry](telemetry.md) — the predictor's "
                  "executor dispatches through the AOT memory-plan "
                  "path (`telemetry.memory.planned_executable`); the "
                  "serving tier's `mxtpu_serve_*` instruments ride "
                  "the same registry"],
    "executor": ["[fusion](fusion.md) — block-granularity fusion + "
                 "layout planning: the `block_fusion` flag captured at "
                 "bind time lowers conv+BN+ReLU / FC+activation chains "
                 "as single fused regions on forward AND the custom-VJP "
                 "backward",
                 "[analysis](analysis.md) — `bind(..., strict=True)` "
                 "graph verification before any compile",
                 "[telemetry](telemetry.md) — executor fwd/bwd/fused "
                 "spans, the per-program memory plan, flight-recorder "
                 "dumps on dispatch failures, and the cost database "
                 "(`telemetry.costdb`): sampled dispatch timing joined "
                 "with flops/bytes into persistent MFU/roofline "
                 "records ranked by `tools/perf_top.py`",
                 "[autotune](autotune.md) — the persistent tuning "
                 "cache the Pallas kernels and fused regions consult "
                 "at trace time (`MXNET_TPU_TUNE_CACHE`; "
                 "`tools/autotune.py` searches it)",
                 "[plansearch](plansearch.md) — the committed "
                 "whole-graph fusion/layout plan (`graph_plan` tuning-"
                 "cache entry) consulted ONCE at bind and activated "
                 "around every trace; greedy on miss "
                 "(`MXNET_TPU_PLAN_SEARCH`; `tools/plan_search.py` "
                 "searches it)",
                 "[telemetry](telemetry.md) training-health numerics "
                 "(`telemetry.numerics`): `set_stats_monitor` computes "
                 "per-node stat bundles INSIDE one compiled forward — "
                 "the jit-safe default Monitor path; the eager "
                 "`_forward_monitored` route is the NaN/Inf provenance "
                 "replay"],
    "io": ["[resilience](resilience.md) — bad-record quotas, the "
           "io.prefetch/io.decode/recordio.read fault seams, "
           "retry/backoff",
           "[telemetry](telemetry.md) — prefetch depth/stall gauges, "
           "records-read counters, the JSONL step-log",
           "[telemetry](telemetry.md) input-pipeline observability "
           "(`telemetry.ioview`): per-stage wall/items/bytes "
           "accounting through the prefetchers, time-weighted queue "
           "occupancy, producer-starved vs consumer-stalled "
           "attribution, and the `position()` API every iterator (and "
           "wrapper) here implements — rendered by `tools/io_top.py`",
           "[overlap](overlap.md) — `DevicePrefetchIter`'s "
           "double-buffered H2D staging (the worker holds one staged "
           "batch aside of the queue so the next transfer dispatches "
           "under backpressure) and the thread-free "
           "`ShardedTrainer.staged_batches` sibling",
           "[io_resume](io_resume.md) — the durable `state()`/"
           "`restore()` contract every tier here implements "
           "(wrappers report the next *undelivered* sample), the "
           "checkpoint `meta.data_state` entry, and the backpressure "
           "controller actuating `DevicePrefetchIter.set_depth`"],
    "model": ["[resilience](resilience.md) — atomic checkpoint writes, "
              "the manifest format, latest-checkpoint fallback",
              "[reshard](reshard.md) — manifest schema v2 mesh "
              "descriptors, `find_latest_checkpoint` as the elastic "
              "resume point, and the offline `tools/reshard.py` "
              "converter",
              "[telemetry](telemetry.md) input-pipeline observability "
              "(`telemetry.ioview`): `save_checkpoint` records the "
              "tracked data iterator's `position()` in the manifest "
              "meta as advisory `data_position` — the recorded half "
              "of mid-epoch resume",
              "[io_resume](io_resume.md) — exact mid-epoch resume: "
              "`save_checkpoint` also writes the tracked iterator's "
              "durable `state()` as `meta.data_state`, and "
              "`fit`/`load_checkpoint` restore it so training resumes "
              "at the exact next sample"],
    "module": ["[resilience](resilience.md) — fault injection, "
               "preemption-safe training, chaos testing",
               "[analysis](analysis.md) — `Module.bind(..., "
               "strict=True)` graph verification",
               "[telemetry](telemetry.md) — per-step spans and the "
               "`fit` step-log/report"],
    "recordio": ["[resilience](resilience.md) — bad-record quota and "
                 "magic-resync semantics",
                 "[telemetry](telemetry.md) — records/bad-record/"
                 "resync counters this reader emits, the ioview "
                 "`read` stage accounting per record, and the "
                 "reader's `position()` (epoch/offset/resyncs) riding "
                 "step records and checkpoint manifests",
                 "[io_resume](io_resume.md) — the reader's durable "
                 "`state()` (`kind=recordio`: byte offset + epoch + "
                 "resync count) restored by `restore_iterator` for "
                 "exact mid-epoch resume, chaos-gated through the "
                 "`io.resume` seam"],
    "parallel": ["[language-model ops](language_model_ops.md) — "
                 "`topk_moe` as `_contrib_TopKMoE`: the share it holds, "
                 "its buffer rule (`parallel.moe.buffer_rows`) and when "
                 "no assignment can be dropped",
                 "[resilience](resilience.md) — multihost init/barrier "
                 "timeouts, watchdog restarts, preemption handler",
                 "[analysis](analysis.md) — MXG007 sharding-coverage "
                 "verification against tp_rules, and the "
                 "distributed-correctness pass (MXG011-016, "
                 "`analysis.spmd`): collective matching, pipeline "
                 "partition validity, sharding-spec composition and "
                 "fwd/bwd collective duality, run at "
                 "`ShardedTrainer(strict=True)` bind time",
                 "[telemetry](telemetry.md) — trainer/pipeline spans, "
                 "kvstore traffic counters, the trainer step's memory "
                 "plan + HBM budget check, the flight-recorder black "
                 "box dumped on step failures, and the cross-rank view "
                 "(`telemetry.distview`): per-step compute/input/"
                 "collective segments, the pre-collective timestamp "
                 "barrier measuring rank skew, and the launch.py "
                 "run timeline rendered by `tools/run_top.py`; "
                 "`ShardedTrainer.cost_summary()` surfaces the cost "
                 "database's per-program wall/MFU roll-up "
                 "(`telemetry.costdb`)",
                 "[fusion](fusion.md) — `ShardedTrainer(fuse_blocks=...)`"
                 ": block-granularity fusion + layout planning on the "
                 "fused train step",
                 "[plansearch](plansearch.md) — the searched whole-"
                 "graph plan the trainer consults at construction, "
                 "keyed per (graph digest, layout, mesh, backend)",
                 "[reshard](reshard.md) — elastic training: "
                 "`ShardedTrainer.load_checkpoint` reshards across mesh "
                 "shapes via the manifest mesh descriptor, "
                 "`MXNET_TPU_RESHARD_RULES` rule tables override the "
                 "derived tp_rules, `DistKVStore.save_state/load_state` "
                 "migrate kvstore state across world sizes, and "
                 "`tools/launch.py --elastic` restarts a fleet at the "
                 "surviving size",
                 "[telemetry](telemetry.md) training-health numerics "
                 "(`telemetry.numerics`): `MXNET_TPU_NUMERICS_EVERY` "
                 "samples in-graph param/grad/fused-block stats inside "
                 "the jitted step, anomaly rules stop a strict run with "
                 "NaN provenance, and the per-step ledger feeds "
                 "`tools/numdiff.py` divergence bisection",
                 "[overlap](overlap.md) — communication overlap "
                 "(`parallel.overlap`): size-targeted gradient buckets "
                 "launched asynchronously as backward produces "
                 "cotangents, the slowest-to-produce-first drain "
                 "scheduler fed by the fleet-agreed skew histograms, "
                 "the all-or-nothing drain contract chaos-tested "
                 "through the `kvstore.collective` seam, and "
                 "`staged_batches` double-buffered H2D staging",
                 "[io_resume](io_resume.md) — exactly-once data "
                 "plane: `ShardedTrainer.save_checkpoint` carries the "
                 "tracked iterator's durable state in the manifest, "
                 "`restore_data_iter` applies it on resume, and the "
                 "`ShardedLedgerIter` cursor remaps exactly across "
                 "world-size changes (the data-plane half of elastic "
                 "training)"],
    "monitor": ["[telemetry](telemetry.md) — training-health numerics "
                "(`telemetry.numerics`): the jit-safe stat machinery "
                "the default Monitor path rides (`mxtpu_monitor_stat"
                "{tensor}` gauges, `mxtpu_nonfinite_total` counting, "
                "strict-mode anomaly stops)",
                "[executor](executor.md) — `set_stats_monitor` (one "
                "compiled forward with per-node stat outputs) vs the "
                "eager `set_monitor_callback` route "
                "(`Monitor(eager=True)`)"],
    "metric": ["[telemetry](telemetry.md) — non-finite update values "
               "are rejected from the running average and counted into "
               "`mxtpu_nonfinite_total{tensor=\"metric/<name>\"}` "
               "(training-health numerics)"],
    "symbol": ["[analysis](analysis.md) — `Symbol.verify()`, "
               "`bind(strict=True)`, the MXG0xx diagnostic catalog",
               "[fusion](fusion.md) — the block-granularity fusion "
               "pass `eval_graph` lowers matched chains through"],
    "kvstore": ["[telemetry](telemetry.md) — push/pull byte counters "
                "and the dist_async in-flight gauge",
                "[overlap](overlap.md) — bucketed async gradient "
                "allreduce (parallel/overlap.py): `DistKVStore."
                "push_bucketed`/`drain` replace the per-push "
                "barrier-then-allreduce for trainer gradients under "
                "`MXNET_TPU_OVERLAP`, launching size-targeted buckets "
                "as backward produces cotangents and draining at the "
                "optimizer boundary"],
    "profiler": ["[telemetry](telemetry.md) — spans feed these Chrome "
                 "traces; metrics/exporters live there, as do the "
                 "memory-plan gauges (`telemetry.memory`), the "
                 "flight-recorder black box (`telemetry.flight`, "
                 "MXNET_TPU_FLIGHT_DIR) for after-the-fact profiling "
                 "of a dead run, and on-demand live capture "
                 "(`telemetry.distview`): SIGUSR1 / `/debug/capture` "
                 "writes a bounded profiler window on a running rank — "
                 "analyze it with `tools/xprof_top.py --trace`"],
}


def first_line(doc):
    if not doc:
        return ""
    return doc.strip().splitlines()[0].strip()


def clean_sig(sig):
    """Strip machine-specific noise from repr'd default values (memory
    addresses, interpreter paths) so regenerating on another machine
    does not churn every page."""
    sig = re.sub(r" at 0x[0-9a-fA-F]+", "", sig)
    sig = re.sub(r"<module '([^']+)' from '[^']*'>", r"<module '\1'>", sig)
    return sig


def gen_ops():
    import jax
    jax.config.update("jax_platforms", "cpu")
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.ops import registry

    lines = [
        "# Operator reference (mx.nd.* / mx.sym.*)",
        "",
        "Generated by `tools/gen_api_docs.py` from the live operator "
        "registry — one registration feeds both the imperative "
        "(`mx.nd`) and symbolic (`mx.sym`) surfaces.",
        "",
        "| op | arguments | attrs (defaults) | summary |",
        "|---|---|---|---|",
    ]
    for name in sorted(registry.list_ops()):
        op = registry.get_op(name)
        try:
            args = ", ".join(op.get_arg_names(
                {k: v for k, v in op.params.items()}))
        except (KeyError, TypeError, ValueError, MXNetError):
            args = "(attr-dependent)"
        attrs = ", ".join("%s=%r" % (k, v)
                          for k, v in sorted(op.params.items()))
        doc = first_line(op.doc or getattr(op.fcompute, "__doc__", ""))
        doc = doc.replace("|", "\\|")
        lines.append("| `%s` | %s | %s | %s |"
                     % (name, args, attrs or "—", doc))
    return "\n".join(lines) + "\n"


def gen_module(title, modname):
    import importlib
    mod = importlib.import_module(modname)
    lines = ["# %s" % title, "",
             first_line(mod.__doc__), "",
             "Generated by `tools/gen_api_docs.py` from `%s`." % modname,
             ""]
    names = getattr(mod, "__all__", None) or \
        [n for n in sorted(vars(mod)) if not n.startswith("_")]
    for n in names:
        obj = getattr(mod, n, None)
        if obj is None or inspect.ismodule(obj):
            continue
        if not (inspect.isclass(obj) or callable(obj)):
            continue
        if getattr(obj, "__module__", modname) is not None and \
                not str(getattr(obj, "__module__", modname)).startswith(
                    "mxnet_tpu"):
            continue
        try:
            sig = clean_sig(str(inspect.signature(obj)))
        except (ValueError, TypeError):
            sig = "(...)"
        kind = "class" if inspect.isclass(obj) else "def"
        lines.append("## `%s %s%s`" % (kind, n, sig))
        lines.append("")
        doc = first_line(obj.__doc__)
        if doc:
            lines.append(doc)
            lines.append("")
        if inspect.isclass(obj):
            for mn, m in sorted(vars(obj).items()):
                if mn.startswith("_") or not callable(m):
                    continue
                try:
                    msig = clean_sig(str(inspect.signature(m)))
                except (ValueError, TypeError):
                    msig = "(...)"
                mdoc = first_line(getattr(m, "__doc__", ""))
                lines.append("- `%s%s` — %s" % (mn, msig, mdoc))
            lines.append("")
    return "\n".join(lines) + "\n"


def main():
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "ops.md"), "w") as f:
        f.write(gen_ops())
    index = ["# API reference", "",
             "Generated by `tools/gen_api_docs.py`; regenerate after "
             "changing public APIs.", "",
             "- [Operator reference](ops.md)"]
    for title, modname in MODULES:
        fname = modname.split(".")[-1] + ".md"
        page = gen_module(title, modname)
        extra = SEE_ALSO.get(fname[:-len(".md")])
        if extra:
            page += "\n## See also\n\n" + \
                "".join("- %s\n" % line for line in extra)
        with open(os.path.join(OUT, fname), "w") as f:
            f.write(page)
        index.append("- [%s](%s)" % (title, fname))
    for title, fname in HAND_WRITTEN:
        index.append("- [%s](%s) (hand-written)" % (title, fname))
    with open(os.path.join(OUT, "index.md"), "w") as f:
        f.write("\n".join(index) + "\n")
    print("wrote docs/api/ (%d pages)"
          % (len(MODULES) + len(HAND_WRITTEN) + 2))


if __name__ == "__main__":
    main()
