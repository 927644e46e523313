"""Runtime configuration: the MXNET_* env-var catalog.

Reference: ``docs/how_to/env_var.md`` + ``dmlc::GetEnv`` reads at singleton
init (SURVEY §5.6).  The TPU build honors the same names where the concept
survives; names whose job XLA took over are documented as accepted-but-
inert so existing launch scripts keep working.
"""
from __future__ import annotations

import os

__all__ = ["get", "get_int", "get_bool", "describe"]

# name -> (default, status, note)
_CATALOG = {
    # engine / threading — XLA owns scheduling; kept for script compat
    "MXNET_ENGINE_TYPE": ("ThreadedEnginePerDevice", "inert",
                          "XLA async dispatch replaces the engine; "
                          "NaiveEngine debugging == JAX_DISABLE_JIT=1"),
    "MXNET_CPU_WORKER_NTHREADS": ("1", "inert", "XLA intra-op threading"),
    "MXNET_GPU_WORKER_NTHREADS": ("2", "inert", ""),
    "MXNET_GPU_COPY_NTHREADS": ("2", "inert", ""),
    "MXNET_CPU_PRIORITY_NTHREADS": ("4", "inert", ""),
    # memory
    "MXNET_GPU_MEM_POOL_RESERVE": ("5", "inert",
                                   "XLA/PJRT owns the HBM allocator"),
    "MXNET_EXEC_NUM_TEMP": ("1", "inert", ""),
    # executor
    "MXNET_EXEC_BULK_EXEC_INFERENCE": ("1", "inert",
                                       "whole-graph jit is always on"),
    "MXNET_EXEC_BULK_EXEC_TRAIN": ("1", "inert", ""),
    "MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN": ("15", "inert", ""),
    "MXNET_EXEC_INPLACE_GRAD_SUM_CAP": ("8", "inert", ""),
    "MXNET_BACKWARD_DO_MIRROR": ("0", "honored",
                                 "maps to jax.checkpoint/remat in the "
                                 "fused trainer"),
    "NNVM_EXEC_MATCH_RANGE": ("16", "inert", "XLA memory planning"),
    # kvstore
    "MXNET_KVSTORE_REDUCTION_NTHREADS": ("4", "inert", ""),
    "MXNET_KVSTORE_BIGARRAY_BOUND": ("1000000", "honored",
                                     "update_on_kvstore heuristic"),
    "MXNET_ENABLE_GPU_P2P": ("1", "inert", "ICI is always direct"),
    # profiler
    "MXNET_FUSE_BLOCKS": ("0", "honored",
        "block-granularity fusion pass (analysis.fusion): conv+BN+ReLU "
        "and FC+activation chains lowered as single fused regions with "
        "an explicit layout plan per boundary (docs/api/fusion.md); "
        "default for Executor binds and ShardedTrainer(fuse_blocks=None)"),
    "MXNET_STEM_S2D": ("0", "honored",
        "space-to-depth rewrite of 7x7/s2 stem convs in ShardedTrainer"),
    "MXNET_PROFILER_AUTOSTART": ("0", "honored", "see profiler.py"),
    "MXNET_PROFILER_MODE": ("0", "honored", ""),
    "MXNET_PROFILER_FILENAME": ("profile.json", "honored", ""),
    "MXNET_PROFILER_XLA_DIR": ("", "honored", "xprof trace capture dir"),
    # cudnn — no analogue
    "MXNET_CUDNN_AUTOTUNE_DEFAULT": ("0", "inert",
                                     "XLA autotuning is automatic"),
    # tests
    "MXNET_TEST_DEVICE": ("cpu", "honored", "test_utils.default_context"),
    # TPU-native additions
    "MXNET_TPU_NUM_PROCESSES": ("1", "honored",
                                "multi-host bootstrap (tools/launch.py)"),
    "MXNET_TPU_PROCESS_ID": ("0", "honored", ""),
    "MXNET_TPU_COORDINATOR": ("", "honored",
                              "jax.distributed coordinator address"),
    "MXNET_USE_NATIVE_REC": ("", "honored",
                             "force (1) or disable (0) the native JPEG "
                             "record pipeline in the examples"),
    # resilience subsystem (docs/api/resilience.md)
    "MXNET_TPU_FAULTS": ("", "honored",
                         "fault-injection spec, e.g. "
                         "'recordio.read:p=0.05,seed=7;checkpoint.save:"
                         "n=1' (resilience.configure_faults grammar)"),
    "MXNET_TPU_BAD_RECORD_QUOTA": ("0", "honored",
                                   "max corrupt/truncated records a "
                                   "reader skips by magic-resync before "
                                   "raising (0 = strict)"),
    "MXNET_TPU_HEARTBEAT_TIMEOUT": ("", "honored",
                                    "jax.distributed peer-failure "
                                    "detection window in seconds "
                                    "(ps-lite heartbeat role)"),
    "MXNET_TPU_INIT_TIMEOUT": ("0", "honored",
                               "per-attempt bound on joining the "
                               "jax.distributed job (0 = runtime "
                               "default)"),
    "MXNET_TPU_INIT_RETRIES": ("2", "honored",
                               "bounded backoff retries for "
                               "multihost.ensure_initialized"),
    "MXNET_TPU_BARRIER_TIMEOUT": ("0", "honored",
                                  "per-attempt bound on process_barrier "
                                  "in seconds (0 = wait forever)"),
    "MXNET_TPU_BARRIER_RETRIES": ("1", "honored",
                                  "bounded backoff retries for "
                                  "process_barrier"),
    "MXNET_TPU_RESTART_BUDGET": ("0", "honored",
                                 "tools/launch.py: relaunch a failed "
                                 "job up to this many times from the "
                                 "last complete checkpoint"),
    "MXNET_TPU_HEARTBEAT_INTERVAL": ("0.2", "honored",
                                     "tools/launch.py watchdog poll "
                                     "interval (dead-rank detection "
                                     "latency)"),
    "MXNET_TPU_RESTART_COUNT": ("0", "honored",
                                "set by tools/launch.py on each restart "
                                "attempt; resume-aware scripts reload "
                                "their latest checkpoint when > 0"),
    "MXNET_TPU_STRICT_BIND": ("0", "honored",
                              "run the mxnet_tpu.analysis graph verifier "
                              "on every bind (Executor and Module) and "
                              "the distributed-correctness pass "
                              "(MXG011-016) on every ShardedTrainer "
                              "construction, failing with node-level "
                              "diagnostics before any XLA compile"),
    # telemetry subsystem (docs/api/telemetry.md)
    "MXNET_TPU_TELEMETRY_JSONL": ("", "honored",
                                  "append one JSON line per training "
                                  "step (span timings + full counter/"
                                  "gauge snapshot) to this file"),
    "MXNET_TPU_TELEMETRY_PORT": ("0", "honored",
                                 "serve Prometheus text metrics on "
                                 "http://0.0.0.0:PORT/metrics "
                                 "(0 = off)"),
    "MXNET_TPU_FLIGHT_DIR": ("", "honored",
                             "write flight-recorder black-box dumps "
                             "here on MXNetError/OOM/SIGTERM/crash "
                             "(recording itself is always on; "
                             "tools/flight_read.py pretty-prints)"),
    "MXNET_TPU_FLIGHT_EVENTS": ("512", "honored",
                                "flight-recorder ring capacity "
                                "(oldest events fall off)"),
    "MXNET_TPU_TRACE_SAMPLE": ("1", "honored",
                               "distributed-tracing sample rate for "
                               "ordinary traces, clamped to [0,1] "
                               "(error/shed and the slow tail are "
                               "ALWAYS kept; 0 disables tracing "
                               "entirely — start_trace returns the "
                               "shared NULL_TRACE and the request "
                               "path allocates nothing)"),
    "MXNET_TPU_TRACE_DIR": ("", "honored",
                            "append kept traces as mxtpu-trace/1 "
                            "JSONL to trace.rank<N>.jsonl here; "
                            "tools/launch.py merges the per-rank "
                            "files into trace.merged.jsonl at job "
                            "end and tools/trace_top.py renders"),
    "MXNET_TPU_TRACE_RING": ("256", "honored",
                             "in-process kept-trace ring capacity "
                             "(floor 8; oldest traces fall off)"),
    "MXNET_TPU_TRACE_SLOW_PCT": ("0.95", "honored",
                                 "slow-tail retention percentile: "
                                 "root durations at or above this "
                                 "percentile of the recent window "
                                 "are always kept regardless of the "
                                 "sample rate"),
    "MXNET_TPU_IOVIEW_EVERY": ("1", "honored",
                               "attach the input-pipeline io block "
                               "(per-stage seconds/items/bytes, "
                               "stall/starved, occupancy, iterator "
                               "position) to every Nth step's JSONL "
                               "record (telemetry.ioview; 0 disables "
                               "the per-step block — stage metrics and "
                               "the bottleneck classifier keep "
                               "running)"),
    "MXNET_TPU_DATA_RESUME": ("1", "honored",
                              "write the tracked data iterator's "
                              "durable state() into checkpoint "
                              "manifests (meta.data_state) and restore "
                              "it on resume, so a mid-epoch kill "
                              "resumes at the exact next sample "
                              "(mxnet_tpu.io_resume; 0 = legacy "
                              "start-of-epoch resume)"),
    "MXNET_TPU_BACKPRESSURE": ("0", "honored",
                               "close the io_top sensor->actuator "
                               "loop: fit() installs a backpressure "
                               "controller that reads the bottleneck "
                               "verdict per batch and retunes pipeline "
                               "knobs (device prefetch depth) with "
                               "hysteresis, telemetering every move "
                               "(mxtpu_backpressure_adjust_total)"),
    "MXNET_TPU_IOVIEW_WINDOW": ("5", "honored",
                                "ioview bottleneck-classifier window "
                                "in seconds: per window, consumer-"
                                "stall vs producer-starved time picks "
                                "producer-bound (naming the slowest "
                                "stage) / consumer-bound / balanced"),
    "MXNET_TPU_SKEW_EVERY": ("8", "honored",
                             "measure the pre-collective timestamp "
                             "barrier (collective wait + rank skew) "
                             "every N collectives (each measured step "
                             "pays a fleet-wide host sync; 1 = every "
                             "step); 0 disables"),
    "MXNET_TPU_CAPTURE_DIR": ("", "honored",
                              "enable on-demand live capture: SIGUSR1 "
                              "(or the /debug/capture endpoint) writes "
                              "a bounded jax.profiler trace window + a "
                              "flight snapshot under this directory "
                              "without restarting the worker"),
    "MXNET_TPU_CAPTURE_SECONDS": ("3", "honored",
                                  "length of the on-demand capture "
                                  "trace window in seconds"),
    "MXNET_TPU_MEMORY_BUDGET": ("1.0", "honored",
                                "fraction of device capacity a "
                                "compiled program's static memory "
                                "plan may use before dispatch raises "
                                "(<=0 disables the budget check)"),
    "MXNET_TPU_HBM_LIMIT_BYTES": ("", "honored",
                                  "device-capacity override for the "
                                  "memory budget check on backends "
                                  "without memory_stats (CPU tests)"),
    "MXNET_TPU_MEMLIVE_TOL": ("0.25", "honored",
                              "MXG018 drift tolerance: the static "
                              "memory-liveness peak may differ from a "
                              "compiled plan's total by this fraction "
                              "before the analyzer flags it"),
    "MXNET_TPU_COSTDB": ("", "honored",
                         "persist the op/block cost database "
                         "(telemetry.costdb, schema mxtpu-costdb/1) "
                         "as JSONL under this directory; "
                         "tools/perf_top.py ranks it"),
    "MXNET_TPU_COSTDB_SAMPLE": ("16", "honored",
                                "measure every Nth post-compile "
                                "dispatch per program into the cost "
                                "database (each sample synchronizes "
                                "the dispatch; 0 disables "
                                "measurement)"),
    "MXNET_TPU_PEAK_FLOPS": ("", "honored",
                             "per-chip peak FLOPs/s override for "
                             "costdb MFU/roofline derivation "
                             "(default: built-in per-backend table)"),
    "MXNET_TPU_PEAK_BW": ("", "honored",
                          "per-chip peak memory bytes/s override for "
                          "costdb roofline derivation (default: "
                          "built-in per-backend table)"),
    # communication overlap (parallel/overlap.py, docs/api/overlap.md)
    "MXNET_TPU_OVERLAP": ("1", "honored",
                          "bucketed async gradient allreduce overlapped "
                          "with backward: DistKVStore trainer-gradient "
                          "sync routes through push_bucketed/drain "
                          "(buckets launch as cotangents land, one "
                          "drain at the optimizer boundary) and "
                          "DevicePrefetchIter double-buffers H2D "
                          "staging; 0 restores the per-push "
                          "barrier-then-allreduce (bit-parity between "
                          "the modes is CI-gated)"),
    "MXNET_TPU_BUCKET_BYTES": ("4194304", "honored",
                               "gradient-bucket size target in bytes "
                               "for the overlap layer (DDP-style; "
                               "smaller buckets start communication "
                               "earlier, larger ones amortize "
                               "per-collective overhead)"),
    # elastic training (docs/api/reshard.md)
    "MXNET_TPU_ELASTIC": ("0", "honored",
                          "tools/launch.py --elastic default: a failed "
                          "attempt relaunches at the SURVIVING worker "
                          "count (rank leave) instead of the fixed one; "
                          "resumed workers reshard their checkpoint "
                          "onto the smaller mesh"),
    "MXNET_TPU_MIN_WORKERS": ("1", "honored",
                              "floor for elastic shrinking in "
                              "tools/launch.py --elastic"),
    "MXNET_TPU_FLEET": ("0", "honored",
                        "tools/launch.py --fleet default: supervise "
                        "workers as INDEPENDENT serving replicas — a "
                        "dead replica is restarted alone (up to "
                        "--restart-budget times each) while its peers "
                        "keep serving, instead of the collective "
                        "all-ranks teardown"),
    # serving tier (docs/api/serving.md)
    "MXNET_TPU_SERVE_LADDER": ("1,4,16,64", "honored",
                               "batch-ladder rungs the serving tier "
                               "AOT-compiles at startup; requests pad "
                               "to the nearest rung, so the request "
                               "path never compiles"),
    "MXNET_TPU_SERVE_WINDOW_MS": ("5", "honored",
                                  "batching window: how long the "
                                  "batcher holds the oldest queued "
                                  "request while coalescing toward "
                                  "the largest rung"),
    "MXNET_TPU_SERVE_QUEUE_DEPTH": ("64", "honored",
                                    "bounded request-queue depth; a "
                                    "submit beyond it is shed "
                                    "immediately (queue_full)"),
    "MXNET_TPU_SERVE_DEADLINE_MS": ("1000", "honored",
                                    "default per-request deadline; a "
                                    "request whose remaining deadline "
                                    "cannot cover the estimated rung "
                                    "wall is shed early (deadline)"),
    "MXNET_TPU_SERVE_PORT": ("8080", "honored",
                             "serving replica base port; each replica "
                             "binds port+MXNET_TPU_PROCESS_ID under "
                             "the fleet launcher"),
    "MXNET_TPU_SERVE_COST_MODEL": ("", "honored",
                                   "path to a fitted autotune cost "
                                   "model used to price rung walls "
                                   "for the deadline scheduler before "
                                   "warm-up measurements exist"),
    "MXNET_TPU_RESHARD_RULES": ("", "honored",
                                "match_partition_rules table "
                                "(parallel.reshard grammar: "
                                "'regex=axis,axis;...' or @file.json) "
                                "overriding the trainer's derived "
                                "tp_rules per matching param — the "
                                "hand-written partition layout for the "
                                "target mesh of a reshard"),
    # autotuner (docs/api/autotune.md)
    "MXNET_TPU_AUTOTUNE": ("cache", "honored",
                           "trace-time tuned-block-config lookup mode: "
                           "off (heuristics only), cache (tuned cache "
                           "entry wins, heuristic on miss — the "
                           "default), search (a miss triggers a "
                           "bounded inline measurement search whose "
                           "winner is committed and used)"),
    "MXNET_TPU_TUNE_CACHE": ("", "honored",
                             "persistent Pallas tuning cache directory "
                             "(mxnet_tpu.autotune, JSONL schema "
                             "mxtpu-tunecache/1); tunecache*.jsonl "
                             "files are merged on load with best-"
                             "measured-wall-wins so multi-host/multi-"
                             "run caches compose; tools/autotune.py "
                             "writes it"),
    # whole-graph plan search (analysis.plansearch,
    # docs/api/plansearch.md)
    "MXNET_TPU_PLAN_SEARCH": ("cache", "honored",
                              "bind-time graph_plan tuning-cache "
                              "consult mode for Executor/"
                              "ShardedTrainer: cache (committed "
                              "searched plan wins, greedy fusion plan "
                              "on miss — the default) or off (no "
                              "lookup at all); searching itself is "
                              "always explicit (tools/plan_search.py, "
                              "ci_check stage 12, bench dry-run)"),
    "MXNET_TPU_PLAN_BUDGET": ("64", "honored",
                              "max candidate whole-graph plans the "
                              "beam search scores with the learned "
                              "cost model per search"),
    "MXNET_TPU_PLAN_BEAM": ("8", "honored",
                            "beam width of the plan search"),
    # training-health numerics (telemetry.numerics,
    # docs/api/telemetry.md)
    "MXNET_TPU_NUMERICS_EVERY": ("0", "honored",
                                 "compute in-graph tensor stats "
                                 "(param/grad/fused-block norms, "
                                 "non-finite counts, value digests) "
                                 "every Nth trainer step() inside the "
                                 "jitted step; 0 disables; run_steps "
                                 "chains warn once and stay "
                                 "unsampled"),
    "MXNET_TPU_NUMERICS_STRICT": ("0", "honored",
                                  "a fired numerics anomaly rule dumps "
                                  "the flight ring and raises a "
                                  "descriptive MXNetError (naming step/"
                                  "tensors + NaN provenance node) "
                                  "instead of warning"),
    "MXNET_TPU_NUMERICS_LEDGER": ("", "honored",
                                  "append one mxtpu-numerics/1 record "
                                  "per sampled step to this file — the "
                                  "divergence ledger tools/numdiff.py "
                                  "compares (one file per rank)"),
    "MXNET_TPU_NUMERICS_SPIKE": ("10", "honored",
                                 "grad_spike anomaly factor: fires "
                                 "when the global grad norm exceeds "
                                 "factor x its running EWMA; 0 "
                                 "disables the rule"),
    "MXNET_TPU_NUMERICS_DEAD": ("1.0", "honored",
                                "dead_grad anomaly threshold on a "
                                "gradient's exact-zero fraction "
                                "(1.0 = only an entirely zero grad; "
                                "0 disables the rule)"),
    # SLO engine / healthd (telemetry.slo, docs/api/telemetry.md)
    "MXNET_TPU_SLO": ("1", "honored",
                      "the in-process SLO engine: 0 disables rule "
                      "evaluation entirely (health() reports "
                      "status=healthy, disabled=true; no alert "
                      "metrics, no ticker)"),
    "MXNET_TPU_SLO_RULES": ("", "honored",
                            "SLO rule-catalog override: @file.json or "
                            "inline JSON list merged over the built-in "
                            "catalog by rule name (disable:true drops "
                            "a rule), or the compact form "
                            "'rule.param=value;rule2.disable=1'; a "
                            "malformed spec warns once and keeps the "
                            "defaults"),
    "MXNET_TPU_SLO_TICK_S": ("1.0", "honored",
                             "background-ticker evaluation cadence in "
                             "seconds (floor 0.05); also rate-limits "
                             "the per-step evaluation hook"),
    "MXNET_TPU_SLO_FAST_S": ("60", "honored",
                             "default fast burn-rate window in "
                             "seconds for rules that leave fast_s "
                             "unset"),
    "MXNET_TPU_SLO_SLOW_S": ("300", "honored",
                             "default slow burn-rate window in "
                             "seconds for rules that leave slow_s "
                             "unset"),
    "MXNET_TPU_SLO_LATENCY_MS": ("250", "honored",
                                 "serving latency SLO threshold: a "
                                 "request slower than this is 'bad' "
                                 "for serve_p99_latency_burn (rounded "
                                 "up to the nearest request-latency "
                                 "histogram bucket bound)"),
}


def get(name, default=None):
    if name in _CATALOG and default is None:
        default = _CATALOG[name][0]
    return os.environ.get(name, default)


def get_int(name, default=None):
    v = get(name, default)
    return int(v) if v not in (None, "") else 0


def get_bool(name, default=None):
    v = get(name, default)
    return str(v) in ("1", "true", "True")


def describe():
    """Catalog as {name: (default, status, note)} — the env_var.md table."""
    return dict(_CATALOG)
