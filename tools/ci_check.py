#!/usr/bin/env python
"""ci_check — the repo's static-analysis gate, runnable standalone or
from pytest (tests/test_analysis.py::test_ci_stage runs each stage of
`STAGES` as its own tier-1 case).

Twenty stages, all of which must be clean:

1. **mxlint** (tools/mxlint.py) over ``mxnet_tpu/ tools/ examples/`` —
   the TPU-hazard rules MXL001-007; pragmas with reasons are the only
   accepted suppressions.
2. **op-registry self-check** — alias/hook/TP-rule drift
   (:func:`mxnet_tpu.ops.registry.selfcheck`).
3. **graph verifier** over every model-zoo entry with its canonical
   input shape — zero diagnostics expected (warnings included: the zoo
   is the reference corpus, it must be spotless).
4. **telemetry self-check** — the catalog validates
   (:func:`mxnet_tpu.telemetry.selfcheck`) and every metric name in
   ``docs/api/telemetry.md`` exists in ``telemetry.CATALOG`` and vice
   versa (the drift-guard pattern that caught ``squeeze`` in PR 2).
5. **flight-recorder smoke** — a fault injected through
   ``MXNET_TPU_FAULTS`` at the ``trainer.step`` seam of a tiny trainer
   must produce a well-formed black-box dump in
   ``MXNET_TPU_FLIGHT_DIR`` that ``tools/flight_read.py`` parses and
   formats.
6. **distview smoke** — a 2-process telemetry dry-run under the
   ``tools/launch.py`` run aggregator (one rank seeded slow) must
   leave an ``mxtpu-run/1`` timeline that ``tools/run_top.py
   --summarize --json`` parses, naming the slow rank the straggler
   with per-rank segment totals.
7. **fusion gate** — the block-granularity fusion pass
   (``mxnet_tpu.analysis.fusion``, docs/api/fusion.md) must plan at
   least one fused block on every zoo net with a fusable pattern
   (BatchNorm chains or FC+activation tails) with ZERO fallbacks on
   the reference corpus, and a fused-vs-unfused executor
   forward+backward on a conv+BN+ReLU micro-net must agree
   numerically (train and eval BN semantics).
8. **perf ground truth** — a ``bench.py --dry-run`` under
   ``MXNET_TPU_COSTDB`` must leave a parseable ``mxtpu-costdb/1``
   database with a measured record (non-null wall/flops/MFU) for the
   step program AND for every dispatched fused block;
   ``tools/perf_top.py --json`` must parse it and name the worst-MFU
   block; ``tools/bench_diff.py`` over a generated BENCH series with
   one errored round must exit 0 (the errored round skipped) and must
   exit nonzero on a synthetic 20%% regression appended to the series.
9. **autotuner** — a dry-run tune (``tools/autotune.py``, interpret
   mode) of one flash shape, forward and backward, must leave a
   strict-parseable ``mxtpu-tunecache/1`` cache, a SECOND run of the
   same commands must be all cache hits (0 searched), the cost model
   must fit on the accumulated costdb records, and a model fitted on
   seeded pathological records must flag a pathological-block graph
   via MXG010.  (The stage-4 drift guard covers the new
   ``mxtpu_tune_cache_*`` metrics automatically.)
10. **reshard gate** — ``tools/reshard.py --selfcheck`` on virtual CPU
    devices: a checkpoint saved on a fake ``{data:2, model:2}`` mesh
    must reshard-load on ``{data:4}`` AND on a single device with
    bit-exact params/aux/optimizer state against a gather reference
    (the trainer stepping afterwards on each target mesh), and the
    offline converter's ``--verify`` roundtrip must be bit-identical.
    (The stage-4 drift guard covers the new ``mxtpu_reshard_*`` /
    ``mxtpu_elastic_*`` metrics automatically.)
11. **numerics gate** — training-health numerics end to end
    (``mxnet_tpu/telemetry/numerics.py``, docs/api/telemetry.md): a
    strict-mode dry run with a NaN injected through the
    ``numerics.nonfinite`` resilience seam must stop with an
    MXNetError naming the tensors AND leave a flight dump whose
    ``numerics_anomaly`` event carries provenance naming the seeded
    node; two further dry-run ledgers — an identical twin and one
    seeded with a mid-run single-tensor divergence — must make
    ``tools/numdiff.py`` exit 0 (bit-clean) and exit nonzero naming
    the first diverging step, respectively.  (The stage-4 drift guard
    covers the new ``mxtpu_tensor_norm`` / ``mxtpu_grad_global_norm``
    / ``mxtpu_nonfinite_total`` / ``mxtpu_numerics_anomalies_total``
    metrics automatically.)
12. **plan-search gate** — the cost-model-guided whole-graph plan
    search (``mxnet_tpu.analysis.plansearch``, docs/api/
    plansearch.md): ``tools/plan_search.py --model mlp`` under a tiny
    budget (interpret-mode CPU measurement) must commit a
    ``graph_plan`` tuning-cache entry whose predicted wall is <= the
    greedy plan's and whose measured wall is <= the measured greedy
    wall; a SECOND identical run must be a pure cache hit with zero
    search; and an Executor lowered through a decision-transformed
    plan (chain split + per-region layout override) must match the
    greedy executor's outputs and gradients numerically.  (The
    stage-4 drift guard covers the new ``mxtpu_plan_cache_*`` metrics
    automatically.)
13. **SPMD gate** — the distributed-correctness pass
    (``mxnet_tpu.analysis.spmd``, MXG011-016): one seeded-defect
    fixture per rule must produce the expected diagnostic with the
    offending node/stage/axis NAMED (a rank-subset kvstore push, a
    ragged ring-attention shard, an axis_index-conditioned psum in a
    jaxpr, a duplicated/fused-straddling pipeline stage, a typo'd
    reshard-rule axis, a donated-then-read buffer group, a
    wrong-direction backward ring), AND a clean sweep — every zoo
    model under a dp mesh plus the composed pipeline and
    sequence-parallel transformer configs — must report ZERO
    findings.  (The stage-4 drift guard covers the new
    ``mxtpu_verify_findings_total`` metric automatically.)
14. **io observability gate** — data-plane bottleneck attribution end
    to end (``mxnet_tpu/telemetry/ioview.py``, docs/api/telemetry.md):
    a dry-run pipeline with a seeded slow stage (an ``io.prefetch``
    ``kind=delay`` fault — the existing seam family) must leave a
    JSONL step-log whose ``io`` blocks ``tools/io_top.py --json``
    parses (schema ``mxtpu-iotop/1``), naming the seeded stage
    producer-bound, with the iterator position present; the live
    classifier must have left an ``io_bottleneck`` flight event and
    bumped ``mxtpu_io_bottleneck_total`` for the same stage.  (The
    stage-4 drift guard covers the new ``mxtpu_io_stage_*`` /
    ``mxtpu_io_queue_occupancy`` / ``mxtpu_io_bottleneck_total`` /
    ``mxtpu_io_prefetch_starved_seconds_total`` metrics
    automatically.)

15. **overlap gate** — the bucketed-async-allreduce overlap layer end
    to end (``mxnet_tpu/parallel/overlap.py``, docs/api/overlap.md):
    ``tools/overlap_ab.py`` runs a 2-process dry run with a seeded
    slow rank twice (overlap off, then on — the on leg routes through
    ``model._update_params_on_kvstore``'s bucketed branch and the real
    ``BucketQueue``); the final params of BOTH ranks must be
    bit-identical between the modes, and the on leg's ``overlap``
    bucket flight events must parse via ``tools/flight_read.py`` and
    count the same whole number a step on both ranks.  The FAST rank's
    ``mxtpu_collective_wait_seconds`` total and ``collective_wait``
    share are in the document for both modes, reported and not gated
    (two wall times on a shared machine).  (The stage-4 drift guard covers the new
    ``mxtpu_overlap_*`` metrics automatically; stage 13 additionally
    discriminates a seeded bucket-order mismatch via MXG011.)

16. **io resume gate** — the exactly-once data plane
    (``mxnet_tpu/io_resume.py``, docs/api/io_resume.md): a 2-process
    fleet SIGKILLed mid-epoch must resume as a 1-process fleet (cursor
    remap world 2 -> 1) with the consumed-id union EXACTLY one epoch —
    nothing dropped, nothing doubled — and a seeded slow producer must
    drive a ``backpressure_adjust`` depth raise visible in the
    counter, the flight box, and the run timeline.

17. **memory gate** — the static memory-liveness analyzer
    (``mxnet_tpu.analysis.memlive``, MXG017-021, docs/api/
    memlive.md): the static eval-schedule peak must agree with the
    XLA ``memory_analysis`` total of the aval-compiled forward (less
    XLA:CPU's scratch copy of the convolution weights) within
    ``MXNET_TPU_MEMLIVE_TOL`` on EVERY zoo model (no MXG018); seeded
    fixtures must fire MXG017 (over budget, peak node NAMED, error
    severity), MXG019 (remat candidate), MXG020 (replicated optimizer
    state) and MXG021 (un-donated dead input); and ``tools/mem_top.py
    --json`` over an over-budget sharded train config must emit a
    strict-parseable ``mxtpu-memtop/1`` document with at least one
    remat and one ZeRO advice record.  (The stage-4 drift guard
    covers the new ``mxtpu_predicted_peak_bytes`` /
    ``mxtpu_remat_candidate_bytes`` / ``mxtpu_memlive_drift_ratio``
    metrics automatically.)

18. **serving gate** — the production predict path
    (``mxnet_tpu/serving/``, docs/api/serving.md): a 1-replica
    ``tools/launch.py --fleet`` job serving the tiny zoo MLP behind
    the batch ladder must answer ``/healthz``; a concurrent burst must
    COALESCE (``mxtpu_serve_rung_dispatch_total`` on a rung > 1) and a
    deadline-starved overload must SHED
    (``mxtpu_serve_shed_total`` > 0) while ok requests keep landing;
    ``tools/serve_top.py --json`` must emit a strict-parseable
    ``mxtpu-servetop/3`` document naming the hot rung; and SIGKILLing
    the replica mid-fleet must end with the watchdog's
    ``replica_restart`` in the supervisor timeline and ``/healthz``
    green again under a NEW pid — the fleet availability contract.

19. **SLO gate** — the healthd engine (``mxnet_tpu/telemetry/slo.py``,
    docs/api/telemetry.md): a serving replica with seconds-scale burn
    windows under a deadline-starved shed storm must take
    ``serve_shed_burn`` through the FULL alert lifecycle — firing
    (both burn windows over the factor), ``/healthz?deep=1`` 503 with
    a critical ``mxtpu-health/1`` verdict, ``tools/health_top.py
    --json`` exit 1 naming the rule, ``tools/serve_top.py`` health
    fields — and then RESOLVE back to 200 once only good traffic
    flows; and a 2-process dry-run with seeded cross-rank skew must
    fire ``fleet_skew`` at the supervisor's aggregator, leaving an
    ``alert`` event in the run timeline that ``health_top.py --run``
    replays (first-fired named) and ``run_top.py --summarize`` rolls
    up.  (The stage-4 drift guard covers the ``mxtpu_alert_*`` /
    ``mxtpu_slo_burn_rate`` / ``mxtpu_health_status`` metrics AND the
    rule catalog vs its docs table automatically.)

20. **tracing gate** — end-to-end distributed tracing
    (``mxnet_tpu/telemetry/tracing.py``, docs/api/telemetry.md
    tracing section): a flight dump recorded under an active trace
    must carry the ``trace_id`` join key and ``tools/flight_read.py``
    must REFUSE a malformed one; a serving replica with a seeded slow
    dispatch (``serve.dispatch`` delay fault) must return
    ``X-Trace-Id`` on every ``/predict`` reply, shed an explicit
    ``deadline_ms=0`` with ``rid``+``trace_id`` in the 503 body,
    export traces whose ``tools/trace_top.py --json`` critical path
    names ``serve.dispatch`` dominant with the ``--trace`` waterfall
    covering >= 95% of the root wall, and resolve ``serve_top``'s p99
    exemplar to an exported trace; and a 2-process launch with a
    seeded slow rank must leave ``trace.merged.jsonl`` whose
    aggregate names ``step.compute`` on the slow rank — the
    fleet-wide critical-path attribution contract.

Usage: ``python tools/ci_check.py [--repo-root PATH]``; exit 1 on any
finding.
"""
from __future__ import annotations

import argparse
import os
import re
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
LINT_DIRS = ("mxnet_tpu", "tools", "examples")


def mxlint_check(repo_root=_ROOT):
    """Stage 1: source lint (no jax needed; it is first so that a
    broken interpreter environment still reports style hazards)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "mxlint", os.path.join(repo_root, "tools", "mxlint.py"))
    mxlint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mxlint)
    paths = [os.path.join(repo_root, d) for d in LINT_DIRS]
    return [str(f) for f in mxlint.lint_paths(paths)]


def registry_check(repo_root=_ROOT):
    """Stage 2: op-registry self-check."""
    from mxnet_tpu.ops import registry
    return list(registry.selfcheck())


def zoo_verify_check(repo_root=_ROOT):
    """Stage 3: verify the model zoo (warnings count — the zoo is the
    reference corpus and must produce zero diagnostics)."""
    from mxnet_tpu.analysis import verify_model
    from mxnet_tpu.models import _MODELS
    problems = []
    for name in _MODELS:
        _net, report = verify_model(name)
        problems.extend("model %s: %s" % (name, d) for d in report)
    return problems


def run_stage(stage, repo_root=_ROOT, out=None):
    """Run one stage of :data:`STAGES` by id; returns its failure
    strings (empty = clean), each prefixed with the stage id.

    ``out``: optional callable for progress lines (default: print).
    """
    say = out or (lambda s: print(s))
    ids = [sid for sid, _fn in STAGES]
    fn = dict(STAGES)[stage]
    sys.path.insert(0, repo_root)
    try:
        problems = fn(repo_root)
    finally:
        sys.path.remove(repo_root)
    say("ci_check[%d/%d] %s: %d problem(s)"
        % (ids.index(stage) + 1, len(ids), stage, len(problems)))
    for p in problems:
        say("  " + p)
    return ["%s: %s" % (stage, p) for p in problems]


def run(repo_root=_ROOT, out=None):
    """Run all stages; returns a list of failure strings (empty = clean).

    ``out``: optional callable for progress lines (default: print).
    """
    failures = []
    for stage, _fn in STAGES:
        failures.extend(run_stage(stage, repo_root, out))
    return failures


def telemetry_drift(repo_root=_ROOT):
    """Cross-check the code metric catalog (``telemetry.CATALOG``)
    against the hand-written one in ``docs/api/telemetry.md``, both
    directions, plus the catalog's own self-validation.  Returns a list
    of problem strings (empty = clean).

    Doc names are every `` `mxtpu_*` `` token in the page; derived
    histogram series (``_bucket``/``_sum``/``_count`` of a declared
    histogram) are accepted as documentation of their parent."""
    from mxnet_tpu import telemetry
    problems = list(telemetry.selfcheck())
    doc_path = os.path.join(repo_root, "docs", "api", "telemetry.md")
    if not os.path.exists(doc_path):
        problems.append("docs/api/telemetry.md is missing (the "
                        "hand-written metric catalog)")
        return problems
    with open(doc_path) as f:
        text = f.read()
    doc_names = set(re.findall(r"`(mxtpu_[a-z0-9_]+)`", text))
    code_names = set(telemetry.CATALOG)

    def _derived(name):
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and \
                    name[:-len(suffix)] in code_names:
                return True
        return False

    for name in sorted(code_names - doc_names):
        problems.append("metric %r is registered in telemetry.CATALOG "
                        "but missing from docs/api/telemetry.md" % name)
    for name in sorted(doc_names - code_names):
        if not _derived(name):
            problems.append("metric %r appears in docs/api/telemetry.md "
                            "but is not in telemetry.CATALOG" % name)

    # SLO rule-catalog drift (telemetry.slo): the built-in rules must
    # selfcheck clean, and the hand-written rule table in the doc's
    # marked block must list exactly the built-in rule names — the
    # same both-directions guard the metric catalog gets
    from mxnet_tpu.telemetry import slo
    problems.extend("slo rule catalog: %s" % p
                    for p in slo.selfcheck_rules())
    m = re.search(r"<!-- slo-rules:begin -->(.*?)<!-- slo-rules:end -->",
                  text, re.S)
    if not m:
        problems.append("docs/api/telemetry.md lacks the "
                        "slo-rules:begin/end marker block (the "
                        "hand-written SLO rule table)")
    else:
        doc_rules = {n for n in re.findall(r"`([a-z0-9_]+)`",
                                           m.group(1))
                     if not n.startswith(("mxtpu_", "mxnet_tpu"))}
        code_rules = {r["name"] for r in slo.RULES}
        for name in sorted(code_rules - doc_rules):
            problems.append("SLO rule %r is in slo.RULES but missing "
                            "from the docs/api/telemetry.md rule "
                            "table" % name)
        for name in sorted(doc_rules - code_rules):
            problems.append("SLO rule %r appears in the docs/api/"
                            "telemetry.md rule table but is not in "
                            "slo.RULES" % name)
    return problems


def flight_smoke(repo_root=_ROOT):
    """End-to-end black-box check: arm a ``trainer.step`` fault through
    ``MXNET_TPU_FAULTS``, run a tiny ShardedTrainer step, and require a
    well-formed flight dump that ``tools/flight_read.py`` parses and
    formats.  Returns a list of problem strings (empty = clean)."""
    import importlib.util
    import tempfile

    import numpy as np

    from mxnet_tpu import models, resilience
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh

    problems = []
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_flight_smoke_")
    saved = {k: os.environ.get(k)
             for k in ("MXNET_TPU_FLIGHT_DIR", "MXNET_TPU_FAULTS")}
    try:
        os.environ["MXNET_TPU_FLIGHT_DIR"] = tmpdir
        net = models.get_model("mlp", num_classes=10)
        trainer = ShardedTrainer(
            net, build_mesh(tp=1),
            data_shapes={"data": (8, 64)},
            label_shapes={"softmax_label": (8,)}, dtype="float32")
        batch = {"data": np.zeros((8, 64), np.float32),
                 "softmax_label": np.zeros((8,), np.float32)}
        # one clean step so the dump carries a memory plan + step events
        float(trainer.step(batch))
        os.environ["MXNET_TPU_FAULTS"] = "trainer.step:n=1"
        try:
            trainer.step(batch)
            problems.append("armed trainer.step fault did not raise")
        except MXNetError:
            pass
        dumps = sorted(f for f in os.listdir(tmpdir)
                       if f.startswith("flight-") and f.endswith(".json"))
        if not dumps:
            problems.append("no flight dump written to "
                            "MXNET_TPU_FLIGHT_DIR on the injected fault")
            return problems
        spec = importlib.util.spec_from_file_location(
            "flight_read", os.path.join(repo_root, "tools",
                                        "flight_read.py"))
        fr = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(fr)
        for name in dumps:
            path = os.path.join(tmpdir, name)
            try:
                doc = fr.load(path)
            except ValueError as e:
                problems.append("flight_read rejects %s: %s" % (name, e))
                continue
            kinds = {e.get("kind") for e in doc["events"]}
            for want in ("step_end", "fault", "memory_plan"):
                if want not in kinds:
                    problems.append("dump %s: missing %r event (got %s)"
                                    % (name, want, sorted(kinds)))
            text = fr.format_dump(doc)
            if "reason=error" not in text:
                problems.append("dump %s: formatted report lacks the "
                                "reason header" % name)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        resilience.clear_faults()
        import shutil
        shutil.rmtree(tmpdir, ignore_errors=True)
    return problems


def distview_smoke(repo_root=_ROOT):
    """End-to-end cross-rank observability check: a 2-process
    telemetry-only dry-run (``tests/dist_distview_worker.py``, no
    cluster, no collectives) under the ``tools/launch.py`` supervisor,
    rank 1 seeded slow.  The supervisor's run aggregator must leave an
    ``mxtpu-run/1`` timeline that ``tools/run_top.py --summarize
    --json`` parses, naming rank 1 the straggler with per-rank segment
    totals.  Returns a list of problem strings (empty = clean)."""
    import json
    import shutil
    import subprocess
    import tempfile

    problems = []
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_distview_smoke_")
    base = os.path.join(tmpdir, "run.jsonl")
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "MXNET_TPU_TELEMETRY_JSONL": base,
                "DISTVIEW_STEPS": "3",
                "DISTVIEW_SLOW_RANK": "1",
                "DISTVIEW_SLOW_S": "0.1",
                "DISTVIEW_BASE_S": "0.01"})
    # one CPU device per worker; ranks never join a jax.distributed job
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_TPU_NUM_PROCESSES", None)
    env.pop("MXNET_TPU_PROCESS_ID", None)
    try:
        res = subprocess.run(
            [sys.executable,
             os.path.join(repo_root, "tools", "launch.py"),
             "-n", "2", "--launcher", "local",
             "--heartbeat-interval", "0.1",
             sys.executable,
             os.path.join(repo_root, "tests",
                          "dist_distview_worker.py")],
            capture_output=True, text=True, timeout=240,
            cwd=repo_root, env=env)
        if res.returncode != 0:
            problems.append("2-process dry-run failed (%d): %s"
                            % (res.returncode,
                               (res.stdout + res.stderr)[-800:]))
            return problems
        run_path = base + ".run"
        if not os.path.exists(run_path):
            problems.append("supervisor wrote no run timeline at %r"
                            % run_path)
            return problems
        res = subprocess.run(
            [sys.executable,
             os.path.join(repo_root, "tools", "run_top.py"),
             run_path, "--summarize", "--json"],
            capture_output=True, text=True, timeout=60, cwd=repo_root)
        if res.returncode != 0:
            problems.append("run_top --summarize failed (%d): %s"
                            % (res.returncode, res.stderr[-400:]))
            return problems
        try:
            summary = json.loads(res.stdout)
        except ValueError as e:
            problems.append("run_top --summarize --json is not "
                            "parseable: %s" % e)
            return problems
        if summary.get("schema") != "mxtpu-run/1":
            problems.append("summary schema %r != 'mxtpu-run/1'"
                            % summary.get("schema"))
        if summary.get("steps", 0) < 3:
            problems.append("expected >= 3 aggregated steps, got %r"
                            % summary.get("steps"))
        if summary.get("straggler") != 1:
            problems.append("seeded slow rank 1 not named the "
                            "straggler (got %r)"
                            % summary.get("straggler"))
        for r in ("0", "1"):
            seg = (summary.get("per_rank", {}).get(r, {})
                   .get("segments_s"))
            if not seg or "compute" not in seg:
                problems.append("rank %s summary lacks segment totals "
                                "(got %r)" % (r, seg))
    except subprocess.TimeoutExpired:
        problems.append("2-process dry-run timed out")
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return problems


def fusion_check(repo_root=_ROOT):
    """Block-fusion gate (docs/api/fusion.md).  Two checks:

    1. the ``analysis.fusion`` pass plans >= 1 fused block with ZERO
       fallbacks on every zoo net carrying a fusable pattern (a
       BatchNorm, or an FC feeding a fusable activation) — the zoo is
       the reference corpus, it must fuse spotlessly;
    2. a conv+BN+ReLU(+FC+ReLU) micro-net run fused vs unfused through
       the Executor (forward + backward, then an eval-mode forward)
       agrees numerically — 0 parity failures.

    Returns a list of problem strings (empty = clean)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.analysis import fusion
    from mxnet_tpu.ops.fused import block_fusion

    problems = []

    def _has_fusable_pattern(topo):
        for node in topo:
            if node.is_variable or node.op is None:
                continue
            if node.op.name == "BatchNorm":
                return True
            if node.op.name == "Activation" and \
                    node.attrs.get("act_type", "relu") in \
                    fusion.FC_FUSABLE_ACTS:
                src, _idx = node.inputs[0]
                if not src.is_variable and src.op is not None and \
                        src.op.name == "FullyConnected":
                    return True
        return False

    for name in models._MODELS:
        net = models.get_model(name, num_classes=10)
        topo = net._topo()
        s = fusion.plan_block_fusion(topo, net._entries, layout="NHWC",
                                     record=False).summary()
        if _has_fusable_pattern(topo) and s["blocks"] < 1:
            problems.append("model %s has fusable chains but the pass "
                            "planned 0 blocks" % name)
        if s["fallbacks"]:
            problems.append("model %s: fusion fallbacks on the "
                            "reference corpus: %s" % (name,
                                                      s["fallbacks"]))

    # parity micro-check: fused vs unfused executor, train fwd+bwd + eval
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1),
                             num_filter=4, no_bias=True, name="c0")
    net = mx.sym.BatchNorm(net, name="bn0", fix_gamma=False)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=8,
                                name="fc0")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc1")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")

    def leg(fuse):
        with block_fusion(fuse):
            ex = sym.simple_bind(mx.cpu(), data=(4, 3, 8, 8),
                                 softmax_label=(4,))
        rng = np.random.RandomState(5)
        for n, arr in sorted(ex.arg_dict.items()):
            if n == "softmax_label":
                arr[:] = rng.randint(0, 10, arr.shape).astype(np.float32)
            else:
                arr[:] = rng.uniform(-0.5, 0.5,
                                     arr.shape).astype(np.float32)
        arng = np.random.RandomState(6)
        for n, arr in sorted(ex.aux_dict.items()):
            arr[:] = arng.uniform(0.1, 1.0, arr.shape).astype(np.float32)
        ex.forward(is_train=True)
        out = np.asarray(ex.outputs[0].asnumpy())
        ex.backward()
        grads = {k: v.asnumpy() for k, v in sorted(ex.grad_dict.items())
                 if v is not None}
        ex.forward(is_train=False)
        ev = np.asarray(ex.outputs[0].asnumpy())
        return out, grads, ev

    o_ref, g_ref, e_ref = leg(False)
    o_fused, g_fused, e_fused = leg(True)
    if not np.allclose(o_ref, o_fused, rtol=2e-5, atol=2e-6):
        problems.append("parity: fused train forward diverges from "
                        "unfused (max abs %.3g)"
                        % np.max(np.abs(o_ref - o_fused)))
    if not np.allclose(e_ref, e_fused, rtol=2e-5, atol=2e-6):
        problems.append("parity: fused eval forward diverges from "
                        "unfused (max abs %.3g)"
                        % np.max(np.abs(e_ref - e_fused)))
    for k in g_ref:
        if not np.allclose(g_ref[k], g_fused[k], rtol=2e-4, atol=2e-5):
            problems.append("parity: gradient %r diverges fused vs "
                            "unfused (max abs %.3g)"
                            % (k, np.max(np.abs(g_ref[k] - g_fused[k]))))
    return problems


def costdb_check(repo_root=_ROOT):
    """Perf-ground-truth gate.  Three checks:

    1. ``bench.py --dry-run`` under ``MXNET_TPU_COSTDB`` leaves a
       parseable ``mxtpu-costdb/1`` database with a measured record
       (non-null wall/flops/MFU) for the step program and one per
       dispatched fused block (the dry-run MLP fuses its fc_act
       chains), and the BENCH JSON embeds the roll-up + ``valid``;
    2. ``tools/perf_top.py --json`` parses the database and names the
       worst-MFU block;
    3. ``tools/bench_diff.py`` over a generated series of round
       wrappers exits 0 (the errored round is skipped, not read as a
       regression) and exits NONZERO when a synthetic 20% regression
       is appended — the trajectory guard actually guards.

    Returns a list of problem strings (empty = clean)."""
    import json
    import shutil
    import subprocess
    import tempfile

    problems = []
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_costdb_check_")
    dbdir = os.path.join(tmpdir, "costdb")
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "MXNET_TPU_COSTDB": dbdir,
                # deterministic: measure every post-compile dispatch
                "MXNET_TPU_COSTDB_SAMPLE": "1"})
    env.pop("MXNET_TPU_TELEMETRY_JSONL", None)
    try:
        res = subprocess.run(
            [sys.executable, os.path.join(repo_root, "bench.py"),
             "--dry-run"],
            capture_output=True, text=True, timeout=300,
            cwd=repo_root, env=env)
        if res.returncode != 0:
            problems.append("bench.py --dry-run failed (%d): %s"
                            % (res.returncode,
                               (res.stdout + res.stderr)[-800:]))
            return problems
        try:
            bench = json.loads(res.stdout.strip().splitlines()[-1])
        except (ValueError, IndexError) as e:
            problems.append("bench.py --dry-run printed no parseable "
                            "JSON line: %s" % e)
            return problems
        if bench.get("valid") is not True:
            problems.append("completed dry-run not marked valid=true")
        roll = bench.get("costdb") or {}
        if roll.get("schema") != "mxtpu-costdb/1":
            problems.append("BENCH JSON costdb roll-up schema %r != "
                            "'mxtpu-costdb/1'" % roll.get("schema"))
        n_fused = ((bench.get("fusion") or {}).get("summary")
                   or {}).get("blocks", 0)

        from mxnet_tpu.telemetry import costdb as costdb_mod
        try:
            records, skipped = costdb_mod.read_records(dbdir,
                                                       strict=True)
        except ValueError as e:
            problems.append("costdb reader rejects the dry-run "
                            "database: %s" % e)
            return problems
        measured = lambda r: (r.get("wall_s") is not None
                              and r.get("flops") is not None
                              and r.get("mfu") is not None)
        progs = [r for r in records if r["kind"] == "program"
                 and measured(r)]
        if not progs:
            problems.append("no measured program record (wall+flops+"
                            "MFU) in the dry-run costdb")
        blocks = [r for r in records if r["kind"] == "block"
                  and measured(r)]
        if n_fused and len({b["name"] for b in blocks}) < n_fused:
            problems.append(
                "dry-run fused %d block(s) but only %d have measured "
                "costdb records (%s)"
                % (n_fused, len({b["name"] for b in blocks}),
                   sorted({b["name"] for b in blocks})))

        # perf_top must parse the database and name the worst block
        res = subprocess.run(
            [sys.executable,
             os.path.join(repo_root, "tools", "perf_top.py"),
             dbdir, "--json"],
            capture_output=True, text=True, timeout=60, cwd=repo_root)
        if res.returncode != 0:
            problems.append("perf_top --json failed (%d): %s"
                            % (res.returncode, res.stderr[-400:]))
        else:
            try:
                top = json.loads(res.stdout)
            except ValueError as e:
                problems.append("perf_top --json not parseable: %s" % e)
                top = {}
            if top and not (top.get("worst") or {}).get("name"):
                problems.append("perf_top names no worst-MFU block "
                                "(got %r)" % top.get("worst"))

        # bench_diff over a rising series with an errored last round
        # must pass...
        reg_dir = os.path.join(tmpdir, "series")
        os.makedirs(reg_dir)
        series = []
        for i, value in enumerate([100.0, 150.0, 160.0, 160.0, 0]):
            parsed = {"metric": "m", "value": value, "unit": "u"}
            if not value:
                parsed["error"] = "run did not finish"
            path = os.path.join(reg_dir, "BENCH_r%02d.json" % (i + 1))
            with open(path, "w") as f:
                json.dump({"rc": 0 if value else 1, "parsed": parsed},
                          f)
            series.append(path)
        res = subprocess.run(
            [sys.executable,
             os.path.join(repo_root, "tools", "bench_diff.py")]
            + series, capture_output=True, text=True, timeout=60,
            cwd=repo_root)
        if res.returncode != 0:
            problems.append("bench_diff over the generated series "
                            "exited %d: %s"
                            % (res.returncode,
                               (res.stdout + res.stderr)[-400:]))
        # ...and a synthetic 20% regression against its best must
        # trip it
        synth = {"rc": 0, "parsed": {
            "metric": "m", "value": 160.0 * 0.8, "unit": "u"}}
        synth_path = os.path.join(reg_dir, "BENCH_zz_synthetic.json")
        with open(synth_path, "w") as f:
            json.dump(synth, f)
        res = subprocess.run(
            [sys.executable,
             os.path.join(repo_root, "tools", "bench_diff.py")]
            + series + [synth_path],
            capture_output=True, text=True, timeout=60, cwd=repo_root)
        if res.returncode == 0:
            problems.append("bench_diff did NOT flag a synthetic 20%% "
                            "regression (output: %s)"
                            % res.stdout[-300:])
    except subprocess.TimeoutExpired:
        problems.append("costdb dry-run timed out")
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return problems


def autotune_check(repo_root=_ROOT):
    """Autotuner gate (docs/api/autotune.md).  Four checks:

    1. a dry-run tune (interpret mode: the real Pallas code paths on
       CPU) of one flash shape, forward and backward, via
       ``tools/autotune.py`` leaves a STRICT-parseable
       ``mxtpu-tunecache/1`` cache whose entries carry both the tuned
       and heuristic walls with tuned <= heuristic;
    2. a SECOND run of the same commands is all cache hits (tuned 0,
       cached == number of keys) — the skip-already-tuned contract the
       zoo sweep relies on;
    3. the learned cost model fits on the costdb records the tuning
       run accumulated (``--fit-model`` emits a loadable
       ``mxtpu-costmodel/1`` document with calibration stats);
    4. a model fitted on seeded pathological records (wall = 100x the
       roofline-attainable time) flags a conv graph via MXG010, and a
       well-calibrated model (wall == attainable) does NOT — the rule
       actually discriminates.

    Returns a list of problem strings (empty = clean)."""
    import json
    import shutil
    import subprocess
    import tempfile

    problems = []
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_autotune_check_")
    cache = os.path.join(tmpdir, "tunecache")
    dbdir = os.path.join(tmpdir, "costdb")
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu"})
    env.pop("MXNET_TPU_TUNE_CACHE", None)
    env.pop("MXNET_TPU_COSTDB", None)
    tool = os.path.join(repo_root, "tools", "autotune.py")
    cmds = [
        [sys.executable, tool, "--op", "flash_fwd", "--shapes",
         "1x256x1x32", "--repeats", "1", "--max-candidates", "3",
         "--interpret", "--cache", cache, "--costdb", dbdir, "--json"],
        [sys.executable, tool, "--op", "flash_bwd", "--shapes",
         "1x256x1x32", "--repeats", "1", "--max-candidates", "3",
         "--interpret", "--cache", cache, "--costdb", dbdir, "--json"],
    ]

    def run_cmds():
        docs = []
        for cmd in cmds:
            res = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=240, cwd=repo_root, env=env)
            if res.returncode != 0:
                problems.append("%s exited %d: %s"
                                % (" ".join(cmd[2:6]), res.returncode,
                                   (res.stdout + res.stderr)[-400:]))
                return None
            try:
                docs.append(json.loads(res.stdout.strip()
                                       .splitlines()[-1]))
            except (ValueError, IndexError) as e:
                problems.append("autotune.py printed no parseable "
                                "JSON: %s" % e)
                return None
        return docs

    try:
        docs = run_cmds()
        if docs is None:
            return problems
        if sum(d["tuned"] for d in docs) < 2:
            problems.append("first tuning run searched %d key(s), "
                            "expected 2"
                            % sum(d["tuned"] for d in docs))

        from mxnet_tpu import autotune
        try:
            entries, _sk = autotune.read_entries(cache, strict=True)
        except ValueError as e:
            problems.append("tunecache reader (strict) rejects the "
                            "dry-run cache: %s" % e)
            return problems
        if len(entries) < 2:
            problems.append("expected >= 2 cache entries, got %d"
                            % len(entries))
        for e in entries:
            tw, hw = e.get("wall_s"), e.get("heuristic_wall_s")
            if tw is None or hw is None:
                problems.append("entry %s lacks the tuned/heuristic "
                                "A/B walls" % e["op"])
            elif tw > hw * (1 + 1e-9):
                problems.append("entry %s: tuned wall %.3g > heuristic "
                                "%.3g — the heuristic must be in the "
                                "candidate set" % (e["op"], tw, hw))

        docs2 = run_cmds()
        if docs2 is None:
            return problems
        if any(d["tuned"] != 0 for d in docs2) or \
                sum(d["cached"] for d in docs2) < 2:
            problems.append("second run was not all cache hits "
                            "(tuned=%s cached=%s)"
                            % ([d["tuned"] for d in docs2],
                               [d["cached"] for d in docs2]))

        # cost model fit on the accumulated ground truth
        model_path = os.path.join(tmpdir, "costmodel.json")
        res = subprocess.run(
            [sys.executable, tool, "--fit-model", model_path,
             "--costdb", dbdir, "--json"],
            capture_output=True, text=True, timeout=120,
            cwd=repo_root, env=env)
        if res.returncode != 0:
            problems.append("--fit-model exited %d: %s"
                            % (res.returncode,
                               (res.stdout + res.stderr)[-400:]))
        else:
            try:
                autotune.CostModel.load(model_path)
            except (ValueError, OSError) as e:
                problems.append("fitted cost model does not load: %s"
                                % e)

        # MXG010 discriminates: pathological records -> flagged;
        # roofline-attaining records -> clean
        from mxnet_tpu.analysis import verify_model
        from mxnet_tpu.telemetry import costdb as costdb_mod
        backend = costdb_mod.backend_name()
        pf = costdb_mod.peak_flops(backend)
        pbw = costdb_mod.peak_bandwidth(backend)

        def seeded(factor):
            recs = []
            for i in range(16):
                flops = 10.0 ** (6 + i % 6)
                bytes_ = flops / 8.0
                att = costdb_mod._attainable_s(flops, bytes_, pf, pbw)
                recs.append({"wall_s": att * factor, "flops": flops,
                             "bytes_accessed": bytes_,
                             "block_config": None, "backend": backend})
            return autotune.CostModel().fit(recs)

        _net, rep = verify_model("lenet", cost_model=seeded(100.0),
                                 slow_factor=3.0)
        if not [d for d in rep if d.rule == "MXG010"]:
            problems.append("pathological cost model raised no MXG010 "
                            "on the seeded graph")
        _net, rep = verify_model("lenet", cost_model=seeded(1.0),
                                 slow_factor=3.0)
        flagged = [d for d in rep if d.rule == "MXG010"]
        if flagged:
            problems.append("roofline-attaining cost model still "
                            "flagged %d node(s) via MXG010 — the rule "
                            "does not discriminate" % len(flagged))
    except subprocess.TimeoutExpired:
        problems.append("autotune dry-run timed out")
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return problems


def reshard_check(repo_root=_ROOT):
    """Elastic reshard gate (docs/api/reshard.md): run
    ``tools/reshard.py --selfcheck`` in a subprocess with 8 virtual
    CPU devices — a checkpoint saved on a fake ``{data:2, model:2}``
    mesh must reshard-load bit-exactly (params + aux + optimizer
    state vs a gather reference) on ``{data:4}`` and on a single
    device, the resumed trainers must step, and the offline
    converter's ``--verify`` roundtrip must be bit-identical.
    Returns a list of problem strings (empty = clean)."""
    import subprocess

    problems = []
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the selfcheck builds 4-device meshes: force the virtual device
    # count (it would default to 1 on a bare CPU host)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.pop("MXNET_TPU_TELEMETRY_JSONL", None)
    env.pop("MXNET_TPU_RESHARD_RULES", None)
    env.pop("MXNET_TPU_FAULTS", None)
    try:
        res = subprocess.run(
            [sys.executable,
             os.path.join(repo_root, "tools", "reshard.py"),
             "--selfcheck"],
            capture_output=True, text=True, timeout=300,
            cwd=repo_root, env=env)
    except subprocess.TimeoutExpired:
        return ["reshard --selfcheck timed out"]
    if res.returncode != 0:
        problems.append("reshard --selfcheck exited %d: %s"
                        % (res.returncode,
                           (res.stdout + res.stderr)[-800:]))
    elif "reshard selfcheck OK" not in res.stdout:
        problems.append("reshard --selfcheck exited 0 without the OK "
                        "marker: %s" % res.stdout[-400:])
    return problems


def numerics_check(repo_root=_ROOT):
    """Training-health numerics gate (stage 11).  Three legs, all on a
    tiny ShardedTrainer with per-step sampling:

    1. **strict NaN stop + provenance** — arm the ``numerics.nonfinite``
       resilience seam (the trainer poisons a data input with NaNs
       instead of raising); the next sampled step must stop with an
       MXNetError naming non-finite tensors, and the flight dump's
       ``numerics_anomaly`` event must carry provenance naming the
       first producing node of the seeded NaN.
    2. **ledger twin** — two identical dry runs must produce ledgers
       ``tools/numdiff.py`` calls bit-clean (exit 0).
    3. **seeded divergence** — a third run with one param perturbed
       before step 3 must make numdiff exit nonzero naming step 3.

    Returns a list of problem strings (empty = clean)."""
    import json
    import shutil
    import subprocess
    import tempfile

    import numpy as np

    problems = []
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_numerics_gate_")
    saved = {k: os.environ.get(k)
             for k in ("MXNET_TPU_FLIGHT_DIR", "MXNET_TPU_FAULTS",
                       "MXNET_TPU_NUMERICS_EVERY",
                       "MXNET_TPU_NUMERICS_STRICT",
                       "MXNET_TPU_NUMERICS_LEDGER")}
    from mxnet_tpu import models, resilience, telemetry
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh

    def dry_run(ledger, steps=4, perturb_at=None):
        """One deterministic tiny-MLP run appending to ``ledger``."""
        os.environ["MXNET_TPU_NUMERICS_LEDGER"] = ledger
        telemetry.numerics.reset()
        net = models.get_model("mlp", num_classes=10)
        trainer = ShardedTrainer(
            net, build_mesh(tp=1), data_shapes={"data": (8, 64)},
            label_shapes={"softmax_label": (8,)}, dtype="float32",
            seed=0)
        rng = np.random.RandomState(3)
        batch = {"data": rng.uniform(-1, 1, (8, 64)).astype(np.float32),
                 "softmax_label": rng.randint(0, 10, 8)
                 .astype(np.float32)}
        for i in range(steps):
            if perturb_at == i + 1:
                import jax.numpy as jnp
                name = sorted(trainer.params)[0]
                trainer.params[name] = trainer.params[name] * \
                    jnp.float32(3.0)
            trainer.step(batch)
        return trainer

    try:
        os.environ["MXNET_TPU_FLIGHT_DIR"] = tmpdir
        os.environ["MXNET_TPU_NUMERICS_EVERY"] = "1"
        os.environ["MXNET_TPU_NUMERICS_STRICT"] = "1"
        os.environ.pop("MXNET_TPU_FAULTS", None)
        resilience.clear_faults()

        # ---- leg 1: seeded NaN -> strict stop with provenance
        trainer = dry_run(os.path.join(tmpdir, "warm.ledger"), steps=2)
        os.environ["MXNET_TPU_FAULTS"] = "numerics.nonfinite:n=1"
        rng = np.random.RandomState(3)
        batch = {"data": rng.uniform(-1, 1, (8, 64)).astype(np.float32),
                 "softmax_label": rng.randint(0, 10, 8)
                 .astype(np.float32)}
        try:
            trainer.step(batch)
            problems.append("seeded NaN did not stop the strict-mode "
                            "run")
        except MXNetError as e:
            if "non" not in str(e) or "finite" not in str(e):
                problems.append("strict-mode error does not describe "
                                "the non-finite anomaly: %s"
                                % str(e)[:200])
        os.environ.pop("MXNET_TPU_FAULTS", None)
        resilience.clear_faults()
        dumps = sorted(f for f in os.listdir(tmpdir)
                       if f.startswith("flight-")
                       and f.endswith(".json"))
        if not dumps:
            problems.append("strict NaN stop left no flight dump")
        else:
            prov_nodes = []
            for name in dumps:
                with open(os.path.join(tmpdir, name)) as f:
                    doc = json.load(f)
                for ev in doc.get("events", ()):
                    if ev.get("kind") == "numerics_anomaly" and \
                            ev.get("provenance"):
                        prov_nodes.append(ev["provenance"].get("node"))
            if not any(prov_nodes):
                problems.append("no numerics_anomaly flight event "
                                "carries provenance naming the seeded "
                                "node (dumps: %s)" % dumps)

        # ---- legs 2+3: ledger twin + seeded divergence -> numdiff
        os.environ["MXNET_TPU_NUMERICS_STRICT"] = "0"
        led_a = os.path.join(tmpdir, "a.ledger")
        led_b = os.path.join(tmpdir, "b.ledger")
        led_c = os.path.join(tmpdir, "c.ledger")
        dry_run(led_a)
        dry_run(led_b)
        dry_run(led_c, perturb_at=3)
        numdiff = os.path.join(repo_root, "tools", "numdiff.py")

        res = subprocess.run([sys.executable, numdiff, led_a, led_b],
                             capture_output=True, text=True, timeout=60)
        if res.returncode != 0:
            problems.append("numdiff over twin ledgers exited %d: %s"
                            % (res.returncode,
                               (res.stdout + res.stderr)[-300:]))
        elif "bit-clean" not in res.stdout:
            problems.append("twin ledgers not reported bit-clean: %s"
                            % res.stdout[-300:])

        res = subprocess.run([sys.executable, numdiff, led_a, led_c],
                             capture_output=True, text=True, timeout=60)
        if res.returncode != 1:
            problems.append("numdiff over the seeded divergence exited "
                            "%d (want 1): %s"
                            % (res.returncode,
                               (res.stdout + res.stderr)[-300:]))
        elif "step 3" not in res.stdout:
            problems.append("numdiff did not name the seeded first "
                            "diverging step 3: %s" % res.stdout[-300:])
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        resilience.clear_faults()
        telemetry.numerics.reset()
        shutil.rmtree(tmpdir, ignore_errors=True)
    return problems


def plansearch_check(repo_root=_ROOT):
    """Plan-search gate (stage 12).  Three legs:

    1. **search + commit** — ``tools/plan_search.py --model mlp`` under
       a tiny budget (interpret/CPU measurement) must commit a
       ``graph_plan`` entry whose predicted wall is <= the greedy
       plan's AND whose measured wall is <= the measured greedy wall
       (greedy is always in the measured set);
    2. **pure cache hit** — a second identical run must answer from
       the cache with ZERO search (``cached`` true, ``searched`` 0);
    3. **output parity** — an Executor forward+backward lowered
       through a decision-transformed plan (chain split + per-region
       layout override) must match the greedy executor numerically.

    Returns a list of problem strings (empty = clean)."""
    import json
    import shutil
    import subprocess
    import tempfile

    import numpy as np

    problems = []
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_plansearch_gate_")
    cache = os.path.join(tmpdir, "cache")
    script = os.path.join(repo_root, "tools", "plan_search.py")
    cmd = [sys.executable, script, "--model", "mlp", "--budget", "8",
           "--beam", "4", "--topk", "2", "--repeats", "1",
           "--cache", cache, "--json"]
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("MXNET_TPU_TUNE_CACHE", None)

    def run_driver():
        res = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=600, env=env)
        if res.returncode != 0:
            return None, "plan_search exited %d: %s" % (
                res.returncode, (res.stdout + res.stderr)[-300:])
        try:
            return json.loads(res.stdout.strip().splitlines()[-1]), None
        except (ValueError, IndexError) as e:
            return None, "plan_search emitted no JSON doc: %s (%s)" % (
                e, res.stdout[-200:])

    try:
        # ---- leg 1: search under a tiny budget, commit the winner
        doc, err = run_driver()
        if err:
            problems.append(err)
        else:
            if doc.get("error"):
                problems.append("search run errored: %s" % doc["error"])
            gp = doc.get("greedy_predicted_s")
            if doc.get("predicted_s") is None or gp is None or \
                    doc["predicted_s"] > gp * (1 + 1e-9):
                problems.append(
                    "committed plan's predicted wall %r is not <= the "
                    "greedy plan's %r" % (doc.get("predicted_s"), gp))
            gw = doc.get("greedy_wall_s")
            if doc.get("wall_s") is None or gw is None or \
                    doc["wall_s"] > gw * (1 + 1e-9):
                problems.append(
                    "committed winner's measured wall %r is worse than "
                    "the measured greedy %r" % (doc.get("wall_s"), gw))
            if not doc.get("measured"):
                problems.append("no candidate plan was measured")
            if not os.path.isdir(cache) or not any(
                    f.startswith("tunecache") and f.endswith(".jsonl")
                    for f in os.listdir(cache)):
                problems.append("no tunecache*.jsonl persisted under "
                                "the --cache directory")

        # ---- leg 2: second run = pure cache hit, zero search
        doc2, err = run_driver()
        if err:
            problems.append(err)
        elif not (doc2.get("cached") and doc2.get("searched") == 0):
            problems.append(
                "second run was not a pure cache hit (cached=%r, "
                "searched=%r)" % (doc2.get("cached"),
                                  doc2.get("searched")))

        # ---- leg 3: searched-vs-greedy executor output parity
        import mxnet_tpu as mx
        from mxnet_tpu.analysis import fusion as _fusion
        from mxnet_tpu.ops.fused import block_fusion

        data = mx.sym.Variable("data")
        net = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1),
                                 num_filter=8, no_bias=True, name="c0")
        net = mx.sym.BatchNorm(net, name="b0", fix_gamma=False)
        net = mx.sym.Activation(net, act_type="relu", name="r0")
        net = mx.sym.Convolution(net, kernel=(1, 1), num_filter=8,
                                 no_bias=True, name="c1")
        net = mx.sym.BatchNorm(net, name="b1", fix_gamma=False)
        net = mx.sym.Activation(net, act_type="relu", name="r1")
        net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=10,
                                    name="fc")
        sym = mx.sym.SoftmaxOutput(net, name="softmax")
        topo = sym._topo()
        plan = _fusion.plan_block_fusion(topo, sym._entries,
                                         record=False, decisions={})
        chains = sorted(b.chain for b in plan.blocks.values()
                        if b.kind == "conv_bn_act")
        decisions = {"chains": {chains[0]: "conv_bn"},
                     "layouts": {chains[1]: "NHWC"}}

        def run_exec(dec):
            with block_fusion(True), _fusion.plan_decisions(dec):
                ex = sym.simple_bind(mx.cpu(), data=(4, 3, 8, 8),
                                     softmax_label=(4,))
            rng = np.random.RandomState(0)
            for name, arr in ex.arg_dict.items():
                arr[:] = (rng.randint(0, 10, arr.shape)
                          if name == "softmax_label"
                          else rng.uniform(-0.5, 0.5, arr.shape)) \
                    .astype(np.float32)
            ex.forward(is_train=True)
            out = ex.outputs[0].asnumpy()
            ex.backward()
            return out, {k: v.asnumpy()
                         for k, v in ex.grad_dict.items()
                         if v is not None}

        # {} pins the reference to EXPLICIT greedy: with None the bind
        # would consult any ambient MXNET_TPU_TUNE_CACHE and could
        # silently compare a committed plan against itself
        o_ref, g_ref = run_exec({})
        o_alt, g_alt = run_exec(decisions)
        if not np.allclose(o_ref, o_alt, rtol=2e-5, atol=2e-6):
            problems.append("searched-plan executor outputs diverge "
                            "from greedy (max |d|=%.3g)"
                            % float(np.max(np.abs(o_ref - o_alt))))
        for k in g_ref:
            if not np.allclose(g_ref[k], g_alt[k], rtol=2e-4,
                               atol=2e-5):
                problems.append("searched-plan gradient %r diverges "
                                "from greedy" % k)
                break
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return problems


def overlap_check(repo_root=_ROOT):
    """Overlap gate (stage 15): run ``tools/overlap_ab.py --json`` —
    the 2-process seeded-slow-rank A/B — and require every gate in its
    document: bit-identical final params across the modes, and
    parseable ``overlap`` bucket flight events on the on leg, as many
    on one rank as on the other.  The fast rank's waits are the
    document's to report.  Returns a list of problem strings (empty =
    clean)."""
    import json
    import subprocess

    problems = []
    try:
        res = subprocess.run(
            [sys.executable,
             os.path.join(repo_root, "tools", "overlap_ab.py"),
             "--json"],
            # > overlap_ab's own worst case: 2 legs x 300s a leg
            capture_output=True, text=True, timeout=700, cwd=repo_root)
    except subprocess.TimeoutExpired:
        return ["overlap A/B dry run timed out"]
    if res.returncode not in (0, 1):
        return ["overlap_ab.py crashed (%d): %s"
                % (res.returncode, (res.stdout + res.stderr)[-800:])]
    try:
        doc = json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError) as e:
        return ["overlap_ab.py output is not parseable JSON: %s (%s)"
                % (e, res.stdout[-400:])]
    if doc.get("schema") != "mxtpu-overlap-ab/1":
        problems.append("A/B schema %r != 'mxtpu-overlap-ab/1'"
                        % doc.get("schema"))
    for mode in ("on", "off"):
        leg = doc.get(mode) or {}
        if not all(isinstance(leg.get(k), (int, float))
                   for k in ("wait_s", "share")):
            problems.append("fast rank's wait and share of the %s leg "
                            "are not reported: %r" % (mode, leg))
    if not doc.get("params_bit_identical"):
        problems.append("final params differ between overlap on/off: %r"
                        % doc.get("params_by_rank"))
    if not doc.get("overlap_flight_events"):
        problems.append("no parseable 'overlap' bucket flight events "
                        "in the on leg's dumps")
    if not doc.get("pass"):
        problems.append("overlap_ab.py's gates do not hold: buckets by "
                        "rank %r over %r steps"
                        % (doc.get("overlap_buckets_by_rank"),
                           doc.get("steps")))
    return problems


def spmd_check(repo_root=_ROOT):
    """SPMD gate (stage 13).  Two legs:

    1. seeded-defect discrimination — one fixture per MXG011-016 rule;
       each must fire with the offending node/stage/axis named in the
       diagnostic;
    2. clean sweep — every zoo model under a {data:2} mesh, plus the
       composed pipeline (mlp tower, dp x pp) and sequence-parallel
       (ring-attention LM) configs, must report ZERO findings.
    """
    problems = []
    import mxnet_tpu as mx
    from mxnet_tpu import analysis
    from mxnet_tpu.analysis import spmd
    from mxnet_tpu.analysis.verifier import Report

    def tower():
        net = mx.sym.Variable("data")
        for i in range(4):
            net = mx.sym.FullyConnected(net, num_hidden=32,
                                        name="fc%d" % i)
            net = mx.sym.Activation(net, act_type="relu",
                                    name="relu%d" % i)
        net = mx.sym.FullyConnected(net, num_hidden=8, name="out")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    def ring_lm(seq, vocab=16, d=16, heads=2):
        data = mx.sym.Variable("data")
        x = mx.sym.Embedding(data, input_dim=vocab, output_dim=d,
                             name="embed")
        h = mx.sym.LayerNorm(x, name="ln1")
        qkv = mx.sym.FullyConnected(h, num_hidden=3 * d, flatten=False,
                                    name="qkv")
        qkv = mx.sym.Reshape(qkv, shape=(0, 0, 3, heads, -1))
        cut = lambda i: mx.sym.Reshape(
            mx.sym.slice_axis(qkv, axis=2, begin=i, end=i + 1),
            shape=(0, 0, -3, -2))
        att = mx.sym._contrib_RingAttention(cut(0), cut(1), cut(2),
                                            causal=True, name="attn")
        att = mx.sym.Reshape(att, shape=(0, 0, -3))
        x = x + mx.sym.FullyConnected(att, num_hidden=d, flatten=False,
                                      name="proj")
        x = mx.sym.Reshape(mx.sym.LayerNorm(x, name="ln_f"),
                           shape=(-1, d))
        logits = mx.sym.FullyConnected(x, num_hidden=vocab, name="head")
        return mx.sym.SoftmaxOutput(logits, name="softmax")

    def expect(tag, report, rule, *needles):
        found = [d for d in report if d.rule == rule]
        if not found:
            problems.append("%s: rule %s did not fire (%s)"
                            % (tag, rule, report))
            return
        text = "\n".join(str(d) for d in found)
        for needle in needles:
            if needle not in text:
                problems.append("%s: %s diagnostic does not name %r: %s"
                                % (tag, rule, needle, text))

    # --- MXG011: rank-subset kvstore push + ragged ring shard
    rep = spmd.verify_spmd(None, {"data": 2}, analysis.build_config(
        kv_push=True, kv_push_ranks=[0]))
    expect("kv-subset", rep, "MXG011", "kv.push", "deadlock")
    # bucketed overlap schedule (parallel/overlap.py): a seeded
    # rank-divergent bucket launch order must be named as the first
    # mismatched bucket; the plan-order schedule must verify clean
    rep = spmd.verify_spmd(None, {"data": 2}, analysis.build_config(
        kv_push=True, kv_buckets=[4096, 2048, 1024],
        kv_bucket_order={1: [2, 1, 0]}))
    expect("kv-bucket-order", rep, "MXG011", "kv.bucket", "diverges")
    rep = spmd.verify_spmd(None, {"data": 2}, analysis.build_config(
        kv_push=True, kv_buckets=[4096, 2048, 1024]))
    if len(rep):
        problems.append("clean bucketed kv schedule flagged: %s" % rep)
    rep = spmd.verify_spmd(
        ring_lm(18), {"data": 1, "model": 4},
        analysis.build_config(sequence_parallel=True,
                              data_shapes={"data": (4, 18)},
                              label_shapes={"softmax_label": (4, 18)}))
    expect("ragged-ring", rep, "MXG011", "attn", "ppermute")

    # --- MXG012: axis_index-conditioned psum in a jaxpr
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P
    from mxnet_tpu.parallel.mesh import shard_map_nocheck
    import numpy as np
    mesh1 = Mesh(np.array(jax.devices("cpu")[:1]), ("data",))

    def bad(x):
        r = lax.axis_index("data")
        return lax.cond(r == 0, lambda v: lax.psum(v, "data"),
                        lambda v: v, x)

    rep = Report()
    spmd.check_rank_divergence(
        jax.make_jaxpr(shard_map_nocheck(bad, mesh1, (P("data"),),
                                         P("data")))(jnp.ones((4,))),
        rep, where="seeded_step")
    expect("rank-cond", rep, "MXG012", "seeded_step", "psum")

    # --- MXG013: duplicated stage node + fused straddle
    net = tower()
    from mxnet_tpu.parallel.pipeline import plan_pipeline_stages
    stages = plan_pipeline_stages(net._topo(), net._entries,
                                  {"data", "softmax_label"}, 2)
    dup = stages[0]["nodes"][-1]
    stages[1]["nodes"] = [dup] + stages[1]["nodes"]
    cfg = analysis.build_config(pipeline_stages=2,
                                pipeline_microbatches=2,
                                data_shapes={"data": (16, 12)},
                                label_shapes={"softmax_label": (16,)})
    rep = Report()
    spmd.check_pipeline_partition(net, {"data": 1, "pipe": 2}, cfg,
                                  rep, stages=stages)
    expect("dup-stage", rep, "MXG013", dup.name, "BOTH")
    fcfg = dict(cfg)
    fcfg["fuse_blocks"] = True
    rep = spmd.verify_spmd(tower(), {"data": 2, "pipe": 2}, fcfg)
    expect("straddle", rep, "MXG013", "straddles")

    # --- MXG014: typo'd reshard-rule axis
    rep = spmd.verify_spmd(
        tower(), {"data": 2, "model": 2},
        analysis.build_config(
            data_shapes={"data": (16, 12)},
            label_shapes={"softmax_label": (16,)},
            reshard_rules=".*fc0_weight=modle"))
    expect("typo-axis", rep, "MXG014", "modle", "fc0_weight")

    # --- MXG015: donated group read after dispatch
    rep = spmd.verify_spmd(None, {"data": 2}, analysis.build_config(
        donate=["params"], post_step_reads=["params"]))
    expect("donate-read", rep, "MXG015", "params", "donated")

    # --- MXG016: backward ring rotating the wrong way
    perm = ((0, 1), (1, 2), (2, 3), (3, 0))
    fwd = [spmd.CollectiveEvent("ppermute", "sp", (2, 4, 2, 8),
                                node="attn", perm=perm)]
    rep = Report()
    spmd.check_gradient_parity(
        fwd, [spmd.CollectiveEvent("ppermute", "sp", (2, 4, 2, 8),
                                   node="attn", perm=perm)],
        rep, where="attn")
    expect("wrong-ring", rep, "MXG016", "attn", "wrong way")

    # --- clean sweep: zoo under a dp mesh + composed configs
    from mxnet_tpu.models import _MODELS
    for name in _MODELS:
        _net, report = analysis.verify_model(
            name, mesh={"data": 2}, parallel=analysis.build_config())
        if len(report):
            problems.append("clean sweep: model %s has findings: %s"
                            % (name, report))
    report = spmd.verify_spmd(tower(), {"data": 2, "pipe": 2}, cfg)
    if len(report):
        problems.append("clean sweep: pipeline config has findings: %s"
                        % report)
    report = spmd.verify_spmd(
        ring_lm(16), {"data": 1, "model": 4},
        analysis.build_config(sequence_parallel=True, kv_push=True,
                              data_shapes={"data": (4, 16)},
                              label_shapes={"softmax_label": (4, 16)}))
    if len(report):
        problems.append("clean sweep: sequence config has findings: %s"
                        % report)
    return problems


def ioview_check(repo_root=_ROOT):
    """IO observability gate (docs/api/telemetry.md): a dry-run
    pipeline with a seeded slow stage — an ``io.prefetch``
    ``kind=delay`` fault, so the PrefetchingIter producer's work window
    is genuinely slow — must leave a JSONL step-log whose ``io`` blocks
    ``tools/io_top.py --json`` parses (schema ``mxtpu-iotop/1``) naming
    the seeded ``host_prefetch`` stage producer-bound with the iterator
    position attached, and the live classifier must agree (flight
    ``io_bottleneck`` event + ``mxtpu_io_bottleneck_total`` counter).
    Returns a list of problem strings (empty = clean)."""
    import json
    import shutil
    import subprocess
    import tempfile

    import numpy as np

    from mxnet_tpu import io as io_mod, resilience, telemetry
    from mxnet_tpu.telemetry import flight, ioview

    problems = []
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_ioview_gate_")
    log_path = os.path.join(tmpdir, "io.jsonl")
    saved = {k: os.environ.get(k)
             for k in ("MXNET_TPU_TELEMETRY_JSONL", "MXNET_TPU_FAULTS",
                       "MXNET_TPU_IOVIEW_EVERY")}
    try:
        ioview.reset()
        os.environ["MXNET_TPU_TELEMETRY_JSONL"] = log_path
        os.environ["MXNET_TPU_IOVIEW_EVERY"] = "1"
        # the seeded slow stage, through the existing io.prefetch seam
        # family: every producer batch sleeps 30ms inside the seam
        os.environ["MXNET_TPU_FAULTS"] = \
            "io.prefetch:kind=delay,delay=0.03"
        x = np.zeros((32, 4), np.float32)
        y = np.zeros(32, np.float32)
        it = io_mod.PrefetchingIter(
            io_mod.NDArrayIter(x, y, batch_size=8))
        ioview.track(it)
        for _batch in it:
            telemetry.step_end(samples=8, step_time=0.001)
        verdict = ioview.classify(force=True)
        if not verdict or verdict.get("verdict") != "producer-bound" \
                or verdict.get("stage") != "host_prefetch":
            problems.append("live classifier did not name the seeded "
                            "slow stage (got %r)" % (verdict,))
        if not any(e.get("kind") == "io_bottleneck"
                   for e in flight.events()):
            problems.append("no io_bottleneck flight event recorded")
        ctr = telemetry.counter("mxtpu_io_bottleneck_total").labels(
            stage="host_prefetch").get()
        if not ctr:
            problems.append("mxtpu_io_bottleneck_total{stage="
                            "host_prefetch} did not advance")
        res = subprocess.run(
            [sys.executable,
             os.path.join(repo_root, "tools", "io_top.py"),
             log_path, "--json"],
            capture_output=True, text=True, timeout=60, cwd=repo_root)
        if res.returncode != 0:
            problems.append("io_top --json failed (%d): %s"
                            % (res.returncode, res.stderr[-400:]))
            return problems
        try:
            report = json.loads(res.stdout)
        except ValueError as e:
            problems.append("io_top --json is not parseable: %s" % e)
            return problems
        if report.get("schema") != "mxtpu-iotop/1":
            problems.append("io_top schema %r != 'mxtpu-iotop/1'"
                            % report.get("schema"))
        b = report.get("bottleneck") or {}
        if b.get("verdict") != "producer-bound" or \
                b.get("stage") != "host_prefetch":
            problems.append("io_top did not name the seeded slow stage "
                            "(got %r)" % (b,))
        rank0 = (report.get("ranks") or {}).get("0") or {}
        pos = rank0.get("position")
        if not isinstance(pos, dict) or "offset" not in pos:
            problems.append("io_top report lacks the iterator position "
                            "(got %r)" % (pos,))
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        resilience.clear_faults()
        ioview.reset()
        shutil.rmtree(tmpdir, ignore_errors=True)
    return problems


def _scrubbed_launch_env(extra):
    """Worker env for a launch.py CPU fleet: one device per process,
    no inherited rank identity."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_TPU_NUM_PROCESSES", None)
    env.pop("MXNET_TPU_PROCESS_ID", None)
    env.update(extra)
    return env


def io_resume_check(repo_root=_ROOT):
    """Exactly-once data plane gate (stage 16, docs/api/io_resume.md).

    Leg A — mid-epoch fleet death and elastic resume: a 2-process
    ``launch.py`` fleet (``tests/dist_ioresume_worker.py``) consuming
    one :class:`~mxnet_tpu.io_resume.ShardedLedgerIter` epoch SIGKILLs
    itself mid-epoch, after a checkpoint whose manifest carries the
    ledger ``data_state``; a 1-process relaunch resumes via
    ``load_latest_checkpoint`` + ``restore_data_iter`` (cursor remap
    world 2 -> 1 through the ``io.remap`` path).  The accounting
    harness over both legs' consumed-id logs must prove the union —
    checkpointed leg-A steps plus the whole resume leg — is EXACTLY
    one epoch: nothing dropped, nothing double-consumed.

    Leg B — backpressure actuation: a seeded slow producer
    (``io.prefetch`` ``kind=delay``) under ``MXNET_TPU_BACKPRESSURE=1``
    must flip the live verdict producer-bound and the controller must
    raise the device prefetch depth — visible in the
    ``mxtpu_backpressure_adjust_total`` counter, a
    ``backpressure_adjust`` flight event, AND a jsonl event record
    (the run-timeline route).  Returns problem strings (empty = clean).
    """
    import json
    import shutil
    import subprocess
    import tempfile

    import numpy as np

    from mxnet_tpu import io as io_mod, io_resume, resilience, telemetry
    from mxnet_tpu.model import find_checkpoints
    from mxnet_tpu.telemetry import flight, ioview

    problems = []
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_ioresume_gate_")
    try:
        # ---------------- leg A: fleet kill + world-size-1 resume
        prefix = os.path.join(tmpdir, "job")
        idlog = os.path.join(tmpdir, "ids.jsonl")
        worker = os.path.join(repo_root, "tests",
                              "dist_ioresume_worker.py")
        env = _scrubbed_launch_env({
            "IORESUME_PHASE": "train", "IORESUME_CKPT": prefix,
            "IORESUME_IDLOG": idlog, "IORESUME_KILL_STEP": "5",
            "IORESUME_CKPT_EVERY": "2"})
        res = subprocess.run(
            [sys.executable,
             os.path.join(repo_root, "tools", "launch.py"),
             "-n", "2", "--launcher", "local",
             sys.executable, worker],
            capture_output=True, text=True, timeout=300,
            cwd=repo_root, env=env)
        if res.returncode == 0:
            problems.append("leg A fleet was SIGKILLed mid-epoch but "
                            "launch.py exited 0")
            return problems
        if "FLEET NEVER ASSEMBLED" in res.stderr:
            problems.append("leg A: a rank waited in vain for the others "
                            "to reach the kill step: %s" % res.stderr[-600:])
            return problems
        eps = find_checkpoints(prefix)
        if not eps:
            problems.append("leg A left no complete checkpoint: %s"
                            % (res.stdout + res.stderr)[-600:])
            return problems
        env = _scrubbed_launch_env({
            "IORESUME_PHASE": "resume", "IORESUME_CKPT": prefix,
            "IORESUME_IDLOG": idlog})
        res = subprocess.run(
            [sys.executable,
             os.path.join(repo_root, "tools", "launch.py"),
             "-n", "1", "--launcher", "local",
             sys.executable, worker],
            capture_output=True, text=True, timeout=300,
            cwd=repo_root, env=env)
        out = res.stdout + res.stderr
        if res.returncode != 0:
            problems.append("resume leg failed (%d): %s"
                            % (res.returncode, out[-800:]))
            return problems
        if "ioresume worker 0/1 OK phase=resume" not in out:
            problems.append("resume leg printed no OK line: %s"
                            % out[-400:])

        # the manifest must carry a versioned ledger data_state saved
        # at the old world size
        resumed = eps[-1]
        manifest = resilience.verify_manifest(prefix, resumed)
        entry = ((manifest or {}).get("meta") or {}).get("data_state")
        st = (entry or {}).get("state") or {}
        if st.get("kind") != "ledger" or st.get("world") != 2:
            problems.append("checkpoint manifest data_state is not a "
                            "world-2 ledger state (got %r)" % (st,))

        # accounting: checkpoint-covered train steps (step < resumed
        # epoch, both ranks — the post-checkpoint tail was consumed
        # but rolled back by the kill, so the resume leg re-consumes
        # those samples) plus the whole resume leg must cover the
        # epoch exactly once
        acct = io_resume.SampleAccountant(96)
        for rank in (0, 1):
            path = "%s.rank%d" % (idlog, rank)
            if not os.path.exists(path):
                problems.append("missing consumed-id log %r" % path)
                return problems
            for line in open(path):
                rec = json.loads(line)
                if rec["phase"] == "resume" or rec["step"] < resumed:
                    acct.record(rec["ids"])
        v = acct.verdict()
        if not v["ok"]:
            problems.append(
                "exactly-once accounting failed across the kill/resume "
                "legs: consumed=%d dropped=%s double=%s"
                % (v["consumed"], v["dropped"][:8], v["double"][:8]))

        # ---------------- leg B: seeded slow producer -> depth raise
        saved = {k: os.environ.get(k)
                 for k in ("MXNET_TPU_TELEMETRY_JSONL",
                           "MXNET_TPU_FAULTS", "MXNET_TPU_IOVIEW_EVERY",
                           "MXNET_TPU_IOVIEW_WINDOW",
                           "MXNET_TPU_BACKPRESSURE")}
        log_path = os.path.join(tmpdir, "bp.jsonl")
        try:
            ioview.reset()
            os.environ["MXNET_TPU_TELEMETRY_JSONL"] = log_path
            os.environ["MXNET_TPU_IOVIEW_EVERY"] = "1"
            os.environ["MXNET_TPU_IOVIEW_WINDOW"] = "0.01"
            os.environ["MXNET_TPU_BACKPRESSURE"] = "1"
            os.environ["MXNET_TPU_FAULTS"] = \
                "io.prefetch:kind=delay,delay=0.02"
            x = np.zeros((240, 4), np.float32)
            it = io_mod.DevicePrefetchIter(
                io_mod.NDArrayIter(x, np.zeros(240, np.float32),
                                   batch_size=8),
                lambda host: host, depth=2)
            ioview.track(it)
            ctl = io_resume.maybe_controller(it)
            if ctl is None:
                problems.append("maybe_controller installed nothing "
                                "over a DevicePrefetchIter chain")
                return problems
            base = telemetry.counter(
                "mxtpu_backpressure_adjust_total").labels(
                    knob="device_prefetch_depth",
                    direction="raise").get()
            for _batch in it:
                telemetry.step_end(samples=8, step_time=0.001)
                ctl.tick()
            if it.depth() <= 2:
                problems.append("seeded slow producer did not raise "
                                "the prefetch depth (still %d; "
                                "adjustments %r)"
                                % (it.depth(), ctl.adjustments))
            got = telemetry.counter(
                "mxtpu_backpressure_adjust_total").labels(
                    knob="device_prefetch_depth",
                    direction="raise").get()
            if got <= base:
                problems.append("mxtpu_backpressure_adjust_total{raise}"
                                " did not advance")
            if not any(e.get("kind") == "backpressure_adjust"
                       for e in flight.events()):
                problems.append("no backpressure_adjust flight event")
            events = []
            if os.path.exists(log_path):
                for line in open(log_path):
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if rec.get("event") == "backpressure_adjust":
                        events.append(rec)
            if not events:
                problems.append("no backpressure_adjust jsonl event "
                                "(run-timeline route) in the step-log")
        finally:
            for k, val in saved.items():
                if val is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = val
            resilience.clear_faults()
            ioview.reset()
    except subprocess.TimeoutExpired:
        problems.append("io_resume gate timed out")
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return problems


def memlive_check(repo_root=_ROOT):
    """Stage 17: static memory-liveness gate (analysis.memlive,
    MXG017-021, docs/api/memlive.md).

    Three legs: (1) zoo-wide drift bound — the static eval-schedule
    peak must agree with the XLA ``memory_analysis`` total of the
    aval-compiled forward, less the copy of the convolution weights
    that XLA:CPU holds in scratch, within ``MXNET_TPU_MEMLIVE_TOL`` on
    EVERY model (no MXG018, no errors); (2) seeded defects — an over-budget
    fixture must be rejected via MXG017 NAMING the peak node, and the
    remat/ZeRO/donation advice rules (MXG019/020/021) must each fire
    on a fixture built to deserve them; (3) ``tools/mem_top.py
    --json`` over an over-budget sharded train config must emit a
    strict-parseable ``mxtpu-memtop/1`` document carrying at least one
    remat and one ZeRO advice record.  The aval-only compile never
    touches a device and costs seconds, not minutes — infer_shape is
    deliberately bypassed in favor of the verifier's shape pass."""
    import contextlib
    import importlib.util
    import io as _io
    import json

    problems = []
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu.symbol as sym
    from mxnet_tpu.symbol import eval_graph, _classify_vars
    from mxnet_tpu.analysis import memlive
    from mxnet_tpu.analysis.verifier import (Report, _DEFAULT_IMAGE,
                                             _MODEL_SHAPES, _shape_pass,
                                             _topo_from_entries)
    from mxnet_tpu.models import _MODELS, get_model
    from mxnet_tpu.telemetry import memory as tmem

    # ---- leg 1: zoo-wide MXG018 drift bound
    for name in _MODELS:
        try:
            net = get_model(name, num_classes=10)
            shapes = dict(_MODEL_SHAPES.get(name, _DEFAULT_IMAGE))
            shapes = {k: (2,) + tuple(v[1:]) for k, v in shapes.items()}
            shapes["softmax_label"] = (2,)
            topo = _topo_from_entries(net._entries)
            arg_shapes, structs = _shape_pass(net, topo, shapes, {},
                                              Report())
            args_v, aux_v = _classify_vars(topo)
            avals = {id(n): jax.ShapeDtypeStruct(
                tuple(arg_shapes[n.name]), jnp.float32)
                for n in args_v + aux_v}

            def fwd(vals, _topo=topo, _entries=net._entries):
                outs, _ = eval_graph(_topo, _entries, vals,
                                     is_train=False)
                return outs

            compiled = jax.jit(fwd).lower(avals).compile()
            plan = tmem.plan_of(compiled, "ci.memlive.%s" % name)
            # the analyser predicts TPU memory and this plan is
            # XLA:CPU's, which keeps an HWIO copy of every
            # convolution's OIHW weight in scratch for the whole
            # program (docs/api/memlive.md has the numbers): the
            # copies are the backend's, not the graph's
            conv_w = {n.inputs[1][0].name for n in topo
                      if not n.is_variable
                      and n.op.name == "Convolution"
                      and n.inputs[1][0].is_variable}
            copies = sum(4 * int(np.prod(arg_shapes[w]))
                         for w in conv_w)
            report = Report()
            memlive.check_memory(net, shapes, report=report,
                                 is_train=False, advice=False,
                                 plan_total=plan.total_bytes - copies,
                                 topo=topo, structs=structs)
            for d in report:
                problems.append("drift %s: %s" % (name, d))
        except Exception as exc:  # mxlint: allow-broad-except(the gate reports any per-model failure as a finding rather than aborting the sweep)
            problems.append("drift %s: %r" % (name, exc))

    # ---- leg 2: seeded defects, one per rule
    d = sym.var("data")
    fc = sym.FullyConnected(d, num_hidden=4, name="fc")
    tiny = sym.Activation(fc, act_type="relu", name="act")
    tiny_shapes = {"data": (4, 8)}

    report = Report()
    memlive.check_memory(tiny, tiny_shapes, report=report,
                         budget_bytes=100, is_train=False,
                         advice=False, fuse=False)
    hits = [x for x in report if x.rule == "MXG017"]
    if not hits:
        problems.append("seeded over-budget fixture: MXG017 missing")
    elif hits[0].node != "fc" or hits[0].severity != "error":
        problems.append("MXG017 must name the peak node as an error, "
                        "got %s" % hits[0])

    report = Report()
    memlive.check_memory(tiny, tiny_shapes, report=report,
                         is_train=True, n_slots=2, mesh={"data": 4},
                         fuse=False)
    rules = {x.rule for x in report}
    for want in ("MXG019", "MXG020"):
        if want not in rules:
            problems.append("seeded advice fixture: %s missing "
                            "(got %s)" % (want, sorted(rules)))
    report = Report()
    memlive.check_memory(tiny, tiny_shapes, report=report,
                         is_train=False, fuse=False)
    if "MXG021" not in {x.rule for x in report}:
        problems.append("seeded un-donated-input fixture: MXG021 "
                        "missing")

    # ---- leg 3: mem_top --json strict parse (in-process: same
    # interpreter, no second jax import)
    spec = importlib.util.spec_from_file_location(
        "mem_top", os.path.join(repo_root, "tools", "mem_top.py"))
    mem_top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mem_top)
    buf = _io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = mem_top.main(["--model", "mlp", "--mesh", "data=8",
                           "--opt-slots", "2", "--budget", "1000000",
                           "--json"])
    if rc != 1:
        problems.append("mem_top over-budget run: expected exit 1, "
                        "got %d" % rc)
    try:
        doc = json.loads(buf.getvalue())
    except ValueError as exc:
        problems.append("mem_top --json unparseable: %s" % exc)
    else:
        if doc.get("schema") != "mxtpu-memtop/1":
            problems.append("mem_top schema drift: %r"
                            % doc.get("schema"))
        kinds = {r.get("kind") for r in doc.get("advice", [])}
        if "remat" not in kinds:
            problems.append("mem_top advice: no remat candidate")
        if "zero" not in kinds:
            problems.append("mem_top advice: no ZeRO record")
        if not doc.get("over_budget"):
            problems.append("mem_top: over_budget flag not set")
    return problems


def serving_check(repo_root=_ROOT):
    """Serving gate (stage 18, docs/api/serving.md).

    One ``tools/launch.py --fleet -n 1`` replica serves the tiny zoo
    MLP behind a 1,4 batch ladder on an ephemeral port.  The gate
    drives it through the whole serving contract:

    * a 6-wide concurrent burst must land entirely as 200s AND coalesce
      into the rung-4 executable (``mxtpu_serve_rung_dispatch_total
      {rung="4"}`` > 0 — the continuous batcher worked);
    * a 24-wide burst under a 1 ms deadline must SHED early at submit
      (503s with a ``shed`` reason / ``mxtpu_serve_shed_total`` > 0 —
      the estimated rung wall cannot meet the deadline) while the ok
      counter keeps growing — load is refused, not queued to death;
    * ``tools/serve_top.py --json`` over the replica's ``/metrics``
      must strict-parse as ``mxtpu-servetop/3`` and name a hot rung;
    * SIGKILLing the replica's process group (exit rc -9, the rc-137
      container-kill shape) must produce the fleet watchdog's
      ``replica_restart`` supervisor event and a green ``/healthz``
      under a NEW pid, peers-keep-serving semantics — in-flight
      requests on the dead replica fail fast at the client.

    Returns problem strings (empty = clean)."""
    import json
    import shutil
    import signal
    import socket
    import subprocess
    import tempfile
    import threading
    import time
    import urllib.error
    import urllib.request

    problems = []
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_serving_gate_")
    jsonl = os.path.join(tmpdir, "sup.jsonl")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    launcher = os.path.join(repo_root, "tools", "launch.py")
    env = _scrubbed_launch_env({"MXNET_TPU_TELEMETRY_JSONL": jsonl})
    sup = None

    def get(path, timeout=5):
        with urllib.request.urlopen(
                "http://127.0.0.1:%d%s" % (port, path),
                timeout=timeout) as r:
            return r.status, r.read()

    def post(rows, deadline_ms, out):
        doc = {"data": [[0.5] * 16] * rows, "deadline_ms": deadline_ms}
        req = urllib.request.Request(
            "http://127.0.0.1:%d/predict" % port,
            data=json.dumps(doc).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                out.append((r.status, json.loads(r.read())))
        except urllib.error.HTTPError as e:
            out.append((e.code, json.loads(e.read())))
        except OSError as e:
            out.append((-1, {"error": str(e)}))

    def burst(n, deadline_ms):
        out = []
        threads = [threading.Thread(target=post,
                                    args=(1, deadline_ms, out))
                   for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return out

    try:
        sup = subprocess.Popen(
            [sys.executable, launcher, "--fleet", "-n", "1",
             "--restart-budget", "2",
             "%s -m mxnet_tpu.serving --model mlp --data-shape 16 "
             "--port %d --ladder 1,4 --window-ms 20 --queue-depth 8 "
             "--deadline-ms 2000" % (sys.executable, port)],
            env=env, cwd=repo_root,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

        deadline = time.time() + 180
        up = False
        while time.time() < deadline:
            if sup.poll() is not None:
                problems.append("fleet supervisor exited early "
                                "(code %s)" % sup.returncode)
                return problems
            try:
                if get("/healthz")[0] == 200:
                    up = True
                    break
            except OSError:
                time.sleep(0.5)
        if not up:
            problems.append("replica /healthz never answered 200")
            return problems

        # coalescing: 6 concurrent 1-row posts against a 20 ms window
        res = burst(6, 2000.0)
        bad = [r for r in res if r[0] != 200]
        if bad:
            problems.append("coalescing burst had non-200 replies: %r"
                            % bad[:3])
        text = get("/metrics")[1].decode()
        if 'mxtpu_serve_rung_dispatch_total{rung="4"}' not in text:
            problems.append("concurrent burst never coalesced into "
                            "rung 4 (no rung-4 dispatch counter)")

        # shedding: 24-wide burst, 1 ms deadline, depth-4 queue
        res = burst(24, 1.0)
        shed = [doc for st, doc in res if st == 503 and doc.get("shed")]
        if not shed:
            problems.append("deadline-starved overload shed nothing "
                            "(no 503 with a shed reason)")
        text = get("/metrics")[1].decode()
        if "mxtpu_serve_shed_total" not in text:
            problems.append("mxtpu_serve_shed_total not exported after "
                            "the overload burst")
        if 'mxtpu_serve_requests_total{outcome="ok"}' not in text:
            problems.append("no ok-outcome requests recorded")

        # serve_top contract
        top = subprocess.run(
            [sys.executable, os.path.join(repo_root, "tools",
                                          "serve_top.py"),
             "--url", "http://127.0.0.1:%d/metrics" % port, "--json"],
            capture_output=True, text=True, env=env, timeout=60)
        if top.returncode != 0:
            problems.append("serve_top --json exited %d: %s"
                            % (top.returncode, top.stderr[:200]))
        else:
            try:
                doc = json.loads(top.stdout)
            except ValueError as e:
                problems.append("serve_top --json unparseable: %s" % e)
                doc = {}
            if doc.get("schema") != "mxtpu-servetop/3":
                problems.append("serve_top schema %r != mxtpu-servetop/3"
                                % doc.get("schema"))
            if not doc.get("hot_rung"):
                problems.append("serve_top named no hot rung")
            if doc.get("sheds") == {}:
                problems.append("serve_top saw no sheds after the "
                                "overload burst")

        # chaos: SIGKILL the replica's process group (rc -9 — the
        # rc-137 shape); the fleet watchdog must restart IT alone and
        # /healthz must come back green under a new pid
        old_pid = None
        with open(jsonl) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("event") == "worker_start":
                    old_pid = rec["pid"]
        if old_pid is None:
            problems.append("no worker_start event in the supervisor "
                            "timeline")
            return problems
        try:
            os.killpg(os.getpgid(old_pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError) as e:
            problems.append("cannot SIGKILL replica pid %d: %s"
                            % (old_pid, e))
            return problems
        deadline = time.time() + 120
        back = False
        while time.time() < deadline:
            try:
                st, body = get("/healthz", timeout=3)
                if st == 200 and json.loads(body)["pid"] != old_pid:
                    back = True
                    break
            except OSError:
                pass
            time.sleep(0.5)
        if not back:
            problems.append("killed replica never came back green "
                            "under a new pid")
        events = []
        with open(jsonl) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("event") == "replica_restart":
                    events.append(rec)
        if not events:
            problems.append("no replica_restart event in the "
                            "supervisor timeline after the kill")
        elif events[0].get("exit_code") != -signal.SIGKILL:
            problems.append("replica_restart recorded exit_code %r, "
                            "expected %d (SIGKILL)"
                            % (events[0].get("exit_code"),
                               -signal.SIGKILL))
    finally:
        if sup is not None:
            sup.send_signal(signal.SIGTERM)
            try:
                sup.wait(20)
            except subprocess.TimeoutExpired:
                sup.kill()
        shutil.rmtree(tmpdir, ignore_errors=True)
    return problems


def slo_check(repo_root=_ROOT):
    """SLO gate (stage 19, docs/api/telemetry.md).

    Two legs over the healthd engine (``telemetry.slo``):

    * **replica leg** — one serving replica with the shed burn-rate
      windows shrunk to seconds (``MXNET_TPU_SLO_RULES`` compact
      grammar, ``MXNET_TPU_SLO_TICK_S=0.2``).  A deadline-starved shed
      storm must take ``serve_shed_burn`` to **firing** (both burn
      windows over the factor), flip ``/healthz?deep=1`` to
      503/critical, and surface through ``/alerts``,
      ``tools/health_top.py --json`` (exit 1, naming
      ``serve_shed_burn``) and ``tools/serve_top.py --json``
      (``health``/``firing_rules``).  With the storm stopped and good
      traffic flowing the alert must **resolve** and deep healthz
      return 200 — the full lifecycle, not a latched flag;
    * **fleet leg** — a 2-process dry-run with seeded cross-rank skew
      and ``fleet_skew.bound`` lowered under it must write a
      fleet-scope ``alert`` event into the run timeline, which
      ``tools/health_top.py --run --json`` replays naming
      ``fleet_skew`` as first-fired and ``tools/run_top.py
      --summarize --json`` rolls up under ``health``.

    Returns problem strings (empty = clean)."""
    import json
    import shutil
    import signal
    import socket
    import subprocess
    import tempfile
    import threading
    import time
    import urllib.error
    import urllib.request

    problems = []
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_slo_gate_")
    jsonl = os.path.join(tmpdir, "sup.jsonl")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    launcher = os.path.join(repo_root, "tools", "launch.py")
    env = _scrubbed_launch_env({
        "MXNET_TPU_TELEMETRY_JSONL": jsonl,
        "MXNET_TPU_SLO_TICK_S": "0.2",
        # seconds-scale burn windows so the gate sees fire AND resolve
        "MXNET_TPU_SLO_RULES":
            "serve_shed_burn.fast_s=2;serve_shed_burn.slow_s=5;"
            "serve_shed_burn.resolve_for_s=2",
    })
    sup = None

    def get(path, timeout=5):
        try:
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d%s" % (port, path),
                    timeout=timeout) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def post(rows, deadline_ms, out):
        doc = {"data": [[0.5] * 16] * rows, "deadline_ms": deadline_ms}
        req = urllib.request.Request(
            "http://127.0.0.1:%d/predict" % port,
            data=json.dumps(doc).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                out.append((r.status, json.loads(r.read())))
        except urllib.error.HTTPError as e:
            out.append((e.code, json.loads(e.read())))
        except OSError as e:
            out.append((-1, {"error": str(e)}))

    def burst(n, deadline_ms):
        out = []
        threads = [threading.Thread(target=post,
                                    args=(1, deadline_ms, out))
                   for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return out

    def tool(name, *args):
        return subprocess.run(
            [sys.executable, os.path.join(repo_root, "tools", name)]
            + list(args), capture_output=True, text=True, env=env,
            timeout=60, cwd=repo_root)

    try:
        sup = subprocess.Popen(
            [sys.executable, launcher, "--fleet", "-n", "1",
             "--restart-budget", "1",
             "%s -m mxnet_tpu.serving --model mlp --data-shape 16 "
             "--port %d --ladder 1,4 --window-ms 20 --queue-depth 8 "
             "--deadline-ms 2000" % (sys.executable, port)],
            env=env, cwd=repo_root,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        deadline = time.time() + 180
        up = False
        while time.time() < deadline:
            if sup.poll() is not None:
                problems.append("fleet supervisor exited early "
                                "(code %s)" % sup.returncode)
                return problems
            try:
                if get("/healthz")[0] == 200:
                    up = True
                    break
            except OSError:
                time.sleep(0.5)
        if not up:
            problems.append("replica /healthz never answered 200")
            return problems

        # shed storm: every request deadline-starved -> the burn on
        # BOTH shrunken windows blows past the factor within ~a tick
        fired = False
        deadline = time.time() + 30
        while time.time() < deadline:
            burst(12, 1.0)
            st, body = get("/healthz?deep=1")
            doc = json.loads(body)
            if st == 503 and doc.get("status") == "critical" and any(
                    f.get("rule") == "serve_shed_burn"
                    for f in (doc.get("health") or {})
                    .get("firing", [])):
                fired = True
                break
            time.sleep(0.3)
        if not fired:
            problems.append("shed storm never took serve_shed_burn to "
                            "firing / deep healthz to 503-critical "
                            "(last: %d %s)" % (st, body[:300]))
            return problems

        st, body = get("/alerts")
        alerts_doc = json.loads(body)
        if alerts_doc.get("schema") != "mxtpu-health/1":
            problems.append("/alerts schema %r != mxtpu-health/1"
                            % alerts_doc.get("schema"))
        if not any(a.get("rule") == "serve_shed_burn"
                   and a.get("state") == "firing"
                   for a in alerts_doc.get("alerts", [])):
            problems.append("/alerts does not show serve_shed_burn "
                            "firing")

        top = tool("health_top.py", "--url",
                   "http://127.0.0.1:%d" % port, "--json")
        if top.returncode != 1:
            problems.append("health_top --json on a critical replica "
                            "exited %d (want 1): %s"
                            % (top.returncode, top.stderr[:200]))
        else:
            doc = json.loads(top.stdout)
            if doc.get("status") != "critical" or not any(
                    f.get("rule") == "serve_shed_burn"
                    for f in doc.get("firing", [])):
                problems.append("health_top --json did not name "
                                "serve_shed_burn critical: %s"
                                % top.stdout[:300])

        top = tool("serve_top.py", "--url",
                   "http://127.0.0.1:%d/metrics" % port, "--json")
        if top.returncode != 0:
            problems.append("serve_top --json exited %d: %s"
                            % (top.returncode, top.stderr[:200]))
        else:
            doc = json.loads(top.stdout)
            if doc.get("health") != "critical":
                problems.append("serve_top health %r != 'critical' "
                                "while the shed alert fires"
                                % doc.get("health"))
            if "serve_shed_burn" not in (doc.get("firing_rules")
                                         or []):
                problems.append("serve_top firing_rules %r misses "
                                "serve_shed_burn"
                                % doc.get("firing_rules"))

        # recovery: good traffic only — the burn windows drain and the
        # alert must RESOLVE (firing -> inactive after resolve_for_s)
        resolved = False
        deadline = time.time() + 60
        while time.time() < deadline:
            burst(2, 2000.0)
            st, body = get("/healthz?deep=1")
            if st == 200 and \
                    json.loads(body).get("status") == "healthy":
                resolved = True
                break
            time.sleep(0.5)
        if not resolved:
            problems.append("serve_shed_burn never resolved after the "
                            "storm stopped (last: %d %s)"
                            % (st, body[:300]))
    finally:
        if sup is not None:
            sup.send_signal(signal.SIGTERM)
            try:
                sup.wait(20)
            except subprocess.TimeoutExpired:
                sup.kill()
        shutil.rmtree(tmpdir, ignore_errors=True)
    if problems:
        return problems

    # ---- fleet leg: seeded skew must fire fleet_skew at the
    # aggregator and land in the timeline as an alert event
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_slo_fleet_")
    base = os.path.join(tmpdir, "run.jsonl")
    env = _scrubbed_launch_env({
        "MXNET_TPU_TELEMETRY_JSONL": base,
        "DISTVIEW_STEPS": "3",
        "DISTVIEW_SLOW_RANK": "1",
        "DISTVIEW_SLOW_S": "0.05",
        "DISTVIEW_BASE_S": "0.01",
        "DISTVIEW_SKEW_S": "0.05",
        "MXNET_TPU_SLO_RULES": "fleet_skew.bound=0.01",
    })
    env["JAX_PLATFORMS"] = "cpu"
    try:
        res = subprocess.run(
            [sys.executable, launcher, "-n", "2",
             "--launcher", "local", "--heartbeat-interval", "0.1",
             sys.executable,
             os.path.join(repo_root, "tests",
                          "dist_distview_worker.py")],
            capture_output=True, text=True, timeout=240,
            cwd=repo_root, env=env)
        if res.returncode != 0:
            problems.append("fleet-leg dry-run failed (%d): %s"
                            % (res.returncode,
                               (res.stdout + res.stderr)[-800:]))
            return problems
        run_path = base + ".run"
        fired = []
        with open(run_path) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("event") == "alert" and \
                        rec.get("scope") == "fleet":
                    fired.append(rec)
        if not any(r.get("rule") == "fleet_skew"
                   and r.get("to") == "firing" for r in fired):
            problems.append("seeded 50 ms skew under a 10 ms bound "
                            "fired no fleet_skew alert event in the "
                            "timeline (alert events: %r)" % fired[:3])
            return problems
        top = tool("health_top.py", "--run", run_path, "--json")
        if top.returncode not in (0, 1):
            problems.append("health_top --run exited %d: %s"
                            % (top.returncode, top.stderr[:200]))
        else:
            doc = json.loads(top.stdout)
            if (doc.get("first_fired") or {}).get("rule") != \
                    "fleet_skew":
                problems.append("health_top --run first_fired %r != "
                                "fleet_skew"
                                % doc.get("first_fired"))
        top = tool("run_top.py", run_path, "--summarize", "--json")
        if top.returncode != 0:
            problems.append("run_top --summarize exited %d: %s"
                            % (top.returncode, top.stderr[:200]))
        else:
            summary = json.loads(top.stdout)
            health = summary.get("health") or {}
            if health.get("status") not in ("degraded", "critical"):
                problems.append("run summary health %r does not "
                                "reflect the firing fleet_skew"
                                % health)
            if not summary.get("alerts"):
                problems.append("run summary carries no alerts list")
    except subprocess.TimeoutExpired:
        problems.append("fleet-leg dry-run timed out")
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return problems


def tracing_check(repo_root=_ROOT):
    """Tracing gate (stage 20, docs/api/telemetry.md tracing section).

    Three legs:

    * **flight cross-reference** (in-process): a flight event recorded
      under an active trace carries its ``trace_id``;
      ``tools/flight_read.py`` strict-parses the dump and REFUSES a
      corrupted (non-32-hex) id — the join key between the black box
      and the ``mxtpu-trace/1`` export is load-bearing;
    * **serving leg**: a 1-replica fleet with a seeded 250 ms
      ``serve.dispatch`` delay fault and ``MXNET_TPU_TRACE_DIR`` set
      must return ``X-Trace-Id`` on 200s, shed an explicit
      ``deadline_ms=0`` as a 503 carrying ``rid`` + ``trace_id`` (the
      falsy-deadline regression, end to end), export traces where
      ``trace_top --json`` names ``serve.dispatch`` as the dominant
      critical-path segment, the ``--trace <X-Trace-Id>`` waterfall
      reconstructs queue -> coalesce -> pad -> dispatch(links) ->
      slice with segment coverage >= 95% of the root wall, and
      ``serve_top --json``'s p99 exemplar resolves to an exported
      trace id;
    * **fleet leg**: a 2-process launch with rank 1 seeded slow must
      leave ``trace.merged.jsonl`` whose critical-path aggregate
      names ``step.compute`` dominant AND mostly on rank 1 — the
      straggler named by attribution, not eyeballing.

    Returns problem strings (empty = clean)."""
    import json
    import shutil
    import signal
    import socket
    import subprocess
    import tempfile
    import time
    import urllib.error
    import urllib.request

    problems = []

    def tool(name, *args, timeout=60):
        return subprocess.run(
            [sys.executable, os.path.join(repo_root, "tools", name)]
            + list(args),
            capture_output=True, text=True, timeout=timeout)

    # ---- flight cross-reference leg (in-process)
    fdir = tempfile.mkdtemp(prefix="mxtpu_trace_flight_")
    prev_sample = os.environ.pop("MXNET_TPU_TRACE_SAMPLE", None)
    try:
        from mxnet_tpu.telemetry import flight, tracing
        with tracing.start_trace("ci.traced") as tr:
            flight.record("step_begin", step=1)
        dump_path = flight.dump("ci_trace", directory=fdir)
        if not dump_path:
            problems.append("flight.dump(directory=...) wrote nothing")
            return problems
        res = tool("flight_read.py", dump_path, "--json")
        if res.returncode != 0:
            problems.append("flight_read rejected a well-formed traced "
                            "dump (%d): %s"
                            % (res.returncode, res.stderr[:200]))
        else:
            doc = json.loads(res.stdout)
            if not any(e.get("trace_id") == tr.trace_id
                       for e in doc["events"]):
                problems.append("no flight event carries the active "
                                "trace id %s" % tr.trace_id)
        with open(dump_path) as f:
            doc = json.load(f)
        poisoned = False
        for ev in doc["events"]:
            if ev.get("trace_id"):
                ev["trace_id"] = "NOT-32-HEX"
                poisoned = True
        if not poisoned:
            problems.append("traced dump has no trace_id event to "
                            "corrupt")
        bad = os.path.join(fdir, "flight-bad.json")
        with open(bad, "w") as f:
            json.dump(doc, f)
        res = tool("flight_read.py", bad)
        if res.returncode == 0:
            problems.append("flight_read ACCEPTED a malformed "
                            "trace_id (the cross-reference contract "
                            "is unenforced)")
    finally:
        if prev_sample is not None:
            os.environ["MXNET_TPU_TRACE_SAMPLE"] = prev_sample
        shutil.rmtree(fdir, ignore_errors=True)
    if problems:
        return problems

    # ---- serving leg: seeded slow dispatch, end-to-end trace story
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_tracing_gate_")
    tdir = os.path.join(tmpdir, "traces")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    launcher = os.path.join(repo_root, "tools", "launch.py")
    env = _scrubbed_launch_env({
        "MXNET_TPU_TRACE_DIR": tdir,
        "MXNET_TPU_FAULTS": "serve.dispatch:p=1,kind=delay,delay=0.25",
    })
    sup = None

    def post(doc, timeout=30):
        req = urllib.request.Request(
            "http://127.0.0.1:%d/predict" % port,
            data=json.dumps(doc).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.headers), json.loads(r.read())

    try:
        sup = subprocess.Popen(
            [sys.executable, launcher, "--fleet", "-n", "1",
             "--restart-budget", "1",
             "%s -m mxnet_tpu.serving --model mlp --data-shape 16 "
             "--port %d --ladder 1,4 --window-ms 20 --queue-depth 8 "
             "--deadline-ms 5000" % (sys.executable, port)],
            env=env, cwd=repo_root,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        deadline = time.time() + 180
        up = False
        while time.time() < deadline:
            if sup.poll() is not None:
                problems.append("fleet supervisor exited early "
                                "(code %s)" % sup.returncode)
                return problems
            try:
                with urllib.request.urlopen(
                        "http://127.0.0.1:%d/healthz" % port,
                        timeout=3) as r:
                    if r.status == 200:
                        up = True
                        break
            except OSError:
                time.sleep(0.5)
        if not up:
            problems.append("replica /healthz never answered 200")
            return problems

        # a few traced requests through the 250 ms-delayed dispatch
        tid = None
        for i in range(4):
            st, headers, body = post(
                {"data": [[0.5] * 16], "deadline_ms": 5000})
            if st != 200:
                problems.append("predict %d answered %d" % (i, st))
                return problems
            tid = headers.get("X-Trace-Id")
            if not tid or len(tid) != 32:
                problems.append("200 reply carries no well-formed "
                                "X-Trace-Id (got %r)" % tid)
                return problems
            if not headers.get("traceparent", "").startswith(
                    "00-%s-" % tid):
                problems.append("traceparent response header does not "
                                "match X-Trace-Id")

        # the falsy-deadline regression, end to end: explicit 0 sheds
        # with rid + trace_id in the 503 body
        try:
            post({"data": [[0.5] * 16], "deadline_ms": 0})
            problems.append("explicit deadline_ms=0 was SERVED (the "
                            "falsy-deadline bug is back)")
        except urllib.error.HTTPError as e:
            if e.code != 503:
                problems.append("deadline_ms=0 answered %d, expected "
                                "503" % e.code)
            else:
                body = json.loads(e.read())
                if body.get("shed") != "deadline":
                    problems.append("deadline_ms=0 shed reason %r != "
                                    "'deadline'" % body.get("shed"))
                if not isinstance(body.get("rid"), int):
                    problems.append("503 shed body carries no rid: %r"
                                    % body)
                shed_tid = body.get("trace_id")
                if not shed_tid or len(shed_tid) != 32:
                    problems.append("503 shed body carries no "
                                    "trace_id: %r" % body)
                if e.headers.get("X-Trace-Id") != shed_tid:
                    problems.append("503 X-Trace-Id header disagrees "
                                    "with the body trace_id")

        # exports land as the replica keeps traces; give the last
        # request's finalization a beat
        trace_file = os.path.join(tdir, "trace.rank0.jsonl")
        deadline = time.time() + 20
        while time.time() < deadline:
            try:
                with open(trace_file) as f:
                    if tid in f.read():
                        break
            except OSError:
                pass
            time.sleep(0.25)
        else:
            problems.append("replica never exported trace %s to "
                            "trace.rank0.jsonl under "
                            "MXNET_TPU_TRACE_DIR" % tid)
            return problems

        # critical path: the seeded slow dispatch must be NAMED
        top = tool("trace_top.py", tdir, "--json")
        if top.returncode != 0:
            problems.append("trace_top --json exited %d: %s"
                            % (top.returncode, top.stderr[:200]))
            return problems
        doc = json.loads(top.stdout)
        if doc.get("schema") != "mxtpu-tracetop/1":
            problems.append("trace_top schema %r != mxtpu-tracetop/1"
                            % doc.get("schema"))
        agg = doc.get("critical_path") or {}
        if agg.get("dominant") != "serve.dispatch":
            problems.append("seeded 250 ms dispatch delay: dominant "
                            "segment %r != 'serve.dispatch' "
                            "(segments: %r)"
                            % (agg.get("dominant"),
                               agg.get("segments_ms")))
        if not any(r.get("status") == "shed" for r in doc.get("rows", ())):
            problems.append("the shed request's trace was not kept/"
                            "exported (no shed row in the ranking)")

        # waterfall: the last 200's X-Trace-Id reconstructs the full
        # segment chain with >= 95% coverage and fan-in links
        top = tool("trace_top.py", tdir, "--trace", tid, "--json")
        if top.returncode != 0:
            problems.append("trace_top --trace %s exited %d: %s"
                            % (tid, top.returncode, top.stderr[:200]))
            return problems
        wf = json.loads(top.stdout)
        names = {r["name"] for r in wf.get("spans", ())}
        missing = {"serve.request", "serve.queue", "serve.coalesce",
                   "serve.pad", "serve.dispatch", "serve.slice"} - names
        if missing:
            problems.append("waterfall lacks segment span(s): %s"
                            % sorted(missing))
        if wf.get("coverage", 0.0) < 0.95:
            problems.append("segment coverage %.3f < 0.95 of the root "
                            "wall (segments %.2fms of %.2fms)"
                            % (wf.get("coverage", 0.0),
                               wf.get("segments_ms", 0.0),
                               wf.get("total_ms", 0.0)))
        disp = [r for r in wf.get("spans", ())
                if r["name"] == "serve.dispatch"]
        if not (disp and disp[0].get("links")):
            problems.append("the dispatch span carries no fan-in "
                            "links")

        # p99 exemplar: serve_top must name an actual exported trace
        top = tool("serve_top.py", "--url",
                   "http://127.0.0.1:%d/metrics" % port, "--json")
        if top.returncode != 0:
            problems.append("serve_top --json exited %d: %s"
                            % (top.returncode, top.stderr[:200]))
            return problems
        doc = json.loads(top.stdout)
        ex = (doc.get("latency_ms") or {}).get("p99_exemplar")
        if not ex or len(ex) != 32:
            problems.append("serve_top resolved no p99 exemplar trace "
                            "(latency_ms: %r)" % doc.get("latency_ms"))
        else:
            with open(trace_file) as f:
                if ex not in f.read():
                    problems.append("p99 exemplar %s is not in the "
                                    "exported trace file" % ex)
    finally:
        if sup is not None:
            sup.send_signal(signal.SIGTERM)
            try:
                sup.wait(20)
            except subprocess.TimeoutExpired:
                sup.kill()
        shutil.rmtree(tmpdir, ignore_errors=True)
    if problems:
        return problems

    # ---- fleet leg: 2-proc launch, rank 1 seeded slow; the merged
    # aggregate must name step.compute on rank 1
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_tracing_fleet_")
    tdir = os.path.join(tmpdir, "traces")
    base = os.path.join(tmpdir, "run.jsonl")
    env = _scrubbed_launch_env({
        "MXNET_TPU_TELEMETRY_JSONL": base,
        "MXNET_TPU_TRACE_DIR": tdir,
        "DISTVIEW_STEPS": "3",
        "DISTVIEW_SLOW_RANK": "1",
        "DISTVIEW_SLOW_S": "0.2",
        "DISTVIEW_BASE_S": "0.01",
    })
    try:
        res = subprocess.run(
            [sys.executable, launcher, "-n", "2",
             "--launcher", "local",
             sys.executable,
             os.path.join(repo_root, "tests",
                          "dist_distview_worker.py")],
            capture_output=True, text=True, timeout=240,
            cwd=repo_root, env=env)
        if res.returncode != 0:
            problems.append("fleet-leg dry-run failed (%d): %s"
                            % (res.returncode,
                               (res.stdout + res.stderr)[-800:]))
            return problems
        merged = os.path.join(tdir, "trace.merged.jsonl")
        if not os.path.exists(merged):
            problems.append("launch.py left no trace.merged.jsonl "
                            "(per-rank merge did not run)")
            return problems
        top = tool("trace_top.py", tdir, "--aggregate", "--json")
        if top.returncode != 0:
            problems.append("trace_top --aggregate exited %d: %s"
                            % (top.returncode, top.stderr[:200]))
            return problems
        agg = json.loads(top.stdout)
        if agg.get("dominant") != "step.compute":
            problems.append("seeded slow rank: fleet dominant %r != "
                            "'step.compute' (segments: %r)"
                            % (agg.get("dominant"),
                               agg.get("segments_ms")))
        if agg.get("dominant_rank") != 1:
            problems.append("dominant segment attributed to rank %r, "
                            "expected the seeded-slow rank 1 "
                            "(split: %r)"
                            % (agg.get("dominant_rank"),
                               agg.get("dominant_rank_split_ms")))
    except subprocess.TimeoutExpired:
        problems.append("fleet-leg dry-run timed out")
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return problems


#: the gate: (id, stage function) in run order.  Each function takes
#: the repo root and returns its problem strings; `run`, `main` and
#: tests/test_analysis.py::test_ci_stage all iterate this table.
STAGES = (
    ("mxlint", mxlint_check),
    ("registry", registry_check),
    ("zoo_verify", zoo_verify_check),
    ("telemetry", telemetry_drift),
    ("flight", flight_smoke),
    ("distview", distview_smoke),
    ("fusion", fusion_check),
    ("costdb", costdb_check),
    ("autotune", autotune_check),
    ("reshard", reshard_check),
    ("numerics", numerics_check),
    ("plansearch", plansearch_check),
    ("spmd", spmd_check),
    ("ioview", ioview_check),
    ("overlap", overlap_check),
    ("io_resume", io_resume_check),
    ("memlive", memlive_check),
    ("serving", serving_check),
    ("slo", slo_check),
    ("tracing", tracing_check),
)


def main(argv=None):
    ap = argparse.ArgumentParser(prog="ci_check")
    ap.add_argument("--repo-root", default=_ROOT)
    args = ap.parse_args(argv)
    failures = run(os.path.abspath(args.repo_root))
    if failures:
        print("ci_check: FAILED (%d finding(s))" % len(failures))
        return 1
    print("ci_check: clean")
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
