"""Benchmark: ResNet-50 training throughput (images/sec/chip).

Mirrors the reference's headline number (`docs/how_to/perf.md:161-193`,
ResNet-50 train_imagenet.py batch 32).  Baseline for vs_baseline: 45.52
img/s on 1x K80 (the reference's own published p2.xlarge number,
BASELINE.md).  Runs the fused pjit train step (mxnet_tpu.parallel.
ShardedTrainer) on all available local devices.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"telemetry"} — the ``telemetry`` block is
``mxnet_tpu.telemetry.report()`` (step-time p50/p90/p99, samples/sec,
compile count/time, per-phase span breakdown), the standardized fields
the BENCH trajectory tracks across rounds.

``--dry-run`` (or BENCH_DRYRUN=1) swaps in a tiny MLP and a handful of
steps so the full pipeline — trainer, telemetry, report — is exercised
in seconds on any backend, under a metric name of its own.  Without it
the bench measures the chip: on any platform other than ``tpu`` it
exits non-zero and prints no result.

``BENCH_FUSE_BLOCKS`` (default on) routes the trainer through the
block-granularity fusion pass (docs/api/fusion.md); the BENCH JSON
carries the plan summary (blocks fused, relayouts eliminated) in a
``fusion`` block, and ``--dry-run`` additionally times an unfused A/B
leg with per-leg step-program sizes (top-level jaxpr equations — each
fused block collapses its chain into ONE custom-vjp call).

The JSON also carries an ``io`` block (telemetry.ioview: per-stage
input-pipeline seconds/items/bytes + the bottleneck verdict — empty on
synthetic-batch runs), a ``costdb`` roll-up (telemetry.costdb: measured
per-program wall/MFU + the worst-MFU fused blocks with their roofline
bound; set ``MXNET_TPU_COSTDB`` to persist the full record set), an
``autotune`` block (tuning-cache mode + hit/miss counts + the tuned
block configs actually dispatched, so a trajectory win is attributable
to tuning — ``MXNET_TPU_TUNE_CACHE`` arms the cache) and a
``valid`` flag (``tools/bench_diff.py`` and the trajectory plots skip
runs that carry ``false`` instead of reading their 0 as a 100%
regression).

``BENCH_OVERLAP_AB=1`` additionally embeds an ``overlap`` block in the
dry-run artifact: the 2-process bucketed-overlap on/off A/B
(``tools/overlap_ab.py`` — fast rank's collective wait + segment share
with overlap on vs off at bit-identical final params, ROADMAP item 4;
docs/api/overlap.md).  Its workers are CPU processes whatever holds
this one (``"platform": "cpu"`` in the block).

``--serve`` (or BENCH_SERVE=1) runs the serving-tier closed-loop load
test instead of the training bench: an in-process batch-ladder replica
driven by closed-loop HTTP clients plus a deadline-starved burst; the
artifact's ``serving`` block carries p50/p99 latency, shed rate, rung
occupancy, and ``compiles_after_warmup`` (asserted 0 — the request
path never compiles; docs/api/serving.md).  BENCH_SERVE_FLEET=1 adds
the 2-replica kill/restart leg under ``tools/launch.py --fleet``; the
replicas are pinned to the CPU (``"replica_platform": "cpu"``), since
a chip belongs to one process and this one may hold it.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_IMG_S = 45.52  # reference ResNet-50 train, 1x K80, batch 32


def _cpu_child_env():
    """Environment for a child that runs JAX: pinned to the CPU.  A
    chip belongs to one process at a time, and this one may hold it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def main():
    if "--serve" in sys.argv[1:] or \
            os.environ.get("BENCH_SERVE", "0") == "1":
        return _serve_bench()

    dry_run = "--dry-run" in sys.argv[1:] or \
        os.environ.get("BENCH_DRYRUN", "0") == "1"

    import jax
    from mxnet_tpu import models
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh

    devices = jax.devices()
    n_dev = len(devices)
    platform = devices[0].platform
    if not dry_run and platform != "tpu":
        sys.exit("bench.py measures the chip: jax found platform %r, not "
                 "'tpu' (use --dry-run for the CPU control-flow check)"
                 % platform)

    fuse_blocks = os.environ.get("BENCH_FUSE_BLOCKS", "1") == "1"

    if dry_run:
        # tiny MLP, a handful of real optimizer steps: exercises the
        # trainer + telemetry + report pipeline end-to-end in seconds
        batch = 8 * n_dev
        mesh = build_mesh(tp=1)
        rng = np.random.RandomState(0)
        host_batch = {
            "data": rng.uniform(-1, 1, (batch, 64)).astype(np.float32),
            "softmax_label":
                rng.randint(0, 10, batch).astype(np.float32)}

        def _mk(fuse):
            return ShardedTrainer(
                models.get_model("mlp", num_classes=10), mesh,
                data_shapes={"data": (batch, 64)},
                label_shapes={"softmax_label": (batch,)},
                optimizer="sgd", learning_rate=0.1, dtype="float32",
                fuse_blocks=fuse)

        steps = 5
        fusion_info = {"enabled": fuse_blocks}
        if fuse_blocks:
            # unfused A/B leg FIRST so the primary leg below owns the
            # telemetry step window (reset_steps) and the plan snapshot
            t_b = _mk(False)
            b_dict = t_b.put_batch(host_batch)
            float(t_b.step(b_dict))  # compile
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = t_b.step(b_dict)
            assert np.isfinite(float(loss))
            dt_b = time.perf_counter() - t0
            fusion_info["ab_unfused"] = {
                "samples_per_sec_per_chip":
                    round(steps * batch / dt_b / n_dev, 2),
                "step_program_eqns": _step_program_eqns(t_b, b_dict),
            }

        trainer = _mk(fuse_blocks)
        batch_dict = trainer.put_batch(host_batch)
        float(trainer.step(batch_dict))  # compile
        # drop the warmup/compile step from the step window so the
        # reported percentiles/throughput cover only the timed loop
        # (compile counters are process-lifetime and survive)
        from mxnet_tpu import telemetry
        telemetry.reset_steps()
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = trainer.step(batch_dict)
        assert np.isfinite(float(loss))
        dt = time.perf_counter() - t0
        fusion_info["summary"] = trainer.fusion_summary()
        fusion_info["step_program_eqns"] = _step_program_eqns(
            trainer, batch_dict)
        if fuse_blocks:
            # plan-search A/B (analysis.plansearch): search the whole-
            # graph fusion/layout plan under a tiny budget, measure the
            # searched winner against greedy for real (same step fn,
            # same inputs), commit it to the tuning cache, and embed
            # the searched-vs-greedy step-wall A/B.  A pre-committed
            # entry reports as a pure cache hit (zero search).
            fusion_info["plansearch"] = _plansearch_ab(
                models, batch)
        _emit({
            "metric": "dryrun_mlp_train_samples_per_sec_per_chip",
            "value": round(steps * batch / dt / n_dev, 2),
            "unit": "samples/s/chip",
            "vs_baseline": 0,
        }, fusion=fusion_info, overlap=_overlap_ab())
        return

    # batch 128/chip: the reference benchmarks batch 32 on 12GB GPUs; the
    # TPU has the HBM for 128.  BENCH_BATCH=32 for the literal reference
    # config.
    per_chip_batch = int(os.environ.get("BENCH_BATCH", "128"))
    batch = per_chip_batch * n_dev
    image = int(os.environ.get("BENCH_IMAGE", "224"))
    num_layers = int(os.environ.get("BENCH_LAYERS", "50"))
    steps = int(os.environ.get("BENCH_STEPS", "50"))

    net = models.get_model("resnet%d" % num_layers, num_classes=1000,
                           image_shape="3,%d,%d" % (image, image))
    mesh = build_mesh(tp=1)  # pure data parallel across local chips
    trainer = ShardedTrainer(
        net, mesh,
        data_shapes={"data": (batch, 3, image, image)},
        label_shapes={"softmax_label": (batch,)},
        optimizer=os.environ.get("BENCH_OPTIMIZER", "sgd"),
        learning_rate=0.1, momentum=0.9, weight_decay=1e-4,
        dtype=os.environ.get("BENCH_DTYPE", "bfloat16"),
        layout=os.environ.get("BENCH_LAYOUT", "NHWC"),
        # exact 4x4/s1 space-to-depth rewrite of the 7x7/s2 stem
        # (ops/fused.py; ~+1%, parity-tested)
        stem_space_to_depth=os.environ.get("BENCH_STEM_S2D", "1") == "1",
        # block-granularity fusion + layout planning (analysis.fusion,
        # docs/api/fusion.md); BENCH_FUSE_BLOCKS=0 for the unfused A/B
        fuse_blocks=fuse_blocks)

    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (batch, 3, image, image)).astype(np.float32)
    y = rng.randint(0, 1000, batch).astype(np.float32)
    # stage once: the benchmark measures the train step, not the host
    # link (a real pipeline overlaps transfer via PrefetchingIter)
    batch_dict = trainer.put_batch({"data": x, "softmax_label": y})

    # warmup (compile); float() forces a value fetch
    float(trainer.step(batch_dict))
    float(trainer.step(batch_dict))

    # BENCH_SCAN>1 (default 10): chain that many full optimizer steps
    # inside one device program (ShardedTrainer.run_steps) — removes
    # per-step host dispatch; each inner step is a complete training
    # update (forward+backward+optimizer+aux).  BENCH_SCAN=1 for the
    # per-step dispatch path.
    scan = int(os.environ.get("BENCH_SCAN", "10"))
    from mxnet_tpu import telemetry
    if scan > 1:
        steps = max(scan, (steps // scan) * scan)
        float(np.asarray(trainer.run_steps(batch_dict, scan))[-1])  # compile
        # exclude warmup/compile steps from the reported step window
        telemetry.reset_steps()
        t0 = time.perf_counter()
        for _ in range(steps // scan):
            losses = trainer.run_steps(batch_dict, scan)
        assert np.isfinite(float(np.asarray(losses)[-1]))
        dt = time.perf_counter() - t0
    else:
        telemetry.reset_steps()
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = trainer.step(batch_dict)
        assert np.isfinite(float(loss))  # value fetch closes the chain
        dt = time.perf_counter() - t0

    img_per_sec = steps * batch / dt
    img_per_sec_chip = img_per_sec / n_dev
    _emit({
        "metric": "resnet%d_train_images_per_sec_per_chip" % num_layers,
        "value": round(img_per_sec_chip, 2),
        "unit": "img/s/chip",
        "vs_baseline": round(img_per_sec_chip / BASELINE_IMG_S, 3),
    }, fusion={"enabled": fuse_blocks,
               "summary": trainer.fusion_summary()})


def _serve_bench():
    """``--serve`` (or BENCH_SERVE=1): the serving-tier closed-loop
    load test (docs/api/serving.md).

    Stands up ONE in-process replica — tiny MLP predictor, batch
    ladder AOT-compiled at 1/4/8, continuous batcher, HTTP front door
    on an ephemeral port — then drives it with BENCH_SERVE_CLIENTS
    closed-loop HTTP clients for BENCH_SERVE_SECONDS, follows with a
    32-wide burst under a 1 ms deadline (forcing the load shedder),
    and emits the ``serving`` BENCH block: client-side p50/p99 latency,
    shed rate, per-rung occupancy, the hot rung, and — the AOT
    contract — ``compiles_after_warmup`` (the process-wide backend
    compile counter's delta across the whole load phase, asserted 0
    by ci_check / tests).  BENCH_SERVE_FLEET=1 appends a fleet leg:
    a 2-replica ``tools/launch.py --fleet`` job, rank 0 SIGKILLed
    mid-load, evidence that the peer keeps answering and the watchdog
    restart lands in the supervisor timeline (never raises — failures
    report as an error field, like the overlap leg)."""
    import threading
    import urllib.request
    import urllib.error

    from mxnet_tpu import models, module, predictor, telemetry
    from mxnet_tpu import initializer, context
    from mxnet_tpu.serving import BatchLadder, Batcher, Server

    features = 64
    net = models.get_model("mlp", num_classes=10)
    mod = module.Module(net, context=context.cpu())
    label_names = [n for n in net.list_arguments() if n.endswith("label")]
    mod.bind(data_shapes=[("data", (1, features))],
             label_shapes=[(n, (1,)) for n in label_names])
    mod.init_params(initializer.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2.0))
    arg_params, aux_params = mod.get_params()
    params = dict(arg_params)
    params.update(aux_params)
    pred = predictor.Predictor(net.tojson(), params,
                               {"data": (1, features)})

    ladder = BatchLadder(pred, rungs=(1, 4, 8))
    batcher = Batcher(ladder, window_ms=2.0, queue_depth=8,
                      default_deadline_ms=500.0)
    server = Server(ladder, batcher=batcher, port=0).start()
    url = "http://127.0.0.1:%d/predict" % server.port

    compile_counter = telemetry.counter("mxtpu_compile_total")
    compiles_before = compile_counter.get()

    def post(rows, deadline_ms, lat, codes):
        doc = {"data": [[0.1] * features] * rows,
               "deadline_ms": deadline_ms}
        body = json.dumps(doc).encode()
        req = urllib.request.Request(
            url, data=body,
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                r.read()
                codes.append(r.status)
        except urllib.error.HTTPError as e:
            e.read()
            codes.append(e.code)
        except OSError:
            codes.append(-1)
        lat.append(time.perf_counter() - t0)

    # closed loop: each client issues its next request the moment the
    # previous one answers — the arrival rate adapts to service rate
    seconds = float(os.environ.get("BENCH_SERVE_SECONDS", "3"))
    n_clients = int(os.environ.get("BENCH_SERVE_CLIENTS", "8"))
    lat, codes = [], []
    stop_at = time.monotonic() + seconds

    def client():
        while time.monotonic() < stop_at:
            post(1, 400.0, lat, codes)

    threads = [threading.Thread(target=client) for _ in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    # burst: 32 concurrent requests under a 1 ms deadline against a
    # depth-8 queue — the load shedder MUST refuse some of these
    burst_codes = []
    burst = [threading.Thread(target=post,
                              args=(1, 1.0, [], burst_codes))
             for _ in range(32)]
    for t in burst:
        t.start()
    for t in burst:
        t.join()

    compiles_after = compile_counter.get()
    server.close()

    lat_ok = sorted(l for l, c in zip(lat, codes) if c == 200)

    def pct(q):
        if not lat_ok:
            return None
        return round(
            lat_ok[min(len(lat_ok) - 1, int(q * len(lat_ok)))] * 1e3, 3)

    all_codes = codes + burst_codes
    sheds = sum(1 for c in all_codes if c == 503)
    servetop = _servetop_doc()
    serving = {
        "requests": len(all_codes),
        "ok": sum(1 for c in all_codes if c == 200),
        "shed": sheds,
        "shed_rate": round(sheds / len(all_codes), 4)
        if all_codes else 0.0,
        "p50_ms": pct(0.50),
        "p99_ms": pct(0.99),
        "rungs": list(ladder.rungs),
        "hot_rung": servetop.get("hot_rung"),
        "rung_occupancy": servetop.get("rung_occupancy"),
        "dominant_shed_reason": servetop.get("dominant_shed_reason"),
        "health": servetop.get("health"),
        "firing_rules": servetop.get("firing_rules"),
        "compiles_after_warmup": int(compiles_after - compiles_before)
        if telemetry.compile.installed() else None,
        "clients": n_clients,
        "seconds": seconds,
    }
    if os.environ.get("BENCH_SERVE_FLEET", "0") == "1":
        serving["fleet"] = _serve_fleet_leg()
    _emit({
        "metric": "serve_mlp_p99_ms",
        "value": serving["p99_ms"] or 0,
        "unit": "ms",
        "vs_baseline": 0,
    }, serving=serving)


def _servetop_doc():
    """The server-side metric roll-up for the serve bench: render the
    in-process registry and summarize it through tools/serve_top.py
    (loaded by file path — it is a stdlib tool, not a package).  Empty
    dict when either half fails; the bench block then simply lacks the
    server-side fields."""
    try:
        import importlib.util
        from mxnet_tpu import telemetry
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tools", "serve_top.py")
        spec = importlib.util.spec_from_file_location("mxtpu_servetop",
                                                      path)
        st = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(st)
        return st.summarize(st.parse_prom(telemetry.render_prom()))
    except Exception as e:  # mxlint: allow-broad-except(the roll-up is bench evidence, not the benchmark; a failure must not kill the artifact)
        return {"error": str(e)[:200]}


def _serve_fleet_leg():
    """The optional fleet leg (BENCH_SERVE_FLEET=1): a 2-replica
    ``tools/launch.py --fleet`` job on ephemeral ports; rank 0 is
    SIGKILLed once both replicas answer, and the leg reports whether
    the PEER kept serving through the kill and whether the watchdog's
    ``replica_restart`` landed in the supervisor timeline.  The
    replicas run on the CPU (:func:`_cpu_child_env`) and the block says
    so.  Never raises."""
    import signal
    import subprocess
    import tempfile
    import urllib.request

    def healthz(port, timeout=3):
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/healthz" % port,
                timeout=timeout) as r:
            return r.status, json.loads(r.read())

    tmp = tempfile.mkdtemp(prefix="mxtpu_serve_fleet_")
    jsonl = os.path.join(tmp, "sup.jsonl")
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    base_port = s.getsockname()[1]
    s.close()
    env = _cpu_child_env()
    env["MXNET_TPU_TELEMETRY_JSONL"] = jsonl
    here = os.path.dirname(os.path.abspath(__file__))
    sup = None
    try:
        sup = subprocess.Popen(
            [sys.executable, os.path.join(here, "tools", "launch.py"),
             "--fleet", "-n", "2", "--restart-budget", "2",
             "%s -m mxnet_tpu.serving --model mlp --data-shape 64 "
             "--port %d --ladder 1,4 --window-ms 5"
             % (sys.executable, base_port)],
            env=env, cwd=here)
        ports = (base_port, base_port + 1)
        deadline = time.time() + 180
        up = set()
        while time.time() < deadline and len(up) < 2:
            for p in ports:
                try:
                    if healthz(p)[0] == 200:
                        up.add(p)
                except OSError:
                    pass
            time.sleep(0.5)
        if len(up) < 2:
            return {"error": "fleet never became healthy"}
        starts = {}
        with open(jsonl) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("event") == "worker_start":
                    starts[rec["rank"]] = rec["pid"]
        os.killpg(os.getpgid(starts[0]), signal.SIGKILL)
        peer_ok = healthz(ports[1])[0] == 200       # peer still serving
        restarted = False
        deadline = time.time() + 120
        while time.time() < deadline and not restarted:
            try:
                st, doc = healthz(ports[0])
                restarted = st == 200 and doc["pid"] != starts[0]
            except OSError:
                pass
            time.sleep(0.5)
        events = []
        with open(jsonl) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("event") in ("replica_restart",
                                        "worker_death"):
                    events.append(rec["event"])
        return {"replicas": 2, "killed_rank": 0,
                "replica_platform": "cpu",
                "peer_served_through_kill": peer_ok,
                "killed_replica_restarted": restarted,
                "supervisor_events": events}
    except Exception as e:  # mxlint: allow-broad-except(the fleet leg is bench evidence, not the benchmark; a failure must not kill the artifact)
        return {"error": str(e)[:200]}
    finally:
        if sup is not None:
            sup.send_signal(signal.SIGTERM)
            try:
                sup.wait(20)
            except subprocess.TimeoutExpired:
                sup.kill()


def _overlap_ab():
    """The dry-run overlap leg (``BENCH_OVERLAP_AB=1``; off by default
    — it launches two 2-process jobs, which the ci_check dry-run legs
    should not pay twice): ``tools/overlap_ab.py``'s bucketed-overlap
    on/off A/B with a seeded slow rank — the BENCH JSON evidence for
    ROADMAP item 4 (fast rank's collective wait + segment share
    strictly smaller with overlap on, at bit-identical params; see
    docs/api/overlap.md).  The tool and its workers run on the CPU
    (:func:`_cpu_child_env`), and the block says so.  Never raises — a
    failure reports as an error field."""
    if os.environ.get("BENCH_OVERLAP_AB", "0") != "1":
        return None
    import subprocess
    try:
        res = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "overlap_ab.py"), "--json"],
            capture_output=True, text=True, timeout=1300,
            env=_cpu_child_env())
        doc = json.loads(res.stdout.strip().splitlines()[-1])
        doc["exit_code"] = res.returncode
        doc["platform"] = "cpu"
        return doc
    except Exception as e:  # mxlint: allow-broad-except(the overlap leg is bench evidence, not the benchmark; a failure must not kill the artifact)
        return {"error": str(e)[:200]}


def _plansearch_ab(models, batch):
    """The dry-run plan-search leg: tiny-budget whole-graph plan search
    on the dry-run MLP with the searched-vs-greedy predicted AND
    measured step walls — the BENCH JSON A/B evidence for ROADMAP
    item 3 (the committed winner is never worse than greedy on the
    measured run by construction; see analysis.plansearch).  Never
    raises — a search failure reports as an error field."""
    try:
        from mxnet_tpu.analysis import plansearch
        doc = plansearch.search_and_commit(
            models.get_model("mlp", num_classes=10),
            {"data": (batch, 64), "softmax_label": (batch,)},
            layout="NCHW", budget=12, beam=4, topk=2, repeats=2)
        return {k: doc.get(k) for k in (
            "graph", "plan_id", "cached", "searched", "measured",
            "predicted_s", "greedy_predicted_s", "wall_s",
            "greedy_wall_s", "candidates")}
    except Exception as e:  # mxlint: allow-broad-except(the plan-search leg is bench evidence, not the benchmark; a failure must not kill the artifact)
        return {"error": str(e)[:200]}


def _step_program_eqns(trainer, batch_dict):
    """Top-level jaxpr equation count of the trainer's step program:
    the A/B graph-size evidence — every fused block collapses its
    conv/BN/act (or FC/act) chain into ONE custom-vjp call equation.
    None when the step cannot be retraced host-side."""
    import jax
    import jax.numpy as jnp
    try:
        jaxpr = jax.make_jaxpr(trainer._py_step)(
            trainer.params, trainer.opt_state, trainer.aux, batch_dict,
            jax.random.PRNGKey(0), jnp.float32(0.1), jnp.float32(1.0))
        return len(jaxpr.jaxpr.eqns)
    except Exception:  # mxlint: allow-broad-except(dry-run evidence only: whatever keeps the step from being retraced on the host leaves the field None)
        return None


def _emit(result, fusion=None, overlap=None, serving=None):
    """Attach the standardized telemetry report (step-time percentiles,
    throughput, compile count, and the HBM block: static memory plans
    per compiled program + peak live memory_stats — the BENCH
    trajectory fields) plus the block-fusion evidence and the cost-
    database roll-up (worst-MFU blocks + per-program roofline;
    MXNET_TPU_COSTDB additionally persists the full record set), and
    print the one-line JSON artifact."""
    from mxnet_tpu import autotune, telemetry
    from mxnet_tpu.telemetry import costdb
    rep = telemetry.report()
    # a completed measurement is a valid trajectory point
    result["valid"] = True
    if fusion is not None:
        result["fusion"] = fusion
    if overlap is not None:
        # the bucketed-overlap on/off A/B (BENCH_OVERLAP_AB=1,
        # tools/overlap_ab.py) — ROADMAP item 4's trajectory evidence
        result["overlap"] = overlap
    if serving is not None:
        # the serving-tier closed-loop load test (--serve /
        # BENCH_SERVE=1): client p50/p99, shed rate, rung occupancy,
        # and the zero-compile-after-warmup evidence
        result["serving"] = serving
    cost = costdb.summary()
    cost["flushed_to"] = costdb.flush()
    result["costdb"] = cost
    # data-plane evidence (telemetry.ioview): per-stage seconds/items/
    # bytes + the bottleneck verdict — empty stages on synthetic-batch
    # runs, populated when the bench is fed from a real pipeline
    result["io"] = telemetry.ioview.summary()
    # tuning-cache attribution: hit/miss counts plus the identity of
    # every tuned config this run dispatched with, so bench_diff
    # trajectories can attribute a win to tuning (not just see it)
    result["autotune"] = autotune.summary()
    # training-health numerics: sampling cadence, anomaly counts, and
    # the last sampled grad norm — a bench run that tripped a numerics
    # rule is suspect as a trajectory point even if it completed
    result["numerics"] = telemetry.numerics.summary()
    result["telemetry"] = {
        "steps": rep["steps"],
        "step_time_s": rep["step_time_s"],
        "throughput": rep["throughput"],
        "compile": rep["compile"],
        "phases": rep["phases"],
        # perf trajectory tracks HBM next to step time: the plan is the
        # compile-time footprint, "live" the measured bytes_in_use/peak
        # (None on backends without memory_stats, e.g. CPU smoke)
        "memory": rep["memory"],
    }
    print(json.dumps(result))


if __name__ == "__main__":
    from mxnet_tpu.base import use_compile_cache
    use_compile_cache()
    main()
