"""Multi-process dist_sync semantics without a cluster.

Reference: tests/nightly/dist_sync_kvstore.py run under
``tools/launch.py --launcher local`` (dmlc_tracker local mode) — the
reference's way of proving multi-node sync semantics on one machine.
Here 4 CPU processes join one jax.distributed job and the jitted pytree
AllReduce must produce identical deterministic sums on every worker.
"""
import os
import socket
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch(worker, n=4, timeout=280, extra_env=None, extra_args=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # one device per process: drop the conftest's 8-device virtual flag
    # (workers wanting several devices per process set their own count
    # via FUSED_DEVS_PER_PROC)
    env.pop("XLA_FLAGS", None)
    env.update(extra_env or {})
    env.pop("MXNET_TPU_NUM_PROCESSES", None)
    env.pop("MXNET_TPU_PROCESS_ID", None)
    cmd = [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
           "-n", str(n), "--launcher", "local",
           "--coordinator", "127.0.0.1:%d" % _free_port()]
    cmd += list(extra_args or [])
    cmd += [sys.executable, os.path.join(ROOT, "tests", worker)]
    res = subprocess.run(cmd, capture_output=True, text=True,
                         timeout=timeout, cwd=ROOT, env=env)
    return res, res.stdout + res.stderr


def _require_cpu_multiprocess():
    """Quarantine guard for the collective-requiring dist tests (ISSUE
    15 satellite triage).  Root cause of the standing failures: jax
    0.4.x's CPU backend does not implement cross-process computations
    at all — every collective raises ``INVALID_ARGUMENT: Multiprocess
    computations aren't implemented on the CPU backend`` within
    seconds, deterministically (not a flake; it only ever LOOKED
    windowed because the tier-1 time cap moved around it).  The cached
    2-process probe (below) detects a capable backend, so these tests
    run wherever collectives exist (real TPU pods, newer jax CPU) and
    skip with this documented reason where they cannot."""
    if not _cpu_multiprocess_supported():
        pytest.skip("this jax/CPU backend cannot run cross-process "
                    "collectives (jax 0.4.x: 'Multiprocess "
                    "computations aren't implemented on the CPU "
                    "backend'); deterministic, not a flake — runs on "
                    "collective-capable backends")


@pytest.mark.timeout(300)
def test_dist_sync_4_workers():
    _require_cpu_multiprocess()
    res, out = _launch("dist_sync_worker.py")
    assert res.returncode == 0, out
    for rank in range(4):
        assert "worker %d/4 OK" % rank in out, out


def _fused_losses(out, rank=0):
    import json
    for line in out.splitlines():
        tag = "fused-dist worker %d/" % rank
        if tag in line and "losses=" in line:
            # both ranks' prints may interleave on one line: decode the
            # first JSON value and ignore trailing bytes
            payload = line.split("losses=", 1)[1]
            val, _end = json.JSONDecoder().raw_decode(payload)
            return val
    raise AssertionError("no losses line for rank %d in:\n%s" % (rank, out))


@pytest.mark.timeout(900)
def test_dist_fused_trainer_multihost_parity(tmp_path):
    """VERDICT r3 #1: the fused performance path composed with
    multi-host.  ShardedTrainer runs over a PROCESS-SPANNING (data x
    model) mesh — 2 processes x 2 virtual CPU devices — with per-process
    data shards, cross-process gradient psum, tensor-parallel weights
    whose checkpoint gather crosses processes, and a mid-run rank-0
    checkpoint that a fresh trainer resumes to identical losses (the
    resume leg runs inside the worker).  Step-for-step loss parity is
    asserted against the SAME global mesh in a single process."""
    _require_cpu_multiprocess()
    env1 = {"FUSED_DEVS_PER_PROC": "4",
            "FUSED_CKPT_PREFIX": str(tmp_path / "sp")}
    res1, out1 = _launch("dist_fused_worker.py", n=1, timeout=400,
                         extra_env=env1)
    assert res1.returncode == 0, out1
    ref = _fused_losses(out1)

    env2 = {"FUSED_DEVS_PER_PROC": "2",
            "FUSED_CKPT_PREFIX": str(tmp_path / "mp")}
    res2, out2 = _launch("dist_fused_worker.py", n=2, timeout=400,
                         extra_env=env2)
    assert res2.returncode == 0, out2
    for rank in range(2):
        assert "fused-dist worker %d/2 OK" % rank in out2, out2

    multi = _fused_losses(out2)
    # identical global program over an identical global mesh; only the
    # cross-process reduce order may differ
    import numpy as np
    np.testing.assert_allclose(multi, ref, rtol=1e-4)


@pytest.mark.timeout(900)
def test_dist_kill_worker_recovery(tmp_path):
    """VERDICT r3 #5 (reference kvstore_dist.h:39-80 heartbeat role):
    a 2-process fused-path job checkpoints every 3 steps; one rank
    SIGKILLs itself mid-run — the launcher must fail the whole job
    fast with a clear error (surviving ranks would block on the dead
    rank's collectives) — then a fresh job resumes every rank from the
    last complete checkpoint and trains to the loss threshold."""
    _require_cpu_multiprocess()
    env = {"RECOVERY_MODE": "crash",
           "RECOVERY_CKPT": str(tmp_path / "rec"),
           "KILL_RANK": "1", "KILL_STEP": "7",
           "MXNET_TPU_HEARTBEAT_TIMEOUT": "10"}
    res, out = _launch("dist_recovery_worker.py", n=2, timeout=400,
                       extra_env=env)
    assert res.returncode != 0, "job must fail when a worker dies:\n" + out
    assert "simulating node failure" in out, out
    assert "aborting job" in out, out
    # the step-6 checkpoint (pre-crash) must be complete on disk
    assert (tmp_path / "rec-0006.params").exists(), out
    assert (tmp_path / "rec-0006.states").exists(), out

    env["RECOVERY_MODE"] = "resume"
    res2, out2 = _launch("dist_recovery_worker.py", n=2, timeout=400,
                         extra_env=env)
    assert res2.returncode == 0, out2
    for rank in range(2):
        assert "recovery worker %d/2 OK mode=resume start=6" % rank \
            in out2, out2


_CPU_MULTIPROC = {}


def _cpu_multiprocess_supported():
    """One cached 2-process probe: can this jax/CPU backend run
    cross-process collectives at all?  (jax 0.4.x CPU cannot — every
    dist test here fails with 'Multiprocess computations aren't
    implemented on the CPU backend'; the probe lets new tests skip in
    seconds instead of burning the tier-1 time budget on doomed
    multi-attempt launches.)"""
    if "ok" not in _CPU_MULTIPROC:
        probe = ("import sys; sys.path.insert(0, %r); "
                 "from mxnet_tpu.parallel import multihost; "
                 "multihost.ensure_initialized(); "
                 "import jax, numpy as np, jax.numpy as jnp; "
                 "from jax.sharding import Mesh, NamedSharding, "
                 "PartitionSpec as P; "
                 "mesh = Mesh(np.array(jax.devices()), ('d',)); "
                 "x = jax.make_array_from_process_local_data("
                 "NamedSharding(mesh, P('d')), np.ones(2, np.float32), "
                 "(4,)); "
                 "print('probe-sum', float(jnp.sum(x)))" % ROOT)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        try:
            res = subprocess.run(
                [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
                 "-n", "2", "--launcher", "local",
                 "--coordinator", "127.0.0.1:%d" % _free_port(),
                 "--", sys.executable, "-c", '"%s"' % probe],
                capture_output=True, text=True, timeout=120,
                cwd=ROOT, env=env)
            _CPU_MULTIPROC["ok"] = res.returncode == 0 and \
                "probe-sum 4.0" in res.stdout
        except subprocess.TimeoutExpired:
            _CPU_MULTIPROC["ok"] = False
    return _CPU_MULTIPROC["ok"]


@pytest.mark.timeout(900)
def test_dist_watchdog_restart_budget(tmp_path):
    """The resilience watchdog path (ISSUE 1): ONE launch.py invocation
    with --restart-budget supervises the whole recovery story.  Rank 1
    SIGKILLs itself at step 7 of the first attempt; the watchdog detects
    the dead rank within a heartbeat interval, tears the group down, and
    relaunches the job, which resumes every rank from the last COMPLETE
    (manifest-verified) checkpoint and trains to the loss threshold —
    exit 0 without any outside intervention."""
    if not _cpu_multiprocess_supported():
        pytest.skip("this jax/CPU backend cannot run cross-process "
                    "collectives (the other dist tests fail the same "
                    "way here); the watchdog path needs a capable "
                    "backend")
    env = {"RECOVERY_MODE": "auto",
           "RECOVERY_CKPT": str(tmp_path / "wd"),
           "KILL_RANK": "1", "KILL_STEP": "7",
           "MXNET_TPU_HEARTBEAT_TIMEOUT": "10"}
    res, out = _launch("dist_recovery_worker.py", n=2, timeout=800,
                       extra_env=env,
                       extra_args=["--restart-budget", "1",
                                   "--heartbeat-interval", "0.1"])
    assert res.returncode == 0, out
    assert "simulating node failure" in out, out
    assert "aborting job" in out, out
    assert "restarting job (attempt 1/1)" in out, out
    assert "job recovered after 1 restart(s)" in out, out
    # the step-6 checkpoint was the resume point on both ranks
    for rank in range(2):
        assert "recovery worker %d/2 OK mode=auto start=6" % rank \
            in out, out
    # the pre-crash checkpoint is manifest-complete on disk
    assert (tmp_path / "wd-0006.params").exists(), out
    assert (tmp_path / "wd-0006.manifest.json").exists(), out


@pytest.mark.timeout(900)
def test_dist_elastic_rank_leave_and_rejoin(tmp_path):
    """ISSUE 10 acceptance (ROADMAP item 5): elastic rank leave/join.

    Leg A: a 2-rank job under ONE ``launch.py --elastic`` invocation;
    rank 1 SIGKILLs itself at step 7 — the watchdog restarts the job at
    the SURVIVING size (1 worker), which reshards the ``{data:2}``
    checkpoint onto its ``{data:1}`` mesh and finishes training.  The
    supervisor's ``mxtpu-run/1`` timeline must carry the
    ``rank_leave``/``elastic_resize`` supervisor events AND the
    worker's ``reshard``/``rank_leave`` JSONL events.

    Leg B: relaunch at the FULL size against the same prefix — both
    ranks resume from the 1-worker checkpoint (``rank_join`` +
    ``reshard`` in the new timeline) and the loss trajectory continues
    to the threshold."""
    if not _cpu_multiprocess_supported():
        pytest.skip("this jax/CPU backend cannot run cross-process "
                    "collectives (the other dist tests fail the same "
                    "way here); the elastic path needs a capable "
                    "backend")
    import json

    def timeline_events(base):
        evs = []
        try:
            with open(base + ".run") as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if rec.get("kind") == "event":
                        evs.append(rec)
        except OSError:
            pass
        return evs

    base_a = str(tmp_path / "legA.jsonl")
    env = {"ELASTIC_PHASE": "kill",
           "ELASTIC_CKPT": str(tmp_path / "el"),
           "KILL_RANK": "1", "KILL_STEP": "7",
           "MXNET_TPU_HEARTBEAT_TIMEOUT": "10",
           "MXNET_TPU_TELEMETRY_JSONL": base_a}
    res, out = _launch("dist_elastic_worker.py", n=2, timeout=800,
                       extra_env=env,
                       extra_args=["--elastic", "--restart-budget", "1",
                                   "--heartbeat-interval", "0.1"])
    assert res.returncode == 0, out
    assert "simulating rank leave" in out, out
    assert "elastic resize 2 -> 1 worker(s)" in out, out
    # the survivor finished ALONE, resumed from the step-6 checkpoint
    assert "elastic worker 0/1 OK phase=kill start=6" in out, out
    evs = timeline_events(base_a)
    names = [e.get("event") for e in evs]
    assert "rank_leave" in names and "elastic_resize" in names, evs
    # the resumed worker's reshard ({data:2} -> {data:1}) passed
    # through its JSONL stream into the timeline
    resh = [e for e in evs if e.get("event") == "reshard"]
    assert resh and resh[-1]["dst"] == "{1}", evs
    assert (tmp_path / "el-0012.params").exists(), out

    # ---- leg B: re-add the rank (relaunch at the full size)
    base_b = str(tmp_path / "legB.jsonl")
    env2 = dict(env, ELASTIC_PHASE="rejoin",
                MXNET_TPU_TELEMETRY_JSONL=base_b)
    res2, out2 = _launch("dist_elastic_worker.py", n=2, timeout=800,
                         extra_env=env2,
                         extra_args=["--heartbeat-interval", "0.1"])
    assert res2.returncode == 0, out2
    for rank in range(2):
        assert "elastic worker %d/2 OK phase=rejoin start=12" % rank \
            in out2, out2
    evs2 = timeline_events(base_b)
    names2 = [e.get("event") for e in evs2]
    assert "rank_join" in names2, evs2
    assert any(e.get("event") == "reshard" for e in evs2), evs2
    # loss trajectory continued: the rejoined fleet's losses start far
    # below a from-scratch first step (~2.3 for 10 classes) and end
    # under the convergence threshold the worker asserts
    line = next(l for l in out2.splitlines()
                if "elastic worker 0/2 OK" in l and "losses=" in l)
    # both ranks' prints may interleave: decode the first JSON value
    losses, _end = json.JSONDecoder().raw_decode(
        line.split("losses=", 1)[1])
    assert losses and losses[0] < 1.0, losses


@pytest.mark.timeout(600)
def test_dist_async_parameter_server_dcasgd():
    """VERDICT r3 #8: true dist_async.  3 workers train through
    Module.fit with the host-driven parameter server
    (parallel/async_kvstore.py) and SERVER-side DCASGD; the server's
    update counter proves per-push application (the reference
    kvstore_dist_server.h:200-208 contract) and every worker converges
    despite gradient staleness."""
    res, out = _launch("dist_async_worker.py", n=3, timeout=560,
                       extra_env={"MXNET_TPU_NUM_SERVERS": "2"})
    assert res.returncode == 0, out
    for rank in range(3):
        assert "dist-async worker %d/3 OK" % rank in out, out
    assert "async server stats" in out, out


@pytest.mark.timeout(600)
def test_dist_kvstore_bigkey_sharding_4w2s():
    """VERDICT r4 #5: the reference nightly's big-key pattern at 4
    workers x 2 servers.  A key above MXNET_KVSTORE_BIGARRAY_BOUND is
    sliced into per-server flat ranges (kvstore_dist.h:273-314
    EncodeKey role): pulls reassemble byte-exactly, server-side SGD
    updates land on BOTH servers' shards, and small keys hash across
    servers instead of funneling through rank 0."""
    res, out = _launch("dist_bigkey_worker.py", n=4, timeout=560,
                       extra_env={"MXNET_TPU_NUM_SERVERS": "2"})
    assert res.returncode == 0, out
    for rank in range(4):
        assert "bigkey worker %d/4 OK" % rank in out, out


@pytest.mark.timeout(600)
def test_dist_distview_straggler_attribution(tmp_path):
    """ISSUE 5 acceptance: a 2-process run with an injected slow rank.
    Each rank runs the telemetry-only distview worker (no collectives
    needed — rank 1 sleeps DISTVIEW_SLOW_S extra per step, and the
    simulated barrier charges the skew to the fast rank's
    collective_wait); the launch.py supervisor's merged run timeline
    must name rank 1 the straggler, carry the injected skew, attribute
    collective wait to the FAST rank, and every rank must see the
    segment metrics in its own Prometheus rendering and write its own
    .rank<N> step-log stream (the port/JSONL collision fix)."""
    import json

    base = str(tmp_path / "run.jsonl")
    env = {"MXNET_TPU_TELEMETRY_JSONL": base,
           "DISTVIEW_STEPS": "4", "DISTVIEW_SLOW_RANK": "1",
           "DISTVIEW_SLOW_S": "0.12", "DISTVIEW_SKEW_S": "0.05",
           "DISTVIEW_BASE_S": "0.01"}
    res, out = _launch("dist_distview_worker.py", n=2, timeout=280,
                       extra_env=env,
                       extra_args=["--heartbeat-interval", "0.1"])
    assert res.returncode == 0, out
    for rank in range(2):
        # the worker itself asserts mxtpu_step_segment_seconds is in
        # its Prometheus rendering and that its step-log is .rank<N>
        assert "distview worker %d/2 OK" % rank in out, out
        assert os.path.exists(base + ".rank%d" % rank), out

    run_path = base + ".run"
    assert os.path.exists(run_path), out
    res2 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "run_top.py"),
         run_path, "--summarize", "--json"],
        capture_output=True, text=True, timeout=60, cwd=ROOT)
    assert res2.returncode == 0, res2.stdout + res2.stderr
    summary = json.loads(res2.stdout)
    assert summary["straggler"] == 1, summary
    assert summary["steps"] >= 4, summary
    assert summary["num_ranks"] == 2, summary
    # mxtpu_rank_step_skew_seconds reflects the injected delay
    assert summary["skew_max_s"] == pytest.approx(0.05), summary
    seg0 = summary["per_rank"]["0"]["segments_s"]
    seg1 = summary["per_rank"]["1"]["segments_s"]
    # collective wait is attributed to the FAST rank, not the straggler
    assert seg0["collective_wait"] == pytest.approx(0.2, rel=0.25), \
        summary
    assert seg1["collective_wait"] == pytest.approx(0.0), summary
    # the injected delay shows up as the straggler's compute segment
    assert seg1["compute"] > seg0["compute"] + 0.3, summary


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_dist_distview_sigusr1_live_capture(tmp_path):
    """ISSUE 5 acceptance: SIGUSR1 on a live worker produces a bounded
    profiler trace window plus a flight snapshot WITHOUT interrupting
    training.  A 2-rank job runs its steps then holds; mid-hold,
    ``tools/launch.py --capture`` broadcasts SIGUSR1 via the supervisor
    JSONL's worker pids; the job must still exit 0 with every rank OK,
    and each rank must leave a flight-*-capture.json (whose ring holds
    the completed steps) plus an xplane trace under its capture dir."""
    import json
    import time

    base = str(tmp_path / "run.jsonl")
    capdir = str(tmp_path / "capture")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_TPU_NUM_PROCESSES", None)
    env.pop("MXNET_TPU_PROCESS_ID", None)
    env.update({"MXNET_TPU_TELEMETRY_JSONL": base,
                "MXNET_TPU_CAPTURE_DIR": capdir,
                "MXNET_TPU_CAPTURE_SECONDS": "1",
                "DISTVIEW_STEPS": "3", "DISTVIEW_BASE_S": "0.02",
                "DISTVIEW_SLOW_RANK": "-1",
                "DISTVIEW_HOLD_S": "60"})
    cmd = [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
           "-n", "2", "--launcher", "local",
           "--heartbeat-interval", "0.2",
           sys.executable,
           os.path.join(ROOT, "tests", "dist_distview_worker.py")]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            cwd=ROOT, env=env)
    try:
        def steps_done():
            for r in (0, 1):
                p = base + ".rank%d" % r
                try:
                    with open(p) as f:
                        if sum(1 for _ in f) < 3:
                            return False
                except OSError:
                    return False
            return True

        deadline = time.time() + 180
        while not steps_done() and time.time() < deadline:
            if proc.poll() is not None:
                break
            time.sleep(0.5)
        assert proc.poll() is None and steps_done(), \
            "workers never reached steady state:\n" + \
            (proc.communicate()[0] if proc.poll() is not None else "")

        res = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
             "--capture", "--jsonl", base],
            capture_output=True, text=True, timeout=60, cwd=ROOT,
            env=env)
        assert res.returncode == 0, res.stdout + res.stderr
        assert "signaled" in res.stdout, res.stdout

        out, _ = proc.communicate(timeout=400)
    except BaseException:
        proc.kill()
        raise
    # training was not interrupted: clean exit, every rank OK
    assert proc.returncode == 0, out
    for rank in range(2):
        assert "distview worker %d/2 OK" % rank in out, out
        rdir = os.path.join(capdir, "rank%d" % rank)
        snaps = [f for f in os.listdir(rdir)
                 if f.startswith("flight-") and
                 f.endswith("-capture.json")]
        assert snaps, "no flight snapshot for rank %d:\n%s" % (rank, out)
        doc = json.load(open(os.path.join(rdir, snaps[0])))
        assert doc["schema"] == "mxtpu-flight/1", doc
        assert doc["rank"] == rank, doc
        kinds = [e.get("kind") for e in doc["events"]]
        assert "capture" in kinds, kinds
        # the ring snapshot carries the steps that already ran
        assert kinds.count("step_end") >= 3, kinds
        import glob as _glob
        planes = _glob.glob(os.path.join(rdir, "**", "*.xplane.pb"),
                            recursive=True)
        assert planes, "no trace window for rank %d:\n%s" % (rank, out)


@pytest.mark.timeout(1500)
def test_dist_overlap_bitparity_and_collective_wait(tmp_path):
    """ISSUE 15 acceptance (ROADMAP item 4): the 2-process overlap A/B.
    ``tools/overlap_ab.py`` trains the same Module twice under
    ``launch.py`` with a seeded slow rank — overlap off (per-key
    barrier-then-allreduce, the retired DistKVStore.push shape) vs on
    (the bucketed ``push_bucketed``/``drain`` branch through the real
    ``parallel.overlap.BucketQueue``).  Gates: final params of BOTH
    ranks bit-identical across the modes; the on leg's ``overlap``
    bucket flight events parseable by flight_read, the same whole
    number a step on both ranks.  The fast rank's
    ``mxtpu_collective_wait_seconds`` total and ``collective_wait``
    share are two wall times on a machine the other workers share:
    printed, not compared.  The
    transport is the filesystem allreduce (no jax cross-process
    collectives needed — this runs on every backend, unlike the
    probe-guarded tests above)."""
    import json
    import subprocess

    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "overlap_ab.py"),
         "--json", "--workdir", str(tmp_path)],
        capture_output=True, text=True, timeout=1300, cwd=ROOT)
    out = res.stdout + res.stderr
    assert res.returncode == 0, out
    doc = json.loads(res.stdout.strip().splitlines()[-1])
    assert doc["schema"] == "mxtpu-overlap-ab/1", doc
    assert doc["pass"] is True, doc
    assert doc["params_bit_identical"] is True, doc
    assert doc["params_by_rank"] == {"rank0": True, "rank1": True}, doc
    assert doc["overlap_flight_events"] > 0, doc
    buckets = doc["overlap_buckets_by_rank"]
    assert sorted(buckets) == ["rank0", "rank1"], doc
    assert buckets["rank0"] == buckets["rank1"] > 0, doc
    assert buckets["rank0"] % doc["steps"] == 0, doc
    print("fast rank's collective wait: off %(off)s, on %(on)s" % doc)


@pytest.mark.timeout(600)
def test_dist_train_convergence_identical_replicas():
    """Reference tests/nightly/dist_lenet.py equivalent: 4 processes
    train the MLP to >0.9 accuracy with dist_sync gradient allreduce,
    each on its own data shard, and every rank proves zero cross-rank
    parameter variance (identical replicas) through the kvstore."""
    _require_cpu_multiprocess()
    res, out = _launch("dist_train_worker.py", timeout=560)
    assert res.returncode == 0, out
    for rank in range(4):
        assert "dist-train worker %d/4 OK" % rank in out, out
