"""Elastic training: checkpoint resharding, mesh reshape, rank join/leave.

ROADMAP item 5 / ISSUE 10 acceptance: a checkpoint saved on the
multichip dryrun's ``{data:4, model:2}`` mesh must resume BIT-EXACT
(params + aux + optimizer state) on ``{data:2, model:2}``, ``{data:8}``
and single-device meshes; a reshard failure must degrade to the
old-mesh error path; and the whole reshape must be observable
(``mxtpu_reshard_*`` metrics, ``reshard``/``rank_join``/``rank_leave``
flight + JSONL events).  Runs on the conftest's virtual 8-device CPU
mesh.  See docs/api/reshard.md.
"""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.parallel import (ShardedTrainer, build_mesh,  # noqa: E402
                                build_mesh_from_axes, multihost, reshard)

GBATCH = 8


def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, name="fc1", num_hidden=32)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=10)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _make(mesh):
    return ShardedTrainer(
        _mlp(), mesh,
        data_shapes={"data": (GBATCH, 64)},
        label_shapes={"softmax_label": (GBATCH,)},
        learning_rate=0.1, momentum=0.9, seed=1)


def _batch(step=0):
    rng = np.random.RandomState(100 + step)
    return {"data": rng.rand(GBATCH, 64).astype("f"),
            "softmax_label": (rng.randint(0, 10, GBATCH)).astype("f")}


def _gather_all(t):
    out = {k: multihost.gather_to_host(v) for k, v in t.params.items()}
    out.update({"aux:" + k: multihost.gather_to_host(v)
                for k, v in t.aux.items()})
    for k, slots in t.opt_state.items():
        for i, s in enumerate(slots):
            out["slot%d:%s" % (i, k)] = multihost.gather_to_host(s)
    return out


@pytest.fixture(scope="module")
def saved_ckpt(tmp_path_factory):
    """Two steps trained + saved on the {data:4, model:2} mesh, with
    the continued-loss reference for the resume tests."""
    prefix = str(tmp_path_factory.mktemp("reshard") / "job")
    src = _make(build_mesh(tp=2))            # 8 devices: data4 x model2
    assert src.tp_rules, "tp=2 must derive sharded weights"
    for step in range(2):
        src.step(_batch(step))
    src.save_checkpoint(prefix, 2, save_optimizer_states=True)
    ref_state = _gather_all(src)
    cont_losses = [float(src.step(_batch(2 + i))) for i in range(2)]
    return {"prefix": prefix, "state": ref_state,
            "cont_losses": cont_losses}


# ------------------------------------------------------------ rule tables

def test_parse_rules_inline_and_match():
    rules = reshard.parse_rules(
        ".*fc1_weight=model;.*fc2_weight=None,model;.*=")
    assert reshard.first_match(rules, "net_fc1_weight") == ("model",)
    assert reshard.first_match(rules, "fc2_weight") == (None, "model")
    assert reshard.first_match(rules, "anything_else") == ()
    specs = reshard.match_partition_rules(
        rules, {"fc1_weight": (32, 64), "fc2_weight": (10, 32),
                "scalar": (1,)})
    assert specs["fc1_weight"] == ("model",)
    assert specs["scalar"] == ()          # scalars never partition


def test_parse_rules_file_form(tmp_path):
    path = tmp_path / "rules.json"
    path.write_text(json.dumps(
        [[".*_weight", ["model"]], [".*", []]]))
    rules = reshard.parse_rules("@" + str(path))
    assert reshard.first_match(rules, "fc1_weight") == ("model",)
    assert reshard.first_match(rules, "fc1_bias") == ()


def test_rules_errors():
    with pytest.raises(MXNetError, match="not a valid regex"):
        reshard.parse_rules("[=model")
    with pytest.raises(MXNetError, match="no reshard rule matches"):
        reshard.match_partition_rules(
            reshard.parse_rules("fc9=model"), {"fc1_weight": (4, 4)})
    with pytest.raises(MXNetError, match="names 2 dims"):
        reshard.match_partition_rules(
            reshard.parse_rules(".*=model,model"), {"v": (8,)})


def test_trainer_reshard_rules_env_override(monkeypatch):
    # force fc1_weight replicated; leave everything else derived
    monkeypatch.setenv("MXNET_TPU_RESHARD_RULES", "fc1_weight=")
    t = _make(build_mesh(tp=2))
    assert "fc1_weight" not in t.tp_rules
    assert "fc2_weight" in t.tp_rules     # untouched derived rule
    monkeypatch.setenv("MXNET_TPU_RESHARD_RULES", "fc1_weight=data")
    with pytest.raises(MXNetError, match="shard only over 'model'"):
        _make(build_mesh(tp=2))


# ----------------------------------------------------- descriptors / plan

def test_mesh_descriptor_and_same_mesh():
    assert reshard.same_mesh({"axes": {"data": 4, "model": 1}},
                             {"axes": {"data": 4}})
    assert not reshard.same_mesh({"axes": {"data": 4, "model": 2}},
                                 {"axes": {"data": 8}})
    assert reshard.same_mesh({"axes": {"data": 1}}, {"axes": {}})
    assert reshard.describe_axes({"axes": {"data": 1}}) == "{1}"
    t = _make(build_mesh(tp=2))
    desc = t.mesh_descriptor()
    assert desc["format"] == 2
    assert reshard.normalized_axes(desc["axes"]) == \
        {"data": 4, "model": 2}
    assert desc["specs"]["fc2_weight"] == [None, "model"]


def test_plan_reshard_rejects_indivisible():
    src = {"axes": {"data": 2}}
    dst = {"axes": {"data": 2, "model": 4},
           "specs": {"w": ["model"], "v": ["model"]}}
    with pytest.raises(MXNetError) as ei:
        reshard.plan_reshard(src, dst, {"w": (10, 4), "v": (8, 2)})
    # every offender is listed; the feasible param is not
    assert "w" in str(ei.value) and "not divisible" in str(ei.value)
    plan = reshard.plan_reshard(src, dst, {"v": (8, 2)})
    assert plan["n_resharded"] == 1
    assert plan["params"]["v"]["resharded"]
    # a typo'd axis name must fail loudly, not silently replicate
    with pytest.raises(MXNetError, match="does not have"):
        reshard.plan_reshard(src, {"axes": {"data": 2},
                                   "specs": {"v": ["modle"]}},
                             {"v": (8, 2)})
    # ...but an axis the mesh declares at size 1 legitimately shards
    # nothing and stays tolerated
    ok = reshard.plan_reshard(src, {"axes": {"data": 2, "model": 1},
                                    "specs": {"v": ["model"]}},
                              {"v": (7, 2)})
    assert ok["n_params"] == 1


def test_build_mesh_from_axes_errors():
    with pytest.raises(ValueError, match="need 64 devices"):
        build_mesh_from_axes({"data": 8, "model": 8})


# ------------------------------------------------- the acceptance matrix

@pytest.mark.parametrize("axes", [{"data": 2, "model": 2},
                                  {"data": 8}, {"data": 1}],
                         ids=["data2xmodel2", "data8", "single"])
def test_reshard_load_bit_exact(saved_ckpt, axes):
    """{data:4, model:2} -> other shapes: params/aux/optimizer state
    bit-exact, the loss trajectory continues identically, and the
    reshape is observable."""
    before = telemetry.counter("mxtpu_reshard_total").labels(
        kind="load").get()
    t = _make(build_mesh_from_axes(axes))
    t.load_checkpoint(saved_ckpt["prefix"], 2,
                      load_optimizer_states=True)
    got = _gather_all(t)
    for k, v in saved_ckpt["state"].items():
        assert np.array_equal(v, got[k]), "state %r differs" % k
    # the trajectory continues where the source left off (different
    # mesh shapes may reorder float reductions; the STATE is bit-exact,
    # the loss is reduction-order-tolerant)
    losses = [float(t.step(_batch(2 + i))) for i in range(2)]
    np.testing.assert_allclose(losses, saved_ckpt["cont_losses"],
                               rtol=1e-4)
    assert telemetry.counter("mxtpu_reshard_total").labels(
        kind="load").get() == before + 1
    ev = [e for e in telemetry.flight.events()
          if e["kind"] == "reshard"
          and e.get("dst") == reshard.describe_axes({"axes": axes})]
    assert ev, "no reshard flight event for %r" % (axes,)
    assert ev[-1]["src"] == "{data:4, model:2}"
    assert ev[-1]["n_params"] > 0


def test_same_mesh_load_does_not_reshard(saved_ckpt):
    before = telemetry.counter("mxtpu_reshard_total").labels(
        kind="load").get()
    t = _make(build_mesh(tp=2))
    t.load_checkpoint(saved_ckpt["prefix"], 2,
                      load_optimizer_states=True)
    assert telemetry.counter("mxtpu_reshard_total").labels(
        kind="load").get() == before


def test_manifest_v2_and_legacy_v1(saved_ckpt, tmp_path):
    man_path = saved_ckpt["prefix"] + "-0002.manifest.json"
    man = json.load(open(man_path))
    assert man["format"] == 2
    mesh = man["meta"]["mesh"]
    assert mesh["axes"] == {"data": 4, "model": 2}
    assert mesh["world"] == 1
    # strip the descriptor -> a v1 manifest: the load takes the legacy
    # (non-reshaping) path even on a different mesh shape
    import shutil
    prefix2 = str(tmp_path / "legacy")
    for suf in ("-symbol.json", "-0002.params", "-0002.states"):
        shutil.copyfile(saved_ckpt["prefix"] + suf, prefix2 + suf)
    man2 = dict(man, format=1, meta={})
    man2["files"] = {f.replace("job", "legacy"): v
                     for f, v in man["files"].items()}
    with open(prefix2 + "-0002.manifest.json", "w") as f:
        json.dump(man2, f)
    before = telemetry.counter("mxtpu_reshard_total").labels(
        kind="load").get()
    t = _make(build_mesh_from_axes({"data": 8}))
    t.load_checkpoint(prefix2, 2, load_optimizer_states=True)
    assert telemetry.counter("mxtpu_reshard_total").labels(
        kind="load").get() == before
    got = _gather_all(t)
    for k, v in saved_ckpt["state"].items():
        assert np.array_equal(v, got[k]), k


def test_world_change_records_rank_join(saved_ckpt, tmp_path,
                                        monkeypatch):
    """A manifest saved at world=2 loaded in this 1-process run is a
    rank LEAVE; the events + counter land, and the JSONL event record
    reaches the per-rank step-log for the run aggregator."""
    import shutil
    prefix2 = str(tmp_path / "w2")
    for suf in ("-symbol.json", "-0002.params", "-0002.states"):
        shutil.copyfile(saved_ckpt["prefix"] + suf, prefix2 + suf)
    man = json.load(open(saved_ckpt["prefix"] + "-0002.manifest.json"))
    man["files"] = {f.replace("job", "w2"): v
                    for f, v in man["files"].items()}
    man["meta"]["mesh"]["world"] = 2
    with open(prefix2 + "-0002.manifest.json", "w") as f:
        json.dump(man, f)
    jsonl = str(tmp_path / "log.jsonl.rank0")
    monkeypatch.setenv("MXNET_TPU_TELEMETRY_JSONL", jsonl)
    before = telemetry.counter("mxtpu_elastic_resizes_total").labels(
        direction="leave").get()
    t = _make(build_mesh(tp=2))
    t.load_checkpoint(prefix2, 2, load_optimizer_states=True)
    monkeypatch.delenv("MXNET_TPU_TELEMETRY_JSONL")
    telemetry.jsonl_event("noop")   # rotate the handle off the file
    assert telemetry.counter("mxtpu_elastic_resizes_total").labels(
        direction="leave").get() == before + 1
    ev = [e for e in telemetry.flight.events()
          if e["kind"] == "rank_leave"]
    assert ev and ev[-1]["from_world"] == 2 and ev[-1]["to_world"] == 1
    recs = [json.loads(l) for l in open(jsonl)]
    assert any(r.get("event") == "rank_leave" for r in recs), recs


def test_aggregator_passes_worker_events_through(tmp_path):
    from mxnet_tpu.telemetry.distview import (RunAggregator,
                                              read_run_timeline)
    base = str(tmp_path / "run.jsonl")
    agg = RunAggregator(base, 1)
    with open(base + ".rank0", "w") as f:
        f.write(json.dumps({"ts": 1.0, "event": "rank_join",
                            "from_world": 1, "to_world": 2}) + "\n")
        f.write(json.dumps({"ts": 2.0, "step": 1,
                            "step_time_s": 0.1}) + "\n")
    agg.poll()
    agg.close()
    recs = read_run_timeline(base + ".run")
    evs = [r for r in recs if r.get("kind") == "event"]
    assert any(r.get("event") == "rank_join" and r.get("rank") == 0
               for r in evs), recs
    assert any(r.get("kind") == "step" for r in recs)


# ------------------------------------------------------- failure modes

def test_reshard_infeasible_target_raises_cleanly(saved_ckpt,
                                                  monkeypatch):
    """A target layout the shapes cannot satisfy fails BEFORE any state
    moves — the old-mesh error path, trainer state untouched."""
    # fc2_bias has 10 elements: force dim 0 over the 4-way model axis
    monkeypatch.setenv("MXNET_TPU_RESHARD_RULES", "")
    t = _make(build_mesh_from_axes({"data": 2, "model": 4}))
    # hand the trainer an impossible target through its own tp_rules
    t.tp_rules = dict(t.tp_rules, fc2_bias=0)
    snap = _gather_all(t)
    with pytest.raises(MXNetError, match="not divisible"):
        t.load_checkpoint(saved_ckpt["prefix"], 2,
                          load_optimizer_states=True)
    for k, v in _gather_all(t).items():
        assert np.array_equal(v, snap[k]), k


@pytest.mark.chaos
def test_chaos_scatter_fault_degrades_to_old_mesh(saved_ckpt):
    """ISSUE 10 satellite: an injected fault inside reshard.scatter
    must surface as a descriptive MXNetError with the live state
    untouched; the next (clean) load succeeds."""
    from mxnet_tpu import resilience as R
    t = _make(build_mesh_from_axes({"data": 8}))
    snap = _gather_all(t)
    R.configure_faults("reshard.scatter:n=1")
    try:
        with pytest.raises(MXNetError, match="resharding checkpoint"):
            t.load_checkpoint(saved_ckpt["prefix"], 2,
                              load_optimizer_states=True)
        stats = R.fault_stats()
        assert stats["reshard.scatter"]["hits"] == 1
    finally:
        R.clear_faults()
    # old-mesh state untouched by the failed reshape
    for k, v in _gather_all(t).items():
        assert np.array_equal(v, snap[k]), k
    # and the path still works once the fault is gone
    t.load_checkpoint(saved_ckpt["prefix"], 2,
                      load_optimizer_states=True)
    got = _gather_all(t)
    for k, v in saved_ckpt["state"].items():
        assert np.array_equal(v, got[k]), k


# ------------------------------------------- find_latest_checkpoint

def test_find_latest_checkpoint_falls_back_past_crc_failure(tmp_path):
    """Satellite regression: the newest epoch passes the quick size
    screen (same-size bit flip) but fails CRC — find_latest_checkpoint
    must return the newest VERIFIED epoch, not the corrupt one."""
    from mxnet_tpu.model import find_checkpoints, find_latest_checkpoint
    prefix = str(tmp_path / "job")
    t = _make(build_mesh_from_axes({"data": 1}))
    t.step(_batch())
    t.save_checkpoint(prefix, 1, save_optimizer_states=True)
    t.step(_batch(1))
    t.save_checkpoint(prefix, 2, save_optimizer_states=True)
    # same-size corruption of the newest params file
    path = prefix + "-0002.params"
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 2)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    # the quick screen still lists it newest...
    assert find_checkpoints(prefix, require_states=True) == [1, 2]
    # ...but full verification falls back to epoch 1
    assert find_latest_checkpoint(prefix, require_states=True) == 1
    # and the trainer-side latest-load lands on the same epoch
    t2 = _make(build_mesh_from_axes({"data": 1}))
    assert t2.load_latest_checkpoint(
        prefix, load_optimizer_states=True) == 1


# ------------------------------------------------------ offline converter

def test_offline_convert_and_verify(saved_ckpt, tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "reshard_tool",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "reshard.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    out_prefix = str(tmp_path / "conv" / "job")
    plan = tool.convert(saved_ckpt["prefix"], 2, out_prefix,
                        {"data": 8})
    assert plan["dst"] == "{data:8}"
    assert plan["n_resharded"] > 0        # tp-sharded weights respec'd
    assert tool.verify_roundtrip(saved_ckpt["prefix"], 2,
                                 out_prefix, say=lambda s: None) == []
    # the converted manifest makes a {data:8} load NON-reshaping...
    before = telemetry.counter("mxtpu_reshard_total").labels(
        kind="load").get()
    t = _make(build_mesh_from_axes({"data": 8}))
    t.load_checkpoint(out_prefix, 2, load_optimizer_states=True)
    assert telemetry.counter("mxtpu_reshard_total").labels(
        kind="load").get() == before
    got = _gather_all(t)
    for k, v in saved_ckpt["state"].items():
        assert np.array_equal(v, got[k]), k
    # ...and an infeasible target is refused with nothing written
    with pytest.raises(MXNetError, match="not divisible"):
        tool.convert(saved_ckpt["prefix"], 2,
                     str(tmp_path / "bad" / "job"), {"model": 4},
                     rules=".*_weight=model;.*=")
    assert tool.parse_mesh("data=4,model=2") == {"data": 4, "model": 2}
    with pytest.raises(ValueError):
        tool.parse_mesh("data=x")


# ------------------------------------------------- elastic supervision

def test_launch_elastic_resize_events(tmp_path):
    """tools/launch.py --elastic: rank 1 of 2 dies on attempt 0; the
    watchdog relaunches ONE worker (rank_leave + elastic_resize events
    in the supervisor stream; MXNET_TPU_NUM_PROCESSES=1 in the resized
    attempt) and the job recovers.  Framework-free workers — this
    tests the supervisor, not jax."""
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sup = str(tmp_path / "sup.jsonl")
    log = str(tmp_path / "worlds.txt")
    worker = (
        "import os\n"
        "with open(%r, 'a') as f:\n"
        "    f.write('%%s/%%s/%%s\\n' %% ("
        "os.environ['MXNET_TPU_RESTART_COUNT'],"
        "os.environ['MXNET_TPU_PROCESS_ID'],"
        "os.environ['MXNET_TPU_NUM_PROCESSES']))\n"
        "raise SystemExit(3 if os.environ['MXNET_TPU_PROCESS_ID'] == "
        "'1' and os.environ['MXNET_TPU_RESTART_COUNT'] == '0' else 0)\n"
        % log)
    script = tmp_path / "worker.py"
    script.write_text(worker)
    env = dict(os.environ, MXNET_TPU_TELEMETRY_JSONL=sup)
    res = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", "2", "--launcher", "local", "--elastic",
         "--restart-budget", "1", "--heartbeat-interval", "0.05",
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=120, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "elastic resize 2 -> 1 worker(s)" in res.stderr, res.stderr
    lines = open(log).read().splitlines()
    assert "0/0/2" in lines and "0/1/2" in lines and "1/0/1" in lines, \
        lines
    events = [json.loads(l) for l in open(sup)]
    leaves = [e for e in events if e.get("event") == "rank_leave"]
    assert leaves and leaves[0]["rank"] == 1, events
    resizes = [e for e in events if e.get("event") == "elastic_resize"]
    assert resizes and resizes[0]["from_workers"] == 2 \
        and resizes[0]["to_workers"] == 1, events


def test_kvstore_state_roundtrip(tmp_path):
    """DistKVStore.save_state/load_state migrate the key/value store
    through the manifest-verified checkpoint format; a forged saved
    world records the kvstore reshard + rank_join."""
    from mxnet_tpu.parallel.dist_kvstore import DistKVStore
    kv = DistKVStore("dist_sync")
    kv.init([3, "named"], [mx.nd.array(np.arange(4, dtype="f")),
                           mx.nd.array(np.ones((2, 2), "f"))])
    # a numeric-looking STRING key must survive as a string (the typed
    # kv:i:/kv:s: encoding keeps it apart from int keys)
    kv.init("7", mx.nd.array(np.full((3,), 9, "f")))
    prefix = str(tmp_path / "kv")
    kv.save_state(prefix, 5)
    kv2 = DistKVStore("dist_sync")
    assert kv2.load_state(prefix, 5) == 1
    out = mx.nd.zeros((4,))
    kv2.pull(3, out=out)
    np.testing.assert_array_equal(out.asnumpy(),
                                  np.arange(4, dtype="f"))
    out7 = mx.nd.zeros((3,))
    kv2.pull("7", out=out7)
    np.testing.assert_array_equal(out7.asnumpy(), np.full((3,), 9, "f"))
    # forge a bigger saved world -> rank_leave + kvstore reshard event
    man = json.load(open(prefix + "-0005.manifest.json"))
    man["meta"]["mesh"]["world"] = 3
    with open(prefix + "-0005.manifest.json", "w") as f:
        json.dump(man, f)
    before = telemetry.counter("mxtpu_reshard_total").labels(
        kind="kvstore").get()
    kv3 = DistKVStore("dist_sync")
    assert kv3.load_state(prefix, 5) == 3
    assert telemetry.counter("mxtpu_reshard_total").labels(
        kind="kvstore").get() == before + 1
    ev = [e for e in telemetry.flight.events()
          if e["kind"] == "rank_leave" and e.get("from_world") == 3]
    assert ev and ev[-1]["to_world"] == 1, ev
