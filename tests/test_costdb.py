"""Cost database (telemetry.costdb) + its consumers.

Covers the contracts in docs/api/telemetry.md (cost database section):
record/dedup/aggregate roundtrip through flush + read_records, schema
validation and reader rejects, MFU/arithmetic-intensity/roofline math
against hand-computed fixtures, block-signature binding + sampled
collection through a real fused Executor, the perf_top ranking /
--json output, and the bench_diff trajectory guard (noise threshold,
errored-run skip, synthetic regression detection).
"""
import importlib.util
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import costdb


def _load_tool(name):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, "tools", "%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    for var in ("MXNET_TPU_COSTDB", "MXNET_TPU_COSTDB_SAMPLE",
                "MXNET_TPU_PEAK_FLOPS", "MXNET_TPU_PEAK_BW"):
        monkeypatch.delenv(var, raising=False)
    telemetry.reset()
    yield
    telemetry.reset()


# ------------------------------------------------------ roofline math

def test_roofline_hand_computed_compute_bound(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("MXNET_TPU_PEAK_BW", "1e11")
    # AI = 1e9/1e6 = 1000 flops/B >= ridge 10 -> compute bound;
    # MFU = 1e9 / 0.01s / 1e12 = 0.1; attainable = 1e9/1e12 = 1 ms
    r = costdb.roofline(1e9, 1e6, 0.01)
    assert r["mfu"] == pytest.approx(0.1)
    assert r["ai"] == pytest.approx(1000.0)
    assert r["bound"] == "compute"
    assert r["attainable_s"] == pytest.approx(1e-3)
    assert r["attained_frac"] == pytest.approx(0.1)


def test_roofline_hand_computed_bandwidth_bound(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("MXNET_TPU_PEAK_BW", "1e11")
    # AI = 1e6/1e6 = 1 < ridge 10 -> bandwidth bound; memory time
    # 1e6/1e11 = 10us dominates compute 1e6/1e12 = 1us
    r = costdb.roofline(1e6, 1e6, 1e-4)
    assert r["bound"] == "bandwidth"
    assert r["attainable_s"] == pytest.approx(1e-5)
    assert r["attained_frac"] == pytest.approx(0.1)


def test_roofline_null_fields_never_raise():
    r = costdb.roofline(None, None, None)
    assert r["mfu"] is None and r["ai"] is None and r["bound"] is None
    r = costdb.roofline(1e6, None, 0.0)      # zero wall, no bytes
    assert r["mfu"] is None and r["bound"] is None
    assert r["attainable_s"] is not None     # compute bound exists


def test_peak_rows_by_device_kind_and_unknown_kind_has_no_peak():
    # the "tpu" row is a v5e's published peaks and applies only to the
    # device_kind a v5e reports; any other accelerator kind keeps its
    # own name, has no peak, and so no MFU — never a default
    assert costdb.DEVICE_KIND_ROWS == {"TPU v5 lite": "tpu"}
    assert costdb.PEAKS["tpu"] == (197e12, 819e9)
    assert costdb.peak_flops("tpu") == 197e12
    assert costdb.peak_flops("TPU v4") is None
    assert costdb.peak_bandwidth("TPU v4") is None
    r = costdb.roofline(1e9, 1e6, 1e-3, backend="TPU v4")
    assert r["mfu"] is None and r["bound"] is None
    assert r["attainable_s"] is None and r["attained_frac"] is None
    assert r["ai"] == pytest.approx(1e3)


def test_peak_table_env_override(monkeypatch):
    base = costdb.peak_flops("cpu")
    assert base == costdb.PEAKS["cpu"][0]
    monkeypatch.setenv("MXNET_TPU_PEAK_FLOPS", "123e9")
    assert costdb.peak_flops("cpu") == pytest.approx(123e9)
    monkeypatch.setenv("MXNET_TPU_PEAK_BW", "45e9")
    assert costdb.peak_bandwidth("tpu") == pytest.approx(45e9)
    # garbage falls back to the table
    monkeypatch.setenv("MXNET_TPU_PEAK_FLOPS", "not-a-number")
    assert costdb.peak_flops("cpu") == base


# ------------------------------------------- record/aggregate/roundtrip

def test_record_dedup_aggregate_and_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("MXNET_TPU_PEAK_BW", "1e11")
    db = costdb.CostDB()
    for wall in (0.02, 0.01, 0.03):
        db.record("block", "b0", wall_s=wall, flops=1e9,
                  bytes_accessed=1e6, shapes=[(8, 64)],
                  dtypes=["float32"], backend="cpu",
                  block_kind="fc_act")
    # same name, DIFFERENT shape -> a separate record
    db.record("block", "b0", wall_s=0.5, flops=1e9,
              bytes_accessed=1e6, shapes=[(16, 64)],
              dtypes=["float32"], backend="cpu", block_kind="fc_act")
    recs = db.records()
    assert len(recs) == 2
    agg = next(r for r in recs if r["count"] == 3)
    assert agg["wall_s"] == pytest.approx(0.01)        # min wall
    assert agg["mean_wall_s"] == pytest.approx(0.02)
    assert agg["mfu"] == pytest.approx(0.1)            # from min wall
    assert agg["schema"] == "mxtpu-costdb/1"

    path = db.flush(str(tmp_path))
    assert path and os.path.exists(path)
    loaded, skipped = costdb.read_records(str(tmp_path))
    assert skipped == 0 and len(loaded) == 2
    by_count = {r["count"]: r for r in loaded}
    assert by_count[3]["wall_s"] == pytest.approx(0.01)
    # a second flush appends a snapshot; the reader dedups to the last
    db.record("block", "b0", wall_s=0.005, flops=1e9,
              bytes_accessed=1e6, shapes=[(8, 64)],
              dtypes=["float32"], backend="cpu", block_kind="fc_act")
    db.flush(str(tmp_path))
    loaded, _ = costdb.read_records(str(tmp_path))
    assert len(loaded) == 2
    assert max(r["count"] for r in loaded) == 4


def test_record_metrics_emitted():
    telemetry.reset()
    db = costdb.DB
    db.record("block", "mblk", wall_s=0.01, flops=1e9,
              bytes_accessed=1e6, shapes=[(4,)], dtypes=["float32"],
              backend="cpu", block_kind="bn_act")
    assert telemetry.counter("mxtpu_costdb_records_total").labels(
        kind="block").get() == 1
    g = telemetry.gauge("mxtpu_block_mfu").labels(block="mblk")
    assert g.get() > 0


def test_flush_without_dir_is_noop():
    db = costdb.CostDB()
    db.record("program", "p", wall_s=0.1)
    assert db.flush() is None        # MXNET_TPU_COSTDB unset


# ------------------------------------------------ schema / reader rejects

def test_reader_rejects_wrong_schema_and_garbage(tmp_path):
    good = {"schema": "mxtpu-costdb/1", "kind": "block", "name": "b",
            "sig": "abc"}
    bad_schema = dict(good, schema="mxtpu-costdb/999")
    bad_kind = dict(good, kind="nonsense")
    p = tmp_path / "costdb-1.jsonl"
    p.write_text(json.dumps(good) + "\n" + json.dumps(bad_schema)
                 + "\nnot json at all\n" + json.dumps(bad_kind) + "\n"
                 + json.dumps({"schema": "mxtpu-costdb/1"}) + "\n")
    recs, skipped = costdb.read_records(str(p))
    assert len(recs) == 1 and recs[0]["name"] == "b"
    assert skipped == 4
    with pytest.raises(ValueError):
        costdb.read_records(str(p), strict=True)
    # an empty directory is only an error in strict mode
    empty = tmp_path / "empty"
    empty.mkdir()
    recs, skipped = costdb.read_records(str(empty))
    assert recs == [] and skipped == 0
    with pytest.raises(ValueError):
        costdb.read_records(str(empty), strict=True)


# ------------------------------------- signature binding + sampled exec

def _fused_executor():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc0")
    net = mx.sym.Activation(net, act_type="relu", name="relu0")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc1")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    from mxnet_tpu.ops.fused import block_fusion
    with block_fusion(True):
        ex = sym.simple_bind(mx.cpu(), data=(4, 8), softmax_label=(4,))
    rng = np.random.RandomState(0)
    for n, arr in sorted(ex.arg_dict.items()):
        arr[:] = rng.uniform(-0.5, 0.5, arr.shape).astype(np.float32)
    return ex


def test_sampled_executor_collection(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_COSTDB_SAMPLE", "1")
    telemetry.reset()
    ex = _fused_executor()
    for _ in range(3):
        ex.forward(is_train=True)
        ex.backward()
    recs = costdb.records()
    progs = {r["name"] for r in recs if r["kind"] == "program"}
    assert "executor.forward" in progs
    blocks = [r for r in recs if r["kind"] == "block"]
    assert {b["name"] for b in blocks} == {"relu0"}
    blk = blocks[0]
    # the acceptance contract: non-null time, flops, and MFU
    assert blk["wall_s"] is not None and blk["wall_s"] > 0
    assert blk["flops"] is not None and blk["flops"] > 0
    assert blk["mfu"] is not None and blk["mfu"] > 0
    assert blk["block_kind"] == "fc_act"
    assert blk["bound"] in ("compute", "bandwidth")
    assert blk["program"] in progs
    assert blk["source"] == "span+roofline-attribution"
    # fc0 relu0: x (4,8), w (16,8) -> flops = 2*out.size*w.size/16
    #           + 10*out.size = 2*64*8 + 640
    assert blk["flops"] == pytest.approx(2 * 4 * 16 * 8 + 10 * 4 * 16)


def test_sampling_disabled_still_binds_signatures(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_COSTDB_SAMPLE", "0")
    telemetry.reset()
    ex = _fused_executor()
    for _ in range(3):
        ex.forward(is_train=True)
    # no measured records...
    assert costdb.records() == []
    # ...but the block signature was still captured and bound
    with costdb.DB._lock:
        bound = {s["name"] for sigs in costdb.DB._bound.values()
                 for s in sigs}
    assert "relu0" in bound


def test_first_dispatch_never_sampled(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_COSTDB_SAMPLE", "1")
    db = costdb.CostDB()
    obs = db.begin_dispatch("p", key=1)
    assert obs[2] is None            # compile dispatch: no timing
    obs = db.begin_dispatch("p", key=1)
    assert obs[2] is not None        # first post-compile: sampled
    # a SECOND instance shares the program name but not the fn: its
    # compile dispatch must not look post-warm (it would record
    # multi-second compile wall as dispatch wall)
    obs = db.begin_dispatch("p", key=2)
    assert obs[2] is None


def test_retrace_rebinds_in_place_not_stacked(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_COSTDB_SAMPLE", "1")
    db = costdb.CostDB()
    for _ in range(2):               # trace + identical retrace
        db.note_block("b0", "fc_act", [(8, 64)], ["float32"],
                      flops=1e6, bytes_accessed=1e5)
        db.end_dispatch(("p", None, None))
    with db._lock:
        assert len(db._bound[("p", None)]) == 1
    # two DIFFERENT instantiations of one kernel in one trace coexist
    db.note_kernel("flash", [(1, 77, 2, 8)], ["float32"], flops=1e6,
                   block_config={"block_q": 77})
    db.note_kernel("flash", [(1, 4096, 2, 8)], ["float32"], flops=1e9,
                   block_config={"block_q": 128})
    db.end_dispatch(("p", None, None))
    with db._lock:
        kernels = [s for s in db._bound[("p", None)]
                   if s["kind"] == "kernel"]
    assert len(kernels) == 2


def test_run_steps_chain_scales_wall_per_step(monkeypatch):
    """A run_steps dispatch executes N full steps: the measured wall
    (and the program's chain-wide cost_analysis flops) must be scaled
    to per-step so block MFU is not understated ~N x."""
    monkeypatch.setenv("MXNET_TPU_COSTDB_SAMPLE", "1")
    monkeypatch.setenv("MXNET_TPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("MXNET_TPU_PEAK_BW", "1e11")
    import time as _time

    def one(db, steps):
        db.note_block("b0", "fc_act", [(8, 64)], ["float32"],
                      flops=1e6, bytes_accessed=1e5)
        db.begin_dispatch("p", key=1)                # compile
        obs = db.begin_dispatch("p", key=1)
        _time.sleep(0.02)
        db.end_dispatch(obs, out=None, args=None, steps=steps)
        return next(r for r in db.records() if r["kind"] == "block")

    blk1 = one(costdb.CostDB(), 1)
    blk8 = one(costdb.CostDB(), 8)
    assert blk8["wall_s"] < blk1["wall_s"]
    assert blk8["wall_s"] == pytest.approx(blk1["wall_s"] / 8,
                                           rel=0.5)


def test_two_instances_do_not_cross_attribute(monkeypatch):
    """Two executors share the fixed program-name strings: one model's
    measured wall must not be split across the other's blocks.  The
    trace (note_block) happens INSIDE the compile dispatch, between
    begin and end — modeled here."""
    monkeypatch.setenv("MXNET_TPU_COSTDB_SAMPLE", "1")
    db = costdb.CostDB()
    # A's compile dispatch: trace registers A's block, end binds it
    obs = db.begin_dispatch("executor.fused", key=1)
    db.note_block("model_a_blk", "fc_act", [(8, 64)], ["float32"],
                  flops=1e6, bytes_accessed=1e5)
    db.end_dispatch(obs, out=None, args=None)
    # B's compile dispatch (same program name, different fn)
    obs = db.begin_dispatch("executor.fused", key=2)
    db.note_block("model_b_blk", "fc_act", [(4, 32)], ["float32"],
                  flops=1e6, bytes_accessed=1e5)
    db.end_dispatch(obs, out=None, args=None)
    with db._lock:
        a = {s["name"] for s in db._bound[("executor.fused", 1)]}
        b = {s["name"] for s in db._bound[("executor.fused", 2)]}
    assert a == {"model_a_blk"} and b == {"model_b_blk"}
    # A's sampled dispatch records A's block only — B's untouched
    obs = db.begin_dispatch("executor.fused", key=1)
    db.end_dispatch(obs, out=None, args=None)
    blocks = {r["name"] for r in db.records() if r["kind"] == "block"}
    assert blocks == {"model_a_blk"}


def test_partial_batch_program_keys_do_not_collapse(monkeypatch):
    """The batch leaf sits past the 4 displayed leaves (params lead the
    trainer's arg tree): the full-leaf digest must still separate the
    partial-final-batch record from the full-batch one."""
    monkeypatch.setenv("MXNET_TPU_COSTDB_SAMPLE", "1")
    import numpy as np_
    db = costdb.CostDB()
    params = [np_.zeros((4, 4), np_.float32)] * 6

    def dispatch(batch_rows, wall):
        args = (params, np_.zeros((batch_rows, 8), np_.float32))
        obs = ("p", 1, None)
        db._end_dispatch(obs, None, args, None)    # bind-only path
        sh, dt, n, digest = costdb._shapes_of(args)
        db.record("program", "p", wall_s=wall, flops=1e6,
                  shapes=sh, dtypes=dt, n_leaves=n,
                  leaves_digest=digest, backend="cpu")

    dispatch(32, 0.010)
    dispatch(7, 0.002)                 # partial tail: faster, own key
    progs = [r for r in db.records() if r["kind"] == "program"]
    assert len(progs) == 2
    assert {round(r["wall_s"], 3) for r in progs} == {0.010, 0.002}


def test_scope_tokens_unique_and_droppable(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_COSTDB_SAMPLE", "1")
    s1, s2 = costdb.next_scope(), costdb.next_scope()
    assert s1 != s2
    db = costdb.CostDB()
    db.begin_dispatch("p", key=(s1, 123))
    db.begin_dispatch("p", key=(s2, 123))
    db.note_block("b", "fc_act", [(8,)], ["float32"], flops=1.0,
                  bytes_accessed=1.0)
    db.bind_pending("p", key=(s1, 123))
    db.drop_scope(s1)
    with db._lock:
        assert ("p", (s1, 123)) not in db._counts
        assert ("p", (s1, 123)) not in db._bound
        assert ("p", (s2, 123)) in db._counts
    # a fresh scope reusing the same id(fn) starts cold (compile skip)
    obs = db.begin_dispatch("p", key=(s1, 123))
    assert obs[2] is None


def test_bench_diff_dominant_metric_survives_rename(tmp_path, capsys):
    """A mid-series metric rename must not anchor the guard on the two
    stale runs and wave a regression through."""
    bench_diff = _load_tool("bench_diff")
    paths = _write_series(tmp_path, [100.0, 101.0], metric="old")
    for i, v in enumerate([102.0, 103.0, 70.0]):     # renamed + drop
        p = tmp_path / ("BENCH_t%02d.json" % i)
        p.write_text(json.dumps({"metric": "new", "value": v,
                                 "unit": "u"}))
        paths.append(str(p))
    assert bench_diff.main(paths + ["--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["metric"] == "new" and doc["regression"] is True


def test_retrace_burst_replaces_stale_shape_variants():
    """A partial-final-batch retrace must not leave the full-batch
    variant bound alongside it — that would split (and corrupt) every
    later sampled dispatch's attributed wall."""
    db = costdb.CostDB()
    db.note_block("b0", "fc_act", [(32, 64)], ["float32"], flops=1e6,
                  bytes_accessed=1e5)
    db.bind_pending("p")
    db.note_block("b0", "fc_act", [(7, 64)], ["float32"], flops=2e5,
                  bytes_accessed=3e4)           # partial-batch retrace
    db.bind_pending("p")
    with db._lock:
        bound = list(db._bound[("p", None)])
    assert len(bound) == 1
    assert bound[0]["shapes"] == [[7, 64]]


def test_multiproc_bind_only_no_dangling_signatures(monkeypatch):
    """The multi-process trainer path binds (no timing): signatures
    must not dangle and attach to the next single-proc program."""
    monkeypatch.setenv("MXNET_TPU_COSTDB_SAMPLE", "1")
    db = costdb.CostDB()
    db.note_block("mp_block", "conv_bn", [(8, 3, 4, 4)], ["float32"],
                  flops=1e6, bytes_accessed=1e5)
    db.bind_pending("trainer.step")              # what multiproc does
    db.begin_dispatch("executor.forward", key=1)
    obs = db.begin_dispatch("executor.forward", key=1)
    db.end_dispatch(obs, out=None, args=None)
    with db._lock:
        assert "mp_block" not in {
            s["name"]
            for s in db._bound.get(("executor.forward", 1), ())}
        assert {s["name"]
                for s in db._bound[("trainer.step", None)]} \
            == {"mp_block"}
    assert not [r for r in db.records()
                if r["kind"] == "block"
                and r["program"] == "executor.forward"]


def test_failed_dispatch_still_binds_but_never_times(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_COSTDB_SAMPLE", "1")
    db = costdb.CostDB()
    db.note_block("b0", "fc_act", [(8, 64)], ["float32"], flops=1e6,
                  bytes_accessed=1e5)
    db.begin_dispatch("p", key=1)                    # compile
    obs = db.begin_dispatch("p", key=1)              # sampled...
    db.end_dispatch(obs, failed=True)                # ...but raised
    with db._lock:
        assert {s["name"] for s in db._bound[("p", 1)]} == {"b0"}
    assert db.records() == []        # no wall recorded for the failure


def test_reader_dedup_prefers_newest_ts(tmp_path):
    base = {"schema": "mxtpu-costdb/1", "kind": "block", "name": "b",
            "sig": "abc"}
    # an OLD run under a lexically-later pid filename must not win
    (tmp_path / "costdb-9999.jsonl").write_text(
        json.dumps(dict(base, ts=100.0, wall_s=9.0)) + "\n")
    (tmp_path / "costdb-788.jsonl").write_text(
        json.dumps(dict(base, ts=200.0, wall_s=1.0)) + "\n")
    recs, skipped = costdb.read_records(str(tmp_path))
    assert skipped == 0 and len(recs) == 1
    assert recs[0]["wall_s"] == 1.0


def test_trainer_cost_summary(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_COSTDB_SAMPLE", "1")
    telemetry.reset()
    from mxnet_tpu import models
    from mxnet_tpu.parallel import ShardedTrainer, build_mesh
    trainer = ShardedTrainer(
        models.get_model("mlp", num_classes=10), build_mesh(tp=1),
        data_shapes={"data": (8, 64)},
        label_shapes={"softmax_label": (8,)}, dtype="float32",
        fuse_blocks=True)
    batch = {"data": np.zeros((8, 64), np.float32),
             "softmax_label": np.zeros((8,), np.float32)}
    for _ in range(3):
        float(trainer.step(batch))
    s = trainer.cost_summary()
    assert s["schema"] == "mxtpu-costdb/1"
    assert "trainer.step" in s["programs"]
    prog = s["programs"]["trainer.step"]
    assert prog["wall_s"] > 0 and prog["mfu"] is not None
    assert s["worst_mfu"] and s["worst_mfu"][0]["mfu"] is not None
    # the mesh shape is part of every record key (axis sizes match the
    # trainer's mesh whatever the local device count is)
    rec = next(r for r in costdb.records()
               if r["kind"] == "program" and r["name"] == "trainer.step")
    assert rec["mesh"] == {str(k): int(v)
                           for k, v in dict(trainer.mesh.shape).items()}


def test_kernel_note_from_flash_attention():
    telemetry.reset()
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    q = jnp.zeros((1, 256, 2, 8), jnp.float32)
    pk._note_kernel_cost("flash_attention_fwd", q, 128, 256, False,
                         n_matmuls=4, n_tensors=4)
    with costdb.DB._lock:
        pend = list(costdb.DB._pending)
    assert len(pend) == 1
    sig = pend[0]
    assert sig["kind"] == "kernel"
    assert sig["block_config"] == {"block_q": 128, "block_k": 256,
                                   "n_k": 1, "causal": False,
                                   "causal_ranges": 1,
                                   "scores_computed_pct": 100.0,
                                   "window": 0, "group_parts": 1,
                                   "tiles_per_q_block": 1}
    assert sig["flops"] == pytest.approx(4 * 1 * 2 * 256 * 256 * 8)


def test_kernel_note_from_block_diffusion_flash_attention():
    """The block-diffusion kernels' record has the causal kernels' fields and
    ``diffusion_block``; its flops count the tiles it runs."""
    telemetry.reset()
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import flash_blockdiff as bd
    q = jax.ShapeDtypeStruct((1, 512, 2, 8), jnp.float32)
    jax.make_jaxpr(lambda q: bd.fwd(q, q, q, 4, True, (64, 128))[0])(q)
    with costdb.DB._lock:
        pend = list(costdb.DB._pending)
    assert len(pend) == 1 and pend[0]["kind"] == "kernel"
    assert pend[0]["name"] == "flash_attention_fwd_blockdiff", pend[0]
    # a half of 4 Q blocks over 2 K/V tiles: 64, 128, 128 + 64, 128 + 128
    # clean columns a block of either copy, and 64 of its own a noised one
    pct = 100.0 * 64 * (2 * (64 + 128 + 192 + 256) + 4 * 64) / (512 * 512)
    assert pend[0]["block_config"] == {
        "block_q": 64, "block_k": 128, "n_k": 4, "causal": False,
        "causal_ranges": 2, "scores_computed_pct": pct, "window": 0,
        "group_parts": 1, "tiles_per_q_block": 3, "diffusion_block": 4}
    assert pend[0]["flops"] == pytest.approx(
        4 * 8 * 1 * 2 * 512 * 512 * pct / 100.0)


# ----------------------------------------------------------- perf_top

def _seed_db(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("MXNET_TPU_PEAK_BW", "1e11")
    db = costdb.CostDB()
    db.record("block", "slow_block", wall_s=0.01, flops=1e8,
              bytes_accessed=1e8, shapes=[(8, 8)], dtypes=["float32"],
              backend="cpu", block_kind="conv_bn_act",
              program="trainer.step")
    db.record("block", "fast_block", wall_s=0.001, flops=9e8,
              bytes_accessed=1e6, shapes=[(8, 8)], dtypes=["float32"],
              backend="cpu", block_kind="fc_act",
              program="trainer.step")
    db.record("kernel", "flash_attention_fwd", wall_s=0.002, flops=5e8,
              bytes_accessed=2e6, shapes=[(1, 256, 1, 32)],
              dtypes=["float32"], backend="cpu",
              block_config={"block_q": 128, "block_k": 256})
    db.record("program", "trainer.step", wall_s=0.013, flops=1.5e9,
              bytes_accessed=1.03e8, shapes=[(8, 8)],
              dtypes=["float32"], backend="cpu")
    db.flush(str(tmp_path))
    return db


def test_perf_top_ranks_worst_first(tmp_path, monkeypatch, capsys):
    _seed_db(tmp_path, monkeypatch)
    perf_top = _load_tool("perf_top")
    assert perf_top.main([str(tmp_path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == "mxtpu-perftop/1"
    # slow_block: mfu = 1e8/0.01/1e12 = 0.01 — the worst
    assert doc["worst"]["name"] == "slow_block"
    assert doc["worst"]["mfu"] == pytest.approx(0.01)
    assert doc["worst"]["bound"] == "bandwidth"
    names = [e["name"] for e in doc["entries"]]
    assert names[0] == "slow_block"
    assert names.index("slow_block") < names.index("fast_block")
    # human rendering names the worst block too
    assert perf_top.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "worst MFU: slow_block" in out
    assert "block_q=128" in out            # block config is visible


def test_perf_top_kind_filter_and_missing_path(tmp_path, monkeypatch,
                                               capsys):
    _seed_db(tmp_path, monkeypatch)
    perf_top = _load_tool("perf_top")
    assert perf_top.main([str(tmp_path), "--json", "--kind",
                          "kernel"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert [e["name"] for e in doc["entries"]] == ["flash_attention_fwd"]
    assert doc["entries"][0]["block_config"]["block_q"] == 128
    assert perf_top.main([str(tmp_path / "nope")]) == 2
    capsys.readouterr()


# --------------------------------------------------------- bench_diff

def _write_series(tmp_path, values, metric="m", wrapper=False,
                  extra=None):
    paths = []
    for i, v in enumerate(values):
        payload = {"metric": metric, "value": v, "unit": "u"}
        if extra and i in extra:
            payload.update(extra[i])
        doc = {"rc": 0, "parsed": payload} if wrapper else payload
        p = tmp_path / ("BENCH_s%02d.json" % i)
        p.write_text(json.dumps(doc))
        paths.append(str(p))
    return paths


def test_bench_diff_ok_within_noise(tmp_path, capsys):
    bench_diff = _load_tool("bench_diff")
    paths = _write_series(tmp_path, [100.0, 110.0, 108.0])
    assert bench_diff.main(paths + ["--threshold", "0.1"]) == 0
    assert "ok" in capsys.readouterr().out


def test_bench_diff_flags_regression(tmp_path, capsys):
    bench_diff = _load_tool("bench_diff")
    paths = _write_series(tmp_path, [100.0, 110.0, 88.0])  # -20% vs 110
    assert bench_diff.main(paths + ["--threshold", "0.1",
                                    "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["regression"] is True
    assert doc["best_earlier"]["value"] == 110.0
    assert doc["change_frac"] == pytest.approx(-0.2)


def test_bench_diff_skips_errored_and_invalid_runs(tmp_path, capsys):
    bench_diff = _load_tool("bench_diff")
    # run 1 did not finish (valid=false + error + value 0), run 2 wrapper
    # rc=1: both skipped — NOT read as 100% regressions
    paths = _write_series(
        tmp_path, [100.0, 0, 102.0, 101.0], wrapper=True,
        extra={1: {"valid": False,
                   "error": "accelerator backend unreachable"}})
    doc1 = json.loads((tmp_path / "BENCH_s03.json").read_text())
    doc1["rc"] = 1
    (tmp_path / "BENCH_s03.json").write_text(json.dumps(doc1))
    assert bench_diff.main(paths + ["--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["regression"] is False
    assert doc["valid_runs"] == 2
    reasons = " ".join(s["reason"] for s in doc["skipped"])
    assert "errored" in reasons and "rc=1" in reasons
    assert doc["latest"]["value"] == 102.0


def test_bench_diff_round_series_with_errored_round(tmp_path, capsys):
    """The acceptance contract over a series of round wrappers: four
    rising rounds, then one that exited 1 with an error payload."""
    bench_diff = _load_tool("bench_diff")
    series = []
    for n, value in enumerate([1838.5, 2758.37, 2950.97, 2950.62, 0], 1):
        parsed = {"metric": "resnet50_train_images_per_sec_per_chip",
                  "value": value, "unit": "img/s/chip"}
        if not value:
            parsed["error"] = "accelerator backend unreachable"
        p = tmp_path / ("BENCH_r%02d.json" % n)
        p.write_text(json.dumps({"n": n, "rc": 0 if value else 1,
                                 "parsed": parsed}))
        series.append(str(p))
    assert bench_diff.main(series + ["--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["comparable"] is True
    # r05 is the errored round: skipped, not a regression
    assert any("r05" in s["path"] for s in doc["skipped"])


def test_bench_diff_insufficient_data_is_not_failure(tmp_path, capsys):
    bench_diff = _load_tool("bench_diff")
    paths = _write_series(tmp_path, [100.0])
    assert bench_diff.main(paths) == 0
    assert "nothing to compare" in capsys.readouterr().out


def test_bench_diff_mixed_metrics_compare_dominant(tmp_path, capsys):
    bench_diff = _load_tool("bench_diff")
    paths = _write_series(tmp_path, [100.0, 101.0])
    other = tmp_path / "BENCH_other.json"
    other.write_text(json.dumps({"metric": "other", "value": 5.0,
                                 "unit": "u"}))
    assert bench_diff.main(paths + [str(other), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["metric"] == "m"
    assert any("metric" in s["reason"] for s in doc["skipped"])


# ------------------------------------------------------------- telemetry

def test_reset_clears_costdb():
    costdb.record("program", "p", wall_s=0.1)
    assert costdb.records()
    telemetry.reset()
    assert costdb.records() == []
