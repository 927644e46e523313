"""Autotuner subsystem (mxnet_tpu.autotune) + its consumers.

Covers the contracts in docs/api/autotune.md: the measurement runner's
min-wall semantics, candidate spaces over the divisor lattice, the
persistent tuning cache (merge-on-load, corrupt-file degradation,
best-wall-wins), trace-time lookup in the flash kernels with the
tuned entry winning over the heuristic, the `_blocks()` heuristic across the full divisor lattice
(ADVICE cliff shapes included), the learned cost model
(fit/predict/save/load/calibration) and analysis rule MXG010, and the
perf_top --suggest / tools/autotune.py CLI surfaces.
"""
import importlib.util
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autotune, telemetry
from mxnet_tpu.ops import pallas_kernels as pk
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
    for var in ("MXNET_TPU_TUNE_CACHE", "MXNET_TPU_AUTOTUNE",
                "MXNET_TPU_COSTDB", "MXNET_TPU_COSTDB_SAMPLE",
                "MXNET_TPU_PEAK_FLOPS", "MXNET_TPU_PEAK_BW"):
        monkeypatch.delenv(var, raising=False)
    autotune.CACHE.clear()
    autotune.reset_stats()
    telemetry.reset()
    yield
    autotune.CACHE.clear()
    autotune.reset_stats()
    telemetry.reset()


# --------------------------------------- the _blocks divisor lattice

def test_blocks_full_divisor_lattice():
    """Satellite: the heuristic across the full lattice — _BLOCK_K
    multiples, the ADVICE cliff shapes (2176, 3200), prime-ish T, and
    T below one Q block."""
    from mxnet_tpu.ops.pallas_kernels import (_BLOCK_K, _BLOCK_Q, _blocks,
                                              _flash_blocks)

    # panel / streaming regulars
    assert _blocks(2048) == (128, 2048)
    assert _blocks(4096) == (128, 2048)
    assert _blocks(512) == (128, 512)
    # ADVICE cliffs
    assert _blocks(3200) == (128, 640)
    assert _blocks(2176) == (128, 128)     # 128*17: no larger divisor
    # prime-ish T (q-tileable but with a prime cofactor)
    assert _blocks(1664) == (128, 1664)    # 128*13 <= _BLOCK_K: panel
    assert _blocks(128 * 31) == (128, 128)  # 3968 > _BLOCK_K, prime co
    assert _blocks(128 * 37) == (128, 128)  # 4736 > _BLOCK_K, prime co
    # T below/at one Q block (ragged paths)
    assert _blocks(100) == (100, 100)
    assert _blocks(128) == (128, 128)
    # invariants over the whole lattice
    for t in range(128, 8193, 128):
        bq, bk = _blocks(t)
        assert bq == min(_BLOCK_Q, t)
        assert t % bk == 0 and bk % bq == 0
        assert bk <= max(_BLOCK_K, bq)
    # the rule the tuning cache falls back on: a call that is not causal
    # keeps that pair, a causal one the K block and the largest Q block of
    # 512, 256, 128 rows that leaves its diagonal four places
    assert _flash_blocks(2048, 64) == (128, 2048)
    assert _flash_blocks(2048, 64, causal=True) == (512, 2048)
    assert _flash_blocks(4096, 64, causal=True) == (512, 2048)
    assert _flash_blocks(1024, 64, causal=True) == (256, 1024)
    assert _flash_blocks(512, 64, causal=True) == (128, 512)
    assert _flash_blocks(3200, 64, causal=True) == (128, 640)
    assert _flash_blocks(2176, 64, causal=True) == (128, 128)
    assert _flash_blocks(100, 64, causal=True) == (100, 100)


def test_select_blocks_tuned_cache_override_wins(monkeypatch, tmp_path):
    """Satellite: a tuned cache entry beats the heuristic at trace
    time; the hit is counted."""
    import jax.numpy as jnp
    monkeypatch.setenv("MXNET_TPU_TUNE_CACHE", str(tmp_path))
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "cache")
    autotune.put("flash_attention_fwd", [(2, 2176, 8, 64)],
                 ["float32"], {"block_q": 64, "block_k": 136},
                 wall_s=1e-3, extra={"causal": False})
    q = jnp.zeros((2, 2176, 8, 64), jnp.float32)
    assert pk._select_blocks("flash_attention_fwd", q, False) \
        == (64, 136)
    # heuristic would have said (128, 128)
    assert pk._blocks(2176) == (128, 128)
    s = autotune.summary()
    assert s["hits"] == 1 and s["misses"] == 0
    assert s["tuned"][0]["config"] == {"block_q": 64, "block_k": 136}
    # a different shape misses -> heuristic
    q2 = jnp.zeros((2, 2048, 8, 64), jnp.float32)
    assert pk._select_blocks("flash_attention_fwd", q2, False) \
        == (128, 2048)
    assert autotune.summary()["misses"] == 1
    # the causal rule is a fall-back too: a cached pair still wins
    assert pk._select_blocks("flash_attention_fwd", q2, True) == (512, 2048)
    autotune.put("flash_attention_fwd", [(2, 2048, 8, 64)], ["float32"],
                 {"block_q": 128, "block_k": 1024}, wall_s=1e-3,
                 extra={"causal": True})
    assert pk._select_blocks("flash_attention_fwd", q2, True) == (128, 1024)


def test_select_blocks_invalid_cached_config_degrades(monkeypatch,
                                                      tmp_path):
    """A stale/corrupt cached config that does not tile the sequence
    falls back to the heuristic instead of compiling a broken grid."""
    import jax.numpy as jnp
    monkeypatch.setenv("MXNET_TPU_TUNE_CACHE", str(tmp_path))
    autotune.put("flash_attention_fwd", [(1, 256, 1, 32)], ["float32"],
                 {"block_q": 96, "block_k": 100},   # 256 % 96 != 0
                 wall_s=1e-3, extra={"causal": False})
    q = jnp.zeros((1, 256, 1, 32), jnp.float32)
    assert pk._select_blocks("flash_attention_fwd", q, False) \
        == pk._blocks(256)


def test_corrupt_or_empty_cache_degrades_without_raising(monkeypatch,
                                                         tmp_path):
    """Satellite: garbage/empty cache files never raise into a trace —
    the heuristic is used, and the lenient reader reports skips while
    the strict reader rejects."""
    import jax.numpy as jnp
    (tmp_path / "tunecache-1.jsonl").write_text(
        "{not json\n\n"
        + json.dumps({"schema": "wrong/9", "sig": "x",
                      "op": "y", "config": {}}) + "\n")
    (tmp_path / "tunecache-2.jsonl").write_text("")
    monkeypatch.setenv("MXNET_TPU_TUNE_CACHE", str(tmp_path))
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "cache")
    q = jnp.zeros((1, 512, 1, 32), jnp.float32)
    assert pk._select_blocks("flash_attention_fwd", q, False) \
        == pk._blocks(512)
    entries, skipped = autotune.read_entries(str(tmp_path))
    assert entries == [] and skipped == 2
    with pytest.raises(ValueError):
        autotune.read_entries(str(tmp_path), strict=True)


def test_autotune_off_mode_skips_lookup(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TPU_TUNE_CACHE", str(tmp_path))
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "off")
    autotune.put("flash_attention_fwd", [(1, 256, 1, 32)], ["float32"],
                 {"block_q": 64, "block_k": 64}, wall_s=1e-3,
                 extra={"causal": False})
    import jax.numpy as jnp
    q = jnp.zeros((1, 256, 1, 32), jnp.float32)
    assert pk._select_blocks("flash_attention_fwd", q, False) \
        == pk._blocks(256)
    s = autotune.summary()
    assert s["hits"] == 0 and s["misses"] == 0


def test_lookup_emits_metrics_and_flight_event(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TPU_TUNE_CACHE", str(tmp_path))
    autotune.put("flash_attention_fwd", [(2, 256, 2, 32)], ["float32"],
                 {"block_q": 64, "block_k": 128}, wall_s=1e-3)
    from mxnet_tpu.telemetry import flight
    flight.RECORDER.clear()
    assert autotune.kernel_config(
        "flash_attention_fwd", [(2, 256, 2, 32)], ["float32"]) \
        == {"block_q": 64, "block_k": 128}
    assert autotune.kernel_config(
        "flash_attention_fwd", [(2, 512, 2, 32)], ["float32"]) is None
    hits = telemetry.counter("mxtpu_tune_cache_hit_total").labels(
        op="flash_attention_fwd").get()
    misses = telemetry.counter("mxtpu_tune_cache_miss_total").labels(
        op="flash_attention_fwd").get()
    assert hits == 1 and misses == 1
    evs = [e for e in flight.RECORDER.events()
           if e["kind"] == "tune_lookup"]
    assert len(evs) == 2
    assert evs[0]["hit"] is True \
        and evs[0]["config"] == {"block_q": 64, "block_k": 128}
    assert evs[1]["hit"] is False


# ------------------------------------------------- cache persistence

def test_cache_put_persist_merge_roundtrip(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TPU_TUNE_CACHE", str(tmp_path))
    autotune.put("flash_attention_fwd", [(2, 256, 2, 32)], ["float32"],
                 {"block_q": 64}, wall_s=2e-3,
                 heuristic_config={"block_q": 128},
                 heuristic_wall_s=3e-3)
    files = [f for f in os.listdir(tmp_path)
             if f.startswith("tunecache")]
    assert len(files) == 1
    entries, skipped = autotune.read_entries(str(tmp_path),
                                             strict=True)
    assert skipped == 0 and len(entries) == 1
    e = entries[0]
    assert e["config"] == {"block_q": 64} and e["wall_s"] == 2e-3
    assert e["heuristic_wall_s"] == 3e-3


def test_cache_merge_best_measured_wall_wins(tmp_path):
    """Multi-host/run composition: two files with the same key keep
    the better-measured config."""
    sig, payload = autotune.key_sig("flash_attention_fwd",
                                    [(2, 256, 2, 32)], ["float32"],
                                    backend="cpu")
    base = {"schema": autotune.SCHEMA, "sig": sig,
            "op": "flash_attention_fwd",
            "shapes": payload["shapes"], "dtypes": payload["dtypes"],
            "mesh": None, "backend": "cpu", "extra": None}
    (tmp_path / "tunecache-hostA.jsonl").write_text(json.dumps(
        dict(base, config={"block_q": 128}, wall_s=5e-3, ts=2.0)) + "\n")
    (tmp_path / "tunecache-hostB.jsonl").write_text(json.dumps(
        dict(base, config={"block_q": 64}, wall_s=1e-3, ts=1.0)) + "\n")
    entries, _ = autotune.read_entries(str(tmp_path))
    assert len(entries) == 1
    assert entries[0]["config"] == {"block_q": 64}   # faster, though older
    c = autotune.TuneCache()
    c.load(str(tmp_path))
    got = c.lookup("flash_attention_fwd", [(2, 256, 2, 32)], ["float32"],
                   backend="cpu")
    assert got["config"] == {"block_q": 64}


def test_full_shape_entry_displaces_proxy(tmp_path):
    """Review fix: an inline search measures at a reduced proxy shape
    (batch/heads -> 1), so its tiny walls must NEVER shadow a later
    full-shape re-tune of the same key under best-wall-wins."""
    key = ("flash_attention_fwd", [(32, 2176, 8, 64)], ["float32"])
    autotune.put(*key, {"block_q": 128, "block_k": 128}, wall_s=1e-4,
                 extra={"causal": False}, proxy=True)
    e = autotune.CACHE.lookup(*key, extra={"causal": False})
    assert e["proxy"] is True
    # the full-shape re-tune has a 100x larger (real) wall — it wins
    autotune.put(*key, {"block_q": 128, "block_k": 2176}, wall_s=1e-2,
                 extra={"causal": False})
    e = autotune.CACHE.lookup(*key, extra={"causal": False})
    assert e["config"] == {"block_q": 128, "block_k": 2176}
    assert not e.get("proxy")
    # and a later proxy commit can never displace it back
    autotune.put(*key, {"block_q": 64, "block_k": 64}, wall_s=1e-5,
                 extra={"causal": False}, proxy=True)
    e = autotune.CACHE.lookup(*key, extra={"causal": False})
    assert e["config"] == {"block_q": 128, "block_k": 2176}
    # within the same fidelity, best wall still wins
    autotune.put(*key, {"block_q": 64, "block_k": 2176}, wall_s=5e-3,
                 extra={"causal": False})
    e = autotune.CACHE.lookup(*key, extra={"causal": False})
    assert e["config"] == {"block_q": 64, "block_k": 2176}


def test_inline_search_commits_proxy_entry(monkeypatch, tmp_path):
    """A flash inline search (shrunk batch/heads) must mark its entry
    as proxy-measured."""
    monkeypatch.setenv("MXNET_TPU_TUNE_CACHE", str(tmp_path))
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "search")
    cfg = autotune.kernel_config("flash_attention_fwd",
                                 [(4, 256, 4, 32)], ["float32"],
                                 extra={"causal": False})
    assert cfg is not None
    e = autotune.CACHE.lookup("flash_attention_fwd", [(4, 256, 4, 32)],
                              ["float32"], extra={"causal": False})
    assert e["proxy"] is True and e["source"] == "inline-search"


# ------------------------------------------------ measurement runner

def test_measure_min_wall_and_chain():
    import jax.numpy as jnp
    a = np.ones((64, 64), np.float32)
    w1 = autotune.measure(lambda x: jnp.dot(x, x), (a,), repeats=3)
    assert w1 > 0
    w2 = autotune.measure(lambda x: jnp.dot(x, x), (a,), repeats=2,
                          chain=4)
    assert w2 > 0


def test_candidate_spaces_contain_heuristic():
    for t in (256, 2048, 2176, 3200):
        cands = autotune.candidate_flash_configs(t)
        heur = dict(zip(("block_q", "block_k"), pk._blocks(t)))
        assert any(c["block_q"] == heur["block_q"]
                   and c["block_k"] == heur["block_k"] for c in cands)
        for c in cands:
            assert t % c["block_q"] == 0 and t % c["block_k"] == 0
        # a causal call's own pair leads the list it is tuned from
        heur = pk._flash_blocks(t, 64, 64, 1, True)
        assert tuple(autotune.candidate_flash_configs(t, heur=heur)[0][k]
                     for k in ("block_q", "block_k")) == heur


def test_tune_flash_fwd_and_bwd_interpret(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TPU_TUNE_CACHE", str(tmp_path))
    for which in ("fwd", "bwd"):
        rep = autotune.tune_flash((1, 256, 1, 32), which=which,
                                  repeats=1, max_candidates=2,
                                  interpret=True)
        assert rep["best"]["wall_s"] <= rep["heuristic"]["wall_s"]
    entries, _ = autotune.read_entries(str(tmp_path), strict=True)
    assert {e["op"] for e in entries} \
        == {"flash_attention_fwd", "flash_attention_bwd"}


def test_flash_attention_correct_under_tuned_config(monkeypatch,
                                                    tmp_path):
    """The tuned override changes the grid, not the math: flash under
    a cached non-heuristic config still matches the jnp oracle."""
    monkeypatch.setenv("MXNET_TPU_TUNE_CACHE", str(tmp_path))
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "cache")
    autotune.put("flash_attention_fwd", [(2, 256, 2, 32)], ["float32"],
                 {"block_q": 64, "block_k": 128}, wall_s=1e-3,
                 extra={"causal": False})
    autotune.put("flash_attention_bwd", [(2, 256, 2, 32)], ["float32"],
                 {"block_q": 64, "block_k": 256}, wall_s=1e-3,
                 extra={"causal": False})
    import jax
    rng = np.random.RandomState(0)
    mk = lambda: rng.normal(0, 1, (2, 256, 2, 32)).astype(np.float32)
    q, k, v = mk(), mk(), mk()
    g = mk()
    out, vjp = jax.vjp(lambda q, k, v:
                       pk.flash_attention(q, k, v, False, True),
                       q, k, v)
    ref, ref_vjp = jax.vjp(lambda q, k, v:
                           pk._attention_jnp(q, k, v, False), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    for got, want in zip(vjp(g), ref_vjp(g)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=3e-5)
    assert autotune.summary()["hits"] >= 2


# --------------------------------------------------- inline search

def test_search_mode_inline_commits_on_miss(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TPU_TUNE_CACHE", str(tmp_path))
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "search")
    key = ("flash_attention_bwd", [(2, 256, 2, 32)], ["float32"])
    cfg = autotune.kernel_config(*key, extra={"causal": True})
    assert cfg is not None and "block_q" in cfg
    s = autotune.summary()
    assert s["misses"] == 1 and s["searches"] == 1
    # committed: the next lookup is a plain hit
    cfg2 = autotune.kernel_config(*key, extra={"causal": True})
    assert cfg2 == cfg
    assert autotune.summary()["hits"] == 1


# ------------------------------------------------ learned cost model

def _synthetic_records(factor, n=16, backend=None):
    backend = backend or costdb.backend_name()
    pf, pbw = costdb.peak_flops(backend), costdb.peak_bandwidth(backend)
    recs = []
    for i in range(n):
        flops = 10.0 ** (6 + i % 6)
        bytes_ = flops / 8.0
        att = costdb._attainable_s(flops, bytes_, pf, pbw)
        recs.append({"wall_s": att * factor, "flops": flops,
                     "bytes_accessed": bytes_, "block_config": None,
                     "backend": backend})
    return recs


def test_cost_model_fit_predict_save_load(tmp_path):
    recs = _synthetic_records(10.0)
    m = autotune.CostModel().fit(recs)
    assert m.stats["n"] == 16
    assert m.stats["r2"] > 0.99          # exact log-linear relation
    pred = m.predict_record(recs[0])
    assert pred == pytest.approx(recs[0]["wall_s"], rel=0.2)
    path = str(tmp_path / "model.json")
    m.save(path)
    m2 = autotune.CostModel.load(path)
    assert m2.predict_record(recs[3]) \
        == pytest.approx(m.predict_record(recs[3]))
    cal = m2.calibration(recs)
    assert cal["n"] == 16
    assert cal["geo_err_factor"] < 1.1
    # wrong schema rejected
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        json.dump({"schema": "nope/1"}, f)
    with pytest.raises(ValueError):
        autotune.CostModel.load(bad)


def test_cost_model_too_few_records():
    with pytest.raises(ValueError):
        autotune.CostModel().fit([])


def test_cost_model_geometry_means_for_configless_predict(tmp_path):
    """Review fix: a model fit on block-config-bearing records must
    predict a configless (MXG010 graph-level) query with the TRAINING
    MEAN geometry, not zeros — otherwise the prediction leaves the
    fitted distribution by an arbitrary factor."""
    recs = []
    for r in _synthetic_records(10.0):
        r = dict(r, block_config={"block_q": 128, "block_k": 512,
                                  "n_k": 4})
        recs.append(r)
    m = autotune.CostModel().fit(recs)
    with_cfg = m.predict(flops=1e8, bytes_accessed=1e7,
                         block_config={"block_q": 128, "block_k": 512,
                                       "n_k": 4})
    without = m.predict(flops=1e8, bytes_accessed=1e7)
    # mean-substitution makes the configless query land on the same
    # prediction as the (uniform) training geometry
    assert without == pytest.approx(with_cfg, rel=0.05)
    # and the means survive a save/load roundtrip
    path = str(tmp_path / "m.json")
    m.save(path)
    m2 = autotune.CostModel.load(path)
    assert m2.predict(flops=1e8, bytes_accessed=1e7) \
        == pytest.approx(without)


def test_mxg010_flags_predicted_slow_and_discriminates():
    from mxnet_tpu.analysis import verify_model
    slow = autotune.CostModel().fit(_synthetic_records(100.0))
    _net, rep = verify_model("lenet", cost_model=slow, slow_factor=3.0)
    findings = [d for d in rep if d.rule == "MXG010"]
    assert findings, "pathological model must flag the graph"
    assert findings[0].severity == "warning"
    assert "roofline-attainable" in findings[0].message
    good = autotune.CostModel().fit(_synthetic_records(1.0))
    _net, rep = verify_model("lenet", cost_model=good, slow_factor=3.0)
    assert not [d for d in rep if d.rule == "MXG010"]
    # no cost model -> rule never runs
    _net, rep = verify_model("lenet")
    assert not [d for d in rep if d.rule == "MXG010"]


def test_infer_node_shapes():
    from mxnet_tpu import models
    from mxnet_tpu.analysis import infer_node_shapes
    net = models.get_model("mlp", num_classes=10)
    topo, shapes = infer_node_shapes(net, {"data": (2, 784),
                                           "softmax_label": (2,)})
    assert len(shapes) == len(topo)
    out_shapes = [s[0] for s in shapes.values()]
    assert (2, 10) in out_shapes


# --------------------------------------------------------- consumers

def test_perf_top_suggest(monkeypatch, tmp_path):
    ptop = _load_tool("perf_top")
    db = tmp_path / "db"
    db.mkdir()
    monkeypatch.setenv("MXNET_TPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("MXNET_TPU_PEAK_BW", "1e11")
    costdb.record("kernel", "flash_attention_bwd", wall_s=5e-3,
                  flops=1e9, bytes_accessed=1e6,
                  shapes=[(2, 256, 2, 32)], dtypes=["float32"],
                  block_config={"block_q": 128}, backend="cpu")
    costdb.flush(str(db))
    cache = tmp_path / "cache"
    autotune.CACHE.clear()
    monkeypatch.setenv("MXNET_TPU_TUNE_CACHE", str(cache))
    autotune.put("flash_attention_bwd", [(2, 256, 2, 32)], ["float32"],
                 {"block_q": 64}, wall_s=1e-3,
                 heuristic_config={"block_q": 128}, heuristic_wall_s=5e-3,
                 backend="cpu")
    records, _ = costdb.read_records(str(db))
    ranked = ptop.rank(records)
    entries = ptop._cache_entries(str(cache))
    rows = ptop.suggest(ranked, entries)
    assert len(rows) == 1
    r = rows[0]
    assert r["status"] == "better-available"
    assert r["tuned_config"] == {"block_q": 64}
    assert r["expected_delta_frac"] == pytest.approx(0.8)
    # an untuned record reports the miss, not a crash
    costdb.record("kernel", "flash_attention_fwd", wall_s=1e-3,
                  flops=1e9, bytes_accessed=1e6,
                  shapes=[(1, 999, 1, 32)], dtypes=["float32"],
                  block_config={"block_q": 128}, backend="cpu")
    rows = ptop.suggest(ptop.rank(costdb.records()), entries)
    assert any(x["status"] == "untuned" for x in rows)


def test_autotune_cli_tune_then_all_hits(monkeypatch, tmp_path):
    at = _load_tool("autotune")
    cache = str(tmp_path / "cache")
    db = str(tmp_path / "db")
    argv = ["--op", "flash_fwd", "--shapes", "1x256x1x32",
            "--repeats", "1", "--max-candidates", "2", "--interpret",
            "--cache", cache, "--costdb", db, "--json"]
    assert at.main(argv) == 0
    autotune.reload_cache()
    entries, _ = autotune.read_entries(cache, strict=True)
    assert len(entries) == 1
    # second run: all cache hits, nothing searched
    assert at.main(argv) == 0
    files = [f for f in os.listdir(cache) if f.endswith(".jsonl")]
    lines = sum(1 for f in files
                for _line in open(os.path.join(cache, f)))
    assert lines == 1          # no re-commit on the cached run
    # report over the cache + costdb
    assert at.main(["--report", "--cache", cache, "--costdb", db,
                    "--json"]) == 0


def test_bench_summary_block():
    s = autotune.summary()
    for key in ("mode", "cache", "entries", "hits", "misses",
                "searches", "tuned"):
        assert key in s
