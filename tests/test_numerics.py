"""Training-health numerics (telemetry.numerics, docs/api/telemetry.md).

Covers: in-graph stat oracles vs numpy, sampling cadence (the UNSAMPLED
step program's jaxpr is equation-identical to the numerics-off one),
anomaly rules (nonfinite / grad_spike / dead_grad) incl. the strict-mode
raise + flight dump, NaN/Inf provenance naming a seeded node, the
ledger write/read/schema-reject roundtrip, tools/numdiff.py localizing a
seeded single-tensor divergence to the exact step, a fused-vs-unfused
ledger comparison that passes clean on a zoo model, the jit-safe
Monitor default (eager=True opt-in), the metric-layer non-finite guard,
and the out-of-range-label regression (parallel/trainer.py loss
mode="clip").
"""
import importlib.util
import json
import math
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models, resilience, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.telemetry import numerics
from mxnet_tpu.parallel import ShardedTrainer, build_mesh


def _load_tool(name):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, "tools", "%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    for k in ("MXNET_TPU_NUMERICS_EVERY", "MXNET_TPU_NUMERICS_STRICT",
              "MXNET_TPU_NUMERICS_LEDGER", "MXNET_TPU_NUMERICS_SPIKE",
              "MXNET_TPU_NUMERICS_DEAD", "MXNET_TPU_FLIGHT_DIR",
              "MXNET_TPU_FAULTS", "MXNET_TPU_TELEMETRY_JSONL"):
        monkeypatch.delenv(k, raising=False)
    resilience.clear_faults()
    telemetry.reset()
    yield
    resilience.clear_faults()
    telemetry.reset()


def _mlp_trainer(**kw):
    net = models.get_model("mlp", num_classes=10)
    kw.setdefault("dtype", "float32")
    kw.setdefault("seed", 0)
    return ShardedTrainer(net, build_mesh(tp=1),
                          data_shapes={"data": (8, 64)},
                          label_shapes={"softmax_label": (8,)}, **kw)


def _batch(seed=3, bad=False, labels_hi=10):
    rng = np.random.RandomState(seed)
    x = rng.uniform(-1, 1, (8, 64)).astype(np.float32)
    if bad:
        x[0, 0] = np.nan
    return {"data": x,
            "softmax_label": rng.randint(0, labels_hi, 8)
            .astype(np.float32)}


# ------------------------------------------------------- stat oracles

def test_tensor_stats_vs_numpy_oracle():
    import jax
    rng = np.random.RandomState(0)
    x = rng.uniform(-2, 2, (7, 13)).astype(np.float32)
    x[0, 0] = np.nan
    x[1, 2] = np.inf
    x[3, :5] = 0.0
    st = jax.device_get(numerics.tensor_stats(x, digest=True))
    finite = x[np.isfinite(x)]
    assert st["nonfinite"] == 2
    assert abs(st["l2"] - np.sqrt((finite ** 2).sum())) < 1e-3
    assert abs(st["mean_abs"]
               - np.abs(np.where(np.isfinite(x), x, 0)).mean()) < 1e-6
    assert abs(st["max_abs"] - np.abs(finite).max()) < 1e-6
    assert abs(st["zero_frac"] - (x == 0).mean()) < 1e-6
    # digest oracle: wrapping uint32 sum of the float32 bit patterns
    want = int(x.view(np.uint32).astype(np.uint64).sum() % (1 << 32))
    assert int(st["digest"]) == want


def test_tensor_stats_inside_jit_and_digest_sensitivity():
    import jax
    import jax.numpy as jnp
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    st = jax.jit(lambda a: numerics.tensor_stats(a, digest=True))(x)
    st = jax.device_get(st)
    assert abs(st["l2"] - np.sqrt((x ** 2).sum())) < 1e-4
    y = x.copy()
    y[2, 3] = np.float32(11.000002)   # a few-ulp flip
    assert y[2, 3] != x[2, 3]
    d2 = int(jax.device_get(numerics.value_digest(jnp.asarray(y))))
    assert d2 != int(st["digest"])


# -------------------------------------------------- sampling cadence

def test_unsampled_step_program_unchanged(monkeypatch):
    """The tentpole no-overhead guarantee: with numerics ENABLED, the
    program dispatched on unsampled steps has exactly the jaxpr of the
    numerics-off step (the stats variant is a separate compile)."""
    import jax
    import jax.numpy as jnp

    def eqn_count(trainer):
        batch = trainer.put_batch(_batch())
        jaxpr = jax.make_jaxpr(trainer._py_step)(
            trainer.params, trainer.opt_state, trainer.aux, batch,
            jax.random.PRNGKey(0), jnp.float32(0.1), jnp.float32(1.0))
        return len(jaxpr.jaxpr.eqns)

    monkeypatch.delenv("MXNET_TPU_NUMERICS_EVERY", raising=False)
    off = eqn_count(_mlp_trainer())
    monkeypatch.setenv("MXNET_TPU_NUMERICS_EVERY", "2")
    tr = _mlp_trainer()
    on = eqn_count(tr)
    assert on == off
    # and the stats VARIANT is a strictly larger program
    tr._build_step(collect_stats=True)
    batch = tr.put_batch(_batch())
    jaxpr = jax.make_jaxpr(tr._py_step_stats)(
        tr.params, tr.opt_state, tr.aux, batch,
        jax.random.PRNGKey(0), jnp.float32(0.1), jnp.float32(1.0))
    assert len(jaxpr.jaxpr.eqns) > on


def test_sampling_cadence_and_ledger(monkeypatch, tmp_path):
    led = str(tmp_path / "run.ledger")
    monkeypatch.setenv("MXNET_TPU_NUMERICS_EVERY", "2")
    monkeypatch.setenv("MXNET_TPU_NUMERICS_LEDGER", led)
    tr = _mlp_trainer()
    batch = _batch()
    for _ in range(5):
        float(tr.step(batch))
    recs = numerics.read_ledger(led)
    assert [r["step"] for r in recs] == [1, 3, 5]
    s = numerics.summary()
    assert s["sampled_steps"] == 3 and s["every"] == 2
    assert s["last_grad_norm"] > 0
    # gauges published
    g = telemetry.gauge("mxtpu_grad_global_norm")
    assert g.get() == pytest.approx(s["last_grad_norm"], rel=1e-6)
    norm = telemetry.gauge("mxtpu_tensor_norm")
    assert norm.labels(tensor="fc1_weight", kind="grad").get() > 0
    assert norm.labels(tensor="fc1_weight", kind="param").get() > 0
    # every record carries the full stat bundle + digests
    for r in recs:
        st = r["tensors"]["param/fc1_weight"]
        for k in ("l2", "mean_abs", "max_abs", "nonfinite",
                  "zero_frac", "digest"):
            assert k in st
        assert r["grad_norm"] > 0 and isinstance(r["digest"], int)


# ------------------------------------------------------ anomaly rules

def test_nonfinite_anomaly_nonstrict_warns_not_raises(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_NUMERICS_EVERY", "1")
    tr = _mlp_trainer()
    float(tr.step(_batch()))
    tr.step(_batch(bad=True))    # NaN data: detected, not fatal
    c = telemetry.counter("mxtpu_numerics_anomalies_total")
    assert c.labels(rule="nonfinite").get() >= 1
    bad = telemetry.counter("mxtpu_nonfinite_total")
    total = sum(bad.samples().values())
    assert total > 0
    evs = [e for e in telemetry.flight.events()
           if e["kind"] == "numerics_anomaly"]
    assert any(e["rule"] == "nonfinite" for e in evs)


def test_strict_mode_raises_with_flight_dump_and_provenance(
        monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TPU_NUMERICS_EVERY", "1")
    monkeypatch.setenv("MXNET_TPU_NUMERICS_STRICT", "1")
    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", str(tmp_path))
    tr = _mlp_trainer()
    float(tr.step(_batch()))
    with pytest.raises(MXNetError) as ei:
        tr.step(_batch(bad=True))
    msg = str(ei.value)
    assert "nonfinite" in msg and "grad/" in msg
    assert "producing node" in msg
    dumps = [f for f in os.listdir(str(tmp_path))
             if f.startswith("flight-") and f.endswith(".json")]
    assert dumps, "strict stop left no flight dump"
    provs = []
    for name in dumps:
        with open(os.path.join(str(tmp_path), name)) as f:
            doc = json.load(f)
        for ev in doc["events"]:
            if ev.get("kind") == "numerics_anomaly" and \
                    ev.get("provenance"):
                provs.append(ev["provenance"]["node"])
    assert provs and all(isinstance(p, str) and p for p in provs)


def test_provenance_names_seeded_nan_node_via_fault_seam(monkeypatch):
    """The numerics.nonfinite resilience seam poisons the data input;
    the eager replay must name the FIRST op node downstream of it."""
    monkeypatch.setenv("MXNET_TPU_NUMERICS_EVERY", "1")
    tr = _mlp_trainer()
    float(tr.step(_batch()))
    monkeypatch.setenv("MXNET_TPU_FAULTS", "numerics.nonfinite:n=1")
    tr.step(_batch())
    evs = [e for e in telemetry.flight.events()
           if e["kind"] == "numerics_anomaly" and e.get("provenance")]
    assert evs, "no anomaly event carries provenance"
    node = evs[0]["provenance"]["node"]
    # the MLP's first op after the poisoned data input is its flatten
    # (auto-named flattenN — the counter is process-global)
    import re
    assert re.fullmatch(r"flatten\d+_output", node), node
    assert evs[0]["provenance"]["nonfinite"] > 0


def test_grad_spike_rule_fires_on_ewma_breakout(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_NUMERICS_SPIKE", "10")

    def payload(gn):
        return {"tensors": {"grad/w": {"l2": gn, "mean_abs": 0.1,
                                       "max_abs": 1.0, "nonfinite": 0,
                                       "zero_frac": 0.0}},
                "grad_norm": np.float32(gn)}

    for i, gn in enumerate((1.0, 1.1, 0.9)):
        out = numerics.process_step(payload(gn), step=i + 1,
                                    program="test.step")
        assert "anomalies" not in out
    out = numerics.process_step(payload(500.0), step=4,
                                program="test.step")
    rules = [a["rule"] for a in out["anomalies"]]
    assert "grad_spike" in rules
    c = telemetry.counter("mxtpu_numerics_anomalies_total")
    assert c.labels(rule="grad_spike").get() == 1
    # the spike did NOT fold into the EWMA: a second spike still fires
    out = numerics.process_step(payload(500.0), step=5,
                                program="test.step")
    assert "grad_spike" in [a["rule"] for a in out["anomalies"]]


def test_dead_grad_rule(monkeypatch):
    p = {"tensors": {"grad/w": {"l2": 0.0, "mean_abs": 0.0,
                                "max_abs": 0.0, "nonfinite": 0,
                                "zero_frac": 1.0},
                     "param/w": {"l2": 1.0, "mean_abs": 0.1,
                                 "max_abs": 1.0, "nonfinite": 0,
                                 "zero_frac": 1.0}},
         "grad_norm": 0.0}
    out = numerics.process_step(p, step=1, program="test.dead")
    anomalies = out["anomalies"]
    assert [a["rule"] for a in anomalies] == ["dead_grad"]
    # only grad/* tensors count as dead; the all-zero PARAM does not
    assert anomalies[0]["tensors"] == ["grad/w"]


# ------------------------------------------------------------- ledger

def test_ledger_read_rejects_wrong_schema(tmp_path):
    bad = tmp_path / "notledger.jsonl"
    bad.write_text(json.dumps({"schema": "mxtpu-flight/1",
                               "events": []}) + "\n")
    with pytest.raises(ValueError):
        numerics.read_ledger(str(bad))
    with pytest.raises(ValueError):
        numerics.read_ledger(str(tmp_path / "missing.jsonl"))
    # malformed record (schema but no tensors) also rejected
    bad2 = tmp_path / "malformed.jsonl"
    bad2.write_text(json.dumps({"schema": numerics.SCHEMA,
                                "step": 1}) + "\n")
    with pytest.raises(ValueError):
        numerics.read_ledger(str(bad2))


def test_ledger_roundtrip_and_inline_form(monkeypatch, tmp_path):
    led = tmp_path / "a.jsonl"
    monkeypatch.setenv("MXNET_TPU_NUMERICS_LEDGER", str(led))
    p = {"tensors": {"grad/w": {"l2": 1.0, "mean_abs": 0.1,
                                "max_abs": 1.0, "nonfinite": 0,
                                "zero_frac": 0.0}},
         "grad_norm": 1.0}
    numerics.process_step(p, step=7, program="test.rt")
    recs = numerics.read_ledger(str(led))
    assert len(recs) == 1 and recs[0]["step"] == 7
    assert recs[0]["schema"] == numerics.SCHEMA
    # the inline (step-JSONL) carrier form parses too
    inline = tmp_path / "steps.jsonl"
    inline.write_text(json.dumps({"step": 7, "step_time_s": 0.1,
                                  "numerics": recs[0]}) + "\n")
    recs2 = numerics.read_ledger(str(inline))
    assert recs2 == recs


def _write_ledger(path, steps, mutate=None):
    """Synthesize a ledger; ``mutate(step, tensors)`` may edit."""
    with open(path, "w") as f:
        for step in steps:
            tensors = {
                "param/w": {"l2": 2.0, "mean_abs": 0.2, "max_abs": 1.0,
                            "nonfinite": 0, "zero_frac": 0.0,
                            "digest": 100 + step},
                "grad/w": {"l2": 1.0, "mean_abs": 0.1, "max_abs": 0.5,
                           "nonfinite": 0, "zero_frac": 0.0,
                           "digest": 200 + step},
            }
            if mutate:
                mutate(step, tensors)
            f.write(json.dumps({"schema": numerics.SCHEMA,
                                "step": step, "rank": 0,
                                "program": "t", "grad_norm": 1.0,
                                "digest": 0, "tensors": tensors})
                    + "\n")


def test_numdiff_localizes_seeded_divergence(tmp_path):
    a = str(tmp_path / "a.ledger")
    b = str(tmp_path / "b.ledger")
    _write_ledger(a, range(1, 9))

    def mutate(step, tensors):
        if step >= 5:
            tensors["grad/w"]["l2"] = 3.0     # 3x off from step 5 on
            tensors["grad/w"]["digest"] += 1
    _write_ledger(b, range(1, 9), mutate=mutate)
    numdiff = _load_tool("numdiff")
    rc = numdiff.main([a, b])
    assert rc == 1
    recs_a = numerics.read_ledger(a)
    recs_b = numerics.read_ledger(b)
    res = numerics.compare_ledgers(recs_a, recs_b)
    assert res["divergence"]["step"] == 5
    assert res["divergence"]["tensor"] == "grad/w"
    assert res["divergence"]["rel"] > 0.1
    # identical ledgers: bit-clean, exit 0
    assert numdiff.main([a, a]) == 0
    res = numerics.compare_ledgers(recs_a, recs_a)
    assert res["bit_clean"] and res["divergence"] is None
    # --strict-bits flips a within-tolerance digest skew to exit 1
    c = str(tmp_path / "c.ledger")

    def bitflip(step, tensors):
        tensors["grad/w"]["digest"] += 1      # stats identical
    _write_ledger(c, range(1, 9), mutate=bitflip)
    assert numdiff.main([a, c]) == 0
    assert numdiff.main([a, c, "--strict-bits"]) == 1
    # disjoint step sets: usage error
    d = str(tmp_path / "d.ledger")
    _write_ledger(d, range(100, 103))
    assert numdiff.main([a, d]) == 2


def test_fused_vs_unfused_ledger_clean_on_zoo_model(monkeypatch,
                                                    tmp_path):
    """Acceptance: the fused path's numerics stay within tolerance of
    the unfused reference on a zoo model — continuously auditable
    lowering (Glow's verification story), not a one-shot unit test."""
    monkeypatch.setenv("MXNET_TPU_NUMERICS_EVERY", "1")

    def run(ledger, fuse):
        os.environ["MXNET_TPU_NUMERICS_LEDGER"] = ledger
        numerics.reset()
        tr = _mlp_trainer(fuse_blocks=fuse)
        batch = _batch()
        for _ in range(3):
            float(tr.step(batch))
        if fuse:
            # the fused leg really fused: block/* entries in its ledger
            recs = numerics.read_ledger(ledger)
            assert any(n.startswith("block/")
                       for n in recs[0]["tensors"])

    a = str(tmp_path / "unfused.ledger")
    b = str(tmp_path / "fused.ledger")
    run(a, fuse=False)
    run(b, fuse=True)
    monkeypatch.delenv("MXNET_TPU_NUMERICS_LEDGER", raising=False)
    res = numerics.compare_ledgers(numerics.read_ledger(a),
                                   numerics.read_ledger(b), rtol=1e-3)
    assert res["steps_compared"] == 3
    assert res["divergence"] is None, res["divergence"]
    assert res["only_b"] > 0        # the uncompared block/* entries
    numdiff = _load_tool("numdiff")
    assert numdiff.main([a, b, "--rtol", "1e-3"]) == 0


# ------------------------------------- run_top / distview integration

def test_run_timeline_carries_grad_norm_and_digest(tmp_path):
    from mxnet_tpu.telemetry import distview
    base = str(tmp_path / "steps.jsonl")
    agg = distview.RunAggregator(base, num_ranks=2)
    for step in (1, 2):
        for rank, gn in ((0, 1.0), (1, 1.0 if step == 1 else 9.0)):
            agg.feed(rank, {"step": step, "step_time_s": 0.1,
                            "ts": step + rank / 10.0,
                            "grad_norm": gn,
                            "digest": 42 if step == 1 else 42 + rank})
    agg.close()
    recs = distview.read_run_timeline(base + ".run")
    steps = [r for r in recs if r.get("kind") == "step"]
    assert steps[0].get("grad_skew") == 0.0
    assert steps[1]["grad_skew"] == pytest.approx(8.0)
    assert "digest_mismatch" not in steps[0]
    assert steps[1]["digest_mismatch"] is True
    summary = distview.summarize_run(recs)
    assert summary["grad_skew_max"] == pytest.approx(8.0)
    assert summary["digest_mismatch_steps"] == 1
    assert summary["per_rank"]["1"]["grad_norm_last"] == 9.0
    assert summary["per_rank"]["1"]["digest_last"] == 43
    # run_top renders the numerics columns
    run_top = _load_tool("run_top")
    dash = run_top.format_dashboard(recs, summary)
    assert "grad norm" in dash and "DIGEST MISMATCH" in dash
    text = run_top.format_summary(summary)
    assert "grad-norm skew" in text and "grad_norm=9" in text


def test_step_jsonl_carries_numerics_pair(monkeypatch, tmp_path):
    path = str(tmp_path / "steps.jsonl")
    monkeypatch.setenv("MXNET_TPU_TELEMETRY_JSONL", path)
    monkeypatch.setenv("MXNET_TPU_NUMERICS_EVERY", "2")
    tr = _mlp_trainer()
    batch = _batch()
    for _ in range(2):
        float(tr.step(batch))
    recs = [json.loads(l) for l in open(path)]
    assert "grad_norm" in recs[0] and "digest" in recs[0]   # sampled
    assert "grad_norm" not in recs[1]                       # unsampled
    # with no dedicated ledger file, the step-log IS the ledger: the
    # full record rides inline and numdiff/read_ledger accept the file
    assert recs[0]["numerics"]["schema"] == numerics.SCHEMA
    led = numerics.read_ledger(path)
    assert len(led) == 1 and led[0]["step"] == 1
    assert "param/fc1_weight" in led[0]["tensors"]
    # a dedicated ledger file suppresses the inline duplicate
    monkeypatch.setenv("MXNET_TPU_NUMERICS_LEDGER",
                       str(tmp_path / "own.ledger"))
    float(tr.step(batch))
    recs = [json.loads(l) for l in open(path)]
    assert "grad_norm" in recs[2] and "numerics" not in recs[2]


def test_compare_ledgers_flags_nonfinite_count_mismatch(tmp_path):
    """NaNs appearing in one run and not the other must DIVERGE even
    when the finite-masked l2/mean stats agree within tolerance."""
    a = str(tmp_path / "a.ledger")
    b = str(tmp_path / "b.ledger")
    _write_ledger(a, range(1, 4))

    def mutate(step, tensors):
        if step == 2:
            tensors["grad/w"]["nonfinite"] = 7   # stats left identical
    _write_ledger(b, range(1, 4), mutate=mutate)
    res = numerics.compare_ledgers(numerics.read_ledger(a),
                                   numerics.read_ledger(b))
    assert res["divergence"] == {"step": 2, "tensor": "grad/w",
                                 "stat": "nonfinite", "a": 0, "b": 7,
                                 "rel": 1.0}
    numdiff = _load_tool("numdiff")
    assert numdiff.main([a, b]) == 1


def test_compare_ledgers_max_abs_and_zero_frac(tmp_path):
    """Single-element corruption (max_abs jumps, l2 barely moves) and
    flush-to-zero drift (zero_frac jumps) must DIVERGE; zero_frac
    compares absolutely so a borderline element flip (0 vs 1e-7)
    stays within tolerance."""
    a = str(tmp_path / "a.ledger")
    _write_ledger(a, range(1, 4))

    b = str(tmp_path / "b.ledger")

    def spike(step, tensors):
        if step == 2:
            tensors["grad/w"]["max_abs"] = 5.0    # l2/mean unchanged
    _write_ledger(b, range(1, 4), mutate=spike)
    res = numerics.compare_ledgers(numerics.read_ledger(a),
                                   numerics.read_ledger(b))
    assert res["divergence"]["stat"] == "max_abs"
    assert res["divergence"]["step"] == 2

    c = str(tmp_path / "c.ledger")

    def flush(step, tensors):
        tensors["grad/w"]["zero_frac"] = 0.5      # flush-to-zero
    _write_ledger(c, range(1, 4), mutate=flush)
    res = numerics.compare_ledgers(numerics.read_ledger(a),
                                   numerics.read_ledger(c))
    assert res["divergence"]["stat"] == "zero_frac"

    d = str(tmp_path / "d.ledger")

    def borderline(step, tensors):
        tensors["grad/w"]["zero_frac"] = 1e-7     # one element of 10M
    _write_ledger(d, range(1, 4), mutate=borderline)
    res = numerics.compare_ledgers(numerics.read_ledger(a),
                                   numerics.read_ledger(d))
    assert res["divergence"] is None


def test_grad_spike_ewma_scoped_per_caller():
    """Two step streams with different typical norms must not share a
    baseline: model B's healthy first step would spike against model
    A's tiny EWMA."""
    def payload(gn):
        return {"tensors": {}, "grad_norm": np.float32(gn)}

    for step in (1, 2):
        out = numerics.process_step(payload(0.01), step=step,
                                    program="trainer.step",
                                    scope=("trainer.step", "A"))
        assert "anomalies" not in out
    out = numerics.process_step(payload(1.0), step=1,
                                program="trainer.step",
                                scope=("trainer.step", "B"))
    assert "anomalies" not in out, "scope B tripped on scope A's EWMA"


def test_run_top_digest_columns_survive_all_nan_run(tmp_path):
    """An all-NaN run omits its grad norms from the step records but
    keeps digests — the dashboard must still show the numerics columns
    and the digest-mismatch flag."""
    from mxnet_tpu.telemetry import distview
    base = str(tmp_path / "steps.jsonl")
    agg = distview.RunAggregator(base, num_ranks=2)
    for rank in (0, 1):
        agg.feed(rank, {"step": 1, "step_time_s": 0.1,
                        "ts": 1.0 + rank, "digest": 7 + rank})
    agg.close()
    recs = distview.read_run_timeline(base + ".run")
    summary = distview.summarize_run(recs)
    assert summary["grad_skew_max"] is None
    assert summary["digest_mismatch_steps"] == 1
    run_top = _load_tool("run_top")
    dash = run_top.format_dashboard(recs, summary)
    assert "digest" in dash and "DIGEST MISMATCH" in dash
    text = run_top.format_summary(summary)
    assert "DIGEST MISMATCH" in text


def test_dead_grad_zero_threshold_disables(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_NUMERICS_DEAD", "0")
    p = {"tensors": {"grad/w": {"l2": 0.0, "mean_abs": 0.0,
                                "max_abs": 0.0, "nonfinite": 0,
                                "zero_frac": 1.0}},
         "grad_norm": 0.0}
    out = numerics.process_step(p, step=1, program="test.deadoff")
    assert "anomalies" not in out


def test_nan_seam_defers_to_a_sampled_step(monkeypatch):
    """An armed numerics.nonfinite fault on an unsampled step must NOT
    fire there (the poison would land where detection never runs): the
    seam is evaluated only on sampled steps, so the injection lands on
    the next sampled one and is detected."""
    monkeypatch.setenv("MXNET_TPU_NUMERICS_EVERY", "2")   # samples 1,3
    tr = _mlp_trainer()
    batch = _batch()
    float(tr.step(batch))                                 # step 1
    monkeypatch.setenv("MXNET_TPU_FAULTS", "numerics.nonfinite:n=1")
    float(tr.step(batch))                                 # step 2: unsampled
    assert telemetry.counter("mxtpu_numerics_anomalies_total")
    c = telemetry.counter("mxtpu_numerics_anomalies_total")
    assert c.labels(rule="nonfinite").get() == 0          # not fired yet
    tr.step(batch)                                        # step 3: sampled
    assert c.labels(rule="nonfinite").get() >= 1
    assert resilience.fault_stats()["numerics.nonfinite"]["hits"] == 1


def test_run_steps_warns_once_and_stays_unsampled(monkeypatch, caplog):
    monkeypatch.setenv("MXNET_TPU_NUMERICS_EVERY", "1")
    tr = _mlp_trainer()
    import logging
    with caplog.at_level(logging.WARNING):
        tr.run_steps(_batch(), 3)
        tr.run_steps(_batch(), 3)
    warns = [r for r in caplog.records
             if "run_steps chains are not sampled" in r.getMessage()]
    assert len(warns) == 1
    assert numerics.summary()["sampled_steps"] == 0


def test_grad_spike_zero_factor_disables(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_NUMERICS_SPIKE", "0")

    def payload(gn):
        return {"tensors": {}, "grad_norm": np.float32(gn)}

    numerics.process_step(payload(1.0), step=1, program="test.spikeoff")
    out = numerics.process_step(payload(1e6), step=2,
                                program="test.spikeoff")
    assert "anomalies" not in out


def test_sampling_phased_on_global_step_across_resume(monkeypatch,
                                                      tmp_path):
    """A resumed run must sample the SAME global step numbers as a
    from-scratch one, or pre- vs post-resume ledgers share no steps
    and the headline numdiff comparison exits 2."""
    monkeypatch.setenv("MXNET_TPU_NUMERICS_EVERY", "5")
    led = str(tmp_path / "resumed.ledger")
    monkeypatch.setenv("MXNET_TPU_NUMERICS_LEDGER", led)
    tr = _mlp_trainer()
    tr._resume_epoch = 7        # as load_checkpoint(epoch=7) leaves it
    batch = _batch()
    for _ in range(6):          # global steps 8..13
        float(tr.step(batch))
    recs = numerics.read_ledger(led)
    # cadence 5 phased globally samples 11 (= 1 + 2*5), not 8
    assert [r["step"] for r in recs] == [11]


def test_stats_monitor_publishes_node_norm_gauge():
    data = mx.sym.Variable("data")
    net = mx.sym.sigmoid(data, name="sg")
    ex = net.simple_bind(mx.cpu(), data=(2, 2))
    mon = mx.Monitor(1, pattern=".*")
    mon.install(ex)
    mon.tic()
    ex.forward(data=np.zeros((2, 2), np.float32))
    mon.toc()
    g = telemetry.gauge("mxtpu_tensor_norm")
    # l2 of four 0.5s = sqrt(4 * 0.25) = 1.0
    assert g.labels(tensor="sg_output", kind="node").get() == \
        pytest.approx(1.0, rel=1e-5)


def test_ledger_lines_stay_strict_json_under_nan(monkeypatch, tmp_path):
    led = tmp_path / "nan.ledger"
    monkeypatch.setenv("MXNET_TPU_NUMERICS_LEDGER", str(led))
    p = {"tensors": {"grad/w": {"l2": 0.0, "mean_abs": 0.0,
                                "max_abs": 0.0, "nonfinite": 4,
                                "zero_frac": 0.0}},
         "grad_norm": float("nan"), "loss": float("inf")}
    numerics.process_step(p, step=1, program="test.nan")
    line = led.read_text().strip()
    assert "NaN" not in line and "Infinity" not in line
    rec = json.loads(line)             # strict-JSON parseable
    assert rec["grad_norm"] is None and rec["loss"] is None
    assert rec["tensors"]["grad/w"]["nonfinite"] == 4


# --------------------------------------------- jit-safe Monitor path

def test_monitor_default_is_jit_safe_stats_path():
    data = mx.sym.Variable("data")
    net = mx.sym.sigmoid(data, name="sig")
    ex = net.simple_bind(mx.cpu(), data=(2, 2))
    mon = mx.Monitor(1, pattern=".*")
    assert mon.eager is False
    mon.install(ex)
    assert ex._stats_cb is not None and ex._monitor_callback is None
    mon.tic()
    ex.forward(data=np.full((2, 2), -0.5, np.float32))
    res = mon.toc()
    assert any(k == "sig_output" for (_n, k, _v) in res)
    g = telemetry.gauge("mxtpu_monitor_stat").labels(
        tensor="sig_output")
    # mean |sigmoid(-0.5)| = sigmoid(-0.5)
    assert g.get() == pytest.approx(1 / (1 + math.exp(0.5)), rel=1e-5)
    # deactivated interval: the PLAIN forward program serves the call
    ex.forward(data=np.zeros((2, 2), np.float32))
    assert True  # no stats queued while inactive
    assert mon.toc() == []


def test_monitor_custom_stat_func_selects_eager():
    data = mx.sym.Variable("data")
    net = mx.sym.sigmoid(data, name="sig")
    ex = net.simple_bind(mx.cpu(), data=(2, 2))
    mon = mx.Monitor(1, stat_func=lambda x: x.asnumpy().max(),
                     pattern=".*")
    assert mon.eager is True
    mon.install(ex)
    assert ex._monitor_callback is not None
    mon.tic()
    ex.forward(data=np.zeros((2, 2), np.float32))
    res = mon.toc()
    assert any(k == "sig_output" for (_n, k, _v) in res)


def test_stats_monitor_counts_nonfinite_with_node_provenance():
    data = mx.sym.Variable("data")
    net = mx.sym.log(data, name="lg")       # log(0) = -inf
    ex = net.simple_bind(mx.cpu(), data=(2, 2))
    mon = mx.Monitor(1, pattern=".*")
    mon.install(ex)
    mon.tic()
    ex.forward(data=np.zeros((2, 2), np.float32))
    mon.toc()
    bad = telemetry.counter("mxtpu_nonfinite_total")
    assert bad.labels(tensor="node/lg_output").get() == 4
    evs = [e for e in telemetry.flight.events()
           if e["kind"] == "numerics_anomaly"]
    assert evs and evs[0]["provenance"]["node"] == "lg_output"


# ------------------------------------------------- metric satellites

def test_metric_nonfinite_update_counted_not_poisoning():
    m = mx.metric.MSE()
    m.update([mx.nd.array([1.0, 2.0])],
             [mx.nd.array([[1.0], [2.0]])])
    m.update([mx.nd.array([1.0, 2.0])],
             [mx.nd.array([[np.nan], [2.0]])])
    name, val = m.get()
    assert math.isfinite(val)       # the NaN batch did not poison it
    assert m.num_nonfinite == 1
    c = telemetry.counter("mxtpu_nonfinite_total")
    assert c.labels(tensor="metric/mse").get() == 1
    m.reset()
    assert m.num_nonfinite == 0


def test_metric_crossentropy_inf_guarded():
    m = mx.metric.CrossEntropy(eps=0.0)
    m.update([mx.nd.array([0.0])], [mx.nd.array([[1.0, 0.0]])])
    m.update([mx.nd.array([1.0])], [mx.nd.array([[1.0, 0.0]])])  # -log 0
    _, val = m.get()
    assert math.isfinite(val)
    assert m.num_nonfinite == 1


def test_out_of_range_label_loss_stays_finite(monkeypatch):
    """Regression for the mode='clip' note at parallel/trainer.py
    (jit's default fill mode would turn an out-of-range label into a
    NaN loss and poison the metric): labels >= num_classes must leave
    the monitoring loss finite AND trip no nonfinite anomaly."""
    monkeypatch.setenv("MXNET_TPU_NUMERICS_EVERY", "1")
    tr = _mlp_trainer()
    batch = _batch()
    batch["softmax_label"] = np.full((8,), 99.0, np.float32)  # >= 10
    loss = float(tr.step(batch))
    assert math.isfinite(loss)
    c = telemetry.counter("mxtpu_numerics_anomalies_total")
    assert c.labels(rule="nonfinite").get() == 0


# ----------------------------------------------------- misc contracts

def test_sampled_cadence_helper():
    os.environ["MXNET_TPU_NUMERICS_EVERY"] = "3"
    try:
        assert [s for s in range(1, 10) if numerics.sampled(s)] == \
            [1, 4, 7]
        os.environ["MXNET_TPU_NUMERICS_EVERY"] = "0"
        assert not any(numerics.sampled(s) for s in range(1, 10))
        os.environ["MXNET_TPU_NUMERICS_EVERY"] = "bogus"
        assert numerics.every() == 0
    finally:
        del os.environ["MXNET_TPU_NUMERICS_EVERY"]


def test_reset_clears_ewma_and_summary(monkeypatch):
    p = {"tensors": {}, "grad_norm": 1.0}
    numerics.process_step(p, step=1, program="test.reset")
    assert numerics.summary()["sampled_steps"] == 1
    telemetry.reset()
    s = numerics.summary()
    assert s["sampled_steps"] == 0 and s["last_grad_norm"] is None
