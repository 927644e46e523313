"""Telemetry subsystem: registry semantics, spans, exporters, e2e fit.

Covers the contracts in docs/api/telemetry.md: labeled counter/gauge/
histogram semantics and thread safety, catalog enforcement, span
nesting + Chrome-trace round trip, JSONL/Prometheus golden outputs,
report() percentiles/throughput/compile accounting, the absorbed
IO/kvstore/resilience counters, an end-to-end Module.fit run on a
zoo model with the JSONL step-log enabled, the memory-observability
layer (version-tolerant plan accessors, plan gauges, HBM budget check,
RESOURCE_EXHAUSTED annotation), and the flight recorder (ring
wraparound, thread safety, dump schema + reader, crash-guard dedup).
"""
import importlib.util
import json
import os
import threading
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.telemetry import flight
from mxnet_tpu.telemetry import memory as tmem
from mxnet_tpu.telemetry import spans


def _load_tool(name):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, "tools", "%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _fresh_telemetry(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_TELEMETRY_JSONL", raising=False)
    monkeypatch.delenv("MXNET_TPU_FLIGHT_DIR", raising=False)
    telemetry.reset()
    yield
    telemetry.reset()


# ------------------------------------------------------------- registry

def test_counter_basic():
    c = telemetry.counter("mxtpu_step_total")
    c.inc()
    c.inc(4)
    assert c.get() == 5


def test_counter_rejects_decrease():
    with pytest.raises(MXNetError):
        telemetry.counter("mxtpu_step_total").inc(-1)


def test_labels_separate_series():
    c = telemetry.counter("mxtpu_io_records_total")
    c.labels(source="recordio").inc(2)
    c.labels(source="native").inc(3)
    samples = c.samples()
    assert samples[(("source", "recordio"),)] == 2
    assert samples[(("source", "native"),)] == 3


def test_label_mismatch_raises():
    c = telemetry.counter("mxtpu_io_records_total")
    with pytest.raises(MXNetError):
        c.labels(wrong="x")
    with pytest.raises(MXNetError):
        c.inc()        # labeled metric needs .labels(...)


def test_undeclared_name_raises():
    with pytest.raises(MXNetError, match="not declared"):
        telemetry.counter("mxtpu_not_in_catalog_total")


def test_kind_mismatch_raises():
    with pytest.raises(MXNetError):
        telemetry.gauge("mxtpu_step_total")


def test_gauge_set_inc_dec():
    g = telemetry.gauge("mxtpu_kvstore_pending_async")
    g.set(5)
    g.inc()
    g.dec(2)
    assert g.get() == 4


def test_histogram_buckets():
    r = telemetry.Registry(catalog=None)
    h = r.histogram("h", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    s = h.get()
    assert s["buckets"] == [1, 1, 1, 1]   # one per bucket + overflow
    assert s["count"] == 4
    assert abs(s["sum"] - 55.55) < 1e-9


def test_histogram_rejects_unsorted_buckets():
    r = telemetry.Registry(catalog=None)
    with pytest.raises(MXNetError):
        r.histogram("h", buckets=(1.0, 0.5))


def test_thread_safety_writer_pool():
    c = telemetry.counter("mxtpu_samples_total")
    h = telemetry.histogram("mxtpu_step_seconds")
    n_threads, n_iter = 8, 500

    def work():
        for _ in range(n_iter):
            c.inc()
            h.observe(0.001)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.get() == n_threads * n_iter
    assert h.get()["count"] == n_threads * n_iter


def test_reset_keeps_cached_children_valid():
    child = telemetry.counter("mxtpu_io_records_total").labels(
        source="recordio")
    child.inc()
    telemetry.reset()
    child.inc(2)
    assert child.get() == 2


# ---------------------------------------------------------------- spans

def test_span_records_histogram_and_nesting():
    with telemetry.span("outer"):
        with telemetry.span("inner"):
            pass
        with telemetry.span("inner"):
            pass
    samples = telemetry.histogram("mxtpu_span_seconds").samples()
    assert samples[(("span", "outer"),)]["count"] == 1
    assert samples[(("span", "inner"),)]["count"] == 2
    # outer wall time covers both inners
    assert samples[(("span", "outer"),)]["sum"] >= \
        samples[(("span", "inner"),)]["sum"]


def test_span_decorator():
    calls = []

    @telemetry.span("decorated")
    def fn(x):
        calls.append(x)
        return x + 1

    assert fn(1) == 2
    assert calls == [1]
    samples = telemetry.histogram("mxtpu_span_seconds").samples()
    assert samples[(("span", "decorated"),)]["count"] == 1


def test_span_chrome_trace_roundtrip(tmp_path):
    fname = str(tmp_path / "trace.json")
    mx.profiler.profiler_set_config(mode="all", filename=fname)
    mx.profiler.profiler_set_state("run")
    with telemetry.span("telemetry_span", category="unit"):
        pass
    mx.profiler.profiler_set_state("stop")
    mx.profiler.dump_profile()
    with open(fname) as f:
        trace = json.load(f)
    evts = [e for e in trace["traceEvents"]
            if e["name"] == "telemetry_span"]
    assert len(evts) == 1
    assert evts[0]["cat"] == "unit"
    assert evts[0]["ph"] == "X"
    assert evts[0]["dur"] >= 0


def test_profiler_record_event_concurrent(tmp_path):
    """Regression: record_event/dump_profile must hold the lock
    consistently — concurrent span callbacks and dumps lose no events
    and never crash."""
    fname = str(tmp_path / "conc.json")
    mx.profiler.profiler_set_config(mode="all", filename=fname)
    mx.profiler.profiler_set_state("run")
    n_threads, n_events = 8, 200
    errors = []

    def writer():
        try:
            for i in range(n_events):
                mx.profiler.record_event("evt", float(i), 1.0)
        except Exception as e:  # noqa: BLE001 - collected for assert
            errors.append(e)

    collected = []

    def dumper():
        try:
            for _ in range(20):
                mx.profiler.dump_profile()
                with open(fname) as f:
                    collected.append(len(json.load(f)["traceEvents"]))
        except Exception as e:  # noqa: BLE001 - collected for assert
            errors.append(e)

    threads = [threading.Thread(target=writer) for _ in range(n_threads)]
    threads.append(threading.Thread(target=dumper))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    mx.profiler.profiler_set_state("stop")
    total = sum(collected) + len(
        json.load(open(mx.profiler.dump_profile()))["traceEvents"])
    assert not errors, errors
    assert total == n_threads * n_events


# ----------------------------------------------------- the span record

def test_span_record_nesting_carries_parent_ids():
    with telemetry.span("outer"):
        with telemetry.span("inner"):
            pass
        with telemetry.span("inner"):
            pass
    recs = spans.records()
    assert [r.name for r in recs] == ["inner", "inner", "outer"]
    a, b, outer = recs
    assert outer.parent is None
    assert a.parent == outer.id and b.parent == outer.id    # children
    assert a.id != b.id and a.parent != b.id                # siblings
    assert outer.start <= a.start <= a.end <= b.start <= b.end <= outer.end
    assert a.thread == outer.thread == threading.get_ident()
    # filters: by prefix, and by a window on time.perf_counter()
    assert [r.name for r in spans.records(prefix="in")] == ["inner"] * 2
    assert spans.records(since=b.start) == [b]
    assert spans.records(until=a.end) == [a]


def test_span_ring_is_bounded_and_keeps_the_newest():
    assert spans._ring.maxlen == spans.RING_SIZE
    for i in range(spans.RING_SIZE + 3):
        spans.record("filler", float(i), float(i) + 0.5, i=i)
    recs = spans.records()
    assert len(recs) == spans.RING_SIZE
    assert recs[0].attrs == {"i": 3}
    assert recs[-1].attrs == {"i": spans.RING_SIZE + 2}


def test_span_shared_across_threads_records_two_roots():
    gate = threading.Barrier(2)

    @telemetry.span("shared.op")
    def work():
        gate.wait(timeout=5)

    with telemetry.span("main.outer"):
        ts = [threading.Thread(target=work) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=5)
            assert not t.is_alive()
    ops = spans.records(prefix="shared.op")
    assert len(ops) == 2
    # the parent is what was open on the ENTERING thread: nothing
    assert [r.parent for r in ops] == [None, None]
    assert len({r.thread for r in ops}) == 2
    assert len({r.id for r in ops}) == 2


def test_span_self_time_on_a_hand_made_family():
    R = spans.Record
    parent = R("p", 10.0, 20.0, 1, None, 7, None)
    family = [
        parent,
        R("a", 11.0, 13.0, 2, 1, 7, None),      # 2 s
        R("b", 12.0, 15.0, 3, 1, 8, None),      # overlaps a: 2 s more
        R("c", 18.0, 25.0, 4, 1, 7, None),      # cut at the parent's end
        R("a.child", 11.0, 12.0, 5, 2, 7, None),    # a grandchild: not p's
        R("other", 16.0, 17.0, 6, None, 7, None),   # no child of p
    ]
    assert spans.self_time(parent, family) == pytest.approx(10 - 4 - 2)
    assert spans.self_time(family[1], family) == pytest.approx(1.0)
    assert spans.self_time(family[5], family) == pytest.approx(1.0)


def test_span_attributes_survive_and_exception_still_records():
    with pytest.raises(ValueError):
        with telemetry.span("program.compile", program="p", steps=3):
            raise ValueError("boom")
    (rec,) = spans.records()
    assert rec.name == "program.compile"
    assert rec.attrs == {"program": "p", "steps": 3}
    assert rec.end >= rec.start
    # the thread's stack of open spans is clean again
    with telemetry.span("after"):
        pass
    assert spans.records(prefix="after")[0].parent is None


def test_span_in_a_trace_has_one_timer_and_one_clock():
    """A span recorded into a tracing trace carries the start of its
    span record (one offset turns perf_counter into epoch seconds)."""
    from mxnet_tpu.telemetry import tracing
    with tracing.start_trace("root") as tr:
        with telemetry.span("traced.op"):
            pass
    (rec,) = spans.records(prefix="traced.op")
    doc = tracing.get_trace(tr.trace_id)
    (sp,) = [s for s in doc["spans"] if s["name"] == "traced.op"]
    assert sp["ts"] == round(tracing.epoch_of(rec.start), 6)
    assert sp["dur_s"] == round(rec.end - rec.start, 6)
    here = os.path.dirname(os.path.abspath(spans.__file__))
    with open(os.path.join(here, "spans.py")) as f:
        assert "time.time()" not in f.read()


def test_span_open_round_step_end_lands_in_its_own_step(tmp_path,
                                                        monkeypatch):
    """``trainer.run_steps`` stays open round its own ``step_end``: its
    sum goes into THAT step's JSONL record, not the next one's."""
    path = str(tmp_path / "steps.jsonl")
    monkeypatch.setenv("MXNET_TPU_TELEMETRY_JSONL", path)
    for _ in range(2):
        with telemetry.span("whole.step"):
            telemetry.step_end(samples=1, step_time=0.01)
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    for rec in recs:
        assert rec["spans"]["whole.step"]["count"] == 1
        assert rec["spans"]["telemetry.step_end"]["count"] == 1
    assert telemetry.step_span_totals() == {}


def test_span_is_a_trace_annotation_under_the_jax_profiler(tmp_path):
    """With a jax.profiler session open the span is also on the
    profiler's host plane, as ``mxtpu:<name>``."""
    import glob
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("annotated.outer"):
            with telemetry.span("annotated.inner"):
                jax.block_until_ready(jax.numpy.ones((8,)) + 1)
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    names = {ev.name
             for plane in jax.profiler.ProfileData.from_file(pb).planes
             for line in plane.lines for ev in line.events}
    if not any(n.startswith("mxtpu:") for n in names):
        pytest.skip("this backend's profiler keeps no host annotations")
    assert {"mxtpu:annotated.outer", "mxtpu:annotated.inner"} <= names


# ------------------------------------------------------------ exporters

def test_jsonl_step_log(tmp_path, monkeypatch):
    path = str(tmp_path / "steps.jsonl")
    monkeypatch.setenv("MXNET_TPU_TELEMETRY_JSONL", path)
    with telemetry.span("phase_a"):
        pass
    telemetry.step_end(samples=32, step_time=0.01)
    telemetry.step_end(samples=32, step_time=0.02, extra={"loss": 1.5})
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert len(recs) == 2
    assert recs[0]["step"] == 1 and recs[1]["step"] == 2
    assert recs[0]["samples"] == 32
    assert recs[0]["spans"]["phase_a"]["count"] == 1
    assert "phase_a" not in recs[1]["spans"]   # drained per step
    assert recs[1]["loss"] == 1.5
    assert recs[1]["counters"]["mxtpu_samples_total"] == 64
    assert "gauges" in recs[0]


def test_render_prom_golden():
    telemetry.counter("mxtpu_io_records_total").labels(
        source="recordio").inc(7)
    telemetry.gauge("mxtpu_kvstore_pending_async").set(2)
    out = telemetry.render_prom()
    assert "# TYPE mxtpu_io_records_total counter" in out
    assert 'mxtpu_io_records_total{source="recordio"} 7' in out
    assert "# TYPE mxtpu_kvstore_pending_async gauge" in out
    assert "mxtpu_kvstore_pending_async 2" in out


def test_render_prom_histogram_cumulative():
    h = telemetry.histogram("mxtpu_step_seconds")
    h.observe(0.0001)
    h.observe(0.3)
    out = telemetry.render_prom()
    assert 'mxtpu_step_seconds_bucket{le="0.0005"} 1' in out
    assert 'mxtpu_step_seconds_bucket{le="+Inf"} 2' in out
    assert "mxtpu_step_seconds_count 2" in out


def test_report_percentiles_and_throughput():
    for i in range(100):
        telemetry.step_end(samples=10, step_time=0.01 * (i + 1))
    rep = telemetry.report()
    assert rep["steps"] == 100
    st = rep["step_time_s"]
    assert st["min"] <= st["p50"] <= st["p90"] <= st["p99"] <= st["max"]
    assert abs(st["p50"] - 0.505) < 0.02
    assert rep["throughput"]["samples_per_sec"] > 0
    # one source: the jax.monitoring listener is always installed
    assert telemetry.compile_events.installed()
    assert rep["compile"]["source"] == "jax.monitoring"


def test_report_phases_from_spans():
    with telemetry.span("phase_x"):
        pass
    rep = telemetry.report()
    assert rep["phases"]["phase_x"]["count"] == 1
    assert rep["phases"]["phase_x"]["total_s"] >= 0


def test_http_endpoint():
    httpd = telemetry.start_http_server(port=0)
    port = httpd.server_address[1]
    telemetry.counter("mxtpu_step_total").inc()
    body = urllib.request.urlopen(
        "http://127.0.0.1:%d/metrics" % port, timeout=10).read().decode()
    assert "mxtpu_step_total 1" in body


def test_selfcheck_and_docs_drift():
    assert telemetry.selfcheck() == []
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cc = _load_tool("ci_check")
    assert cc.telemetry_drift(root) == []


# ------------------------------------------------------- memory / HBM

class _AttrMA:
    """jax 0.4.x CompiledMemoryStats shape: *_size_in_bytes attributes."""
    argument_size_in_bytes = 1000
    output_size_in_bytes = 200
    temp_size_in_bytes = 300
    alias_size_in_bytes = 150
    generated_code_size_in_bytes = 50


class _FakeCompiled:
    def memory_analysis(self):
        return _AttrMA()

    def cost_analysis(self):
        # jax <= 0.4.x list-of-dict form, space-separated key
        return [{"flops": 1e6, "bytes accessed": 2e6}]


def test_memory_accessors_version_tolerant():
    assert tmem.memory_analysis_of(_FakeCompiled()) == {
        "argument": 1000, "output": 200, "temp": 300,
        "alias": 150, "generated_code": 50}
    assert tmem.cost_analysis_of(_FakeCompiled()) == {
        "flops": 1e6, "bytes_accessed": 2e6}

    class DictForm:          # jax >= 0.5 plain-dict shapes
        def memory_analysis(self):
            return {"argument": 7, "temp": 3}

        def cost_analysis(self):
            return {"flops": 5.0, "bytes_accessed": 9.0}

    assert tmem.memory_analysis_of(DictForm()) == {"argument": 7,
                                                   "temp": 3}
    assert tmem.cost_analysis_of(DictForm())["bytes_accessed"] == 9.0

    class Absent:            # backend without analyses
        def memory_analysis(self):
            return None

        def cost_analysis(self):
            raise RuntimeError("unsupported")

    assert tmem.memory_analysis_of(Absent()) is None
    assert tmem.cost_analysis_of(Absent()) is None
    assert tmem.plan_of(Absent(), "x") is None
    assert tmem.memory_analysis_of(object()) is None   # no method at all


def test_plan_totals_and_register_gauges():
    plan = tmem.plan_of(_FakeCompiled(), "unit.prog")
    # arg + out + temp + code - alias
    assert plan.total_bytes == 1000 + 200 + 300 + 50 - 150
    tmem.register_plan(plan)
    g = telemetry.gauge("mxtpu_memory_plan_bytes")
    assert g.labels(program="unit.prog", category="argument").get() == 1000
    assert g.labels(program="unit.prog", category="total").get() == 1400
    assert telemetry.gauge("mxtpu_program_flops").labels(
        program="unit.prog").get() == 1e6
    assert tmem.get_plan("unit.prog") is plan
    rep = telemetry.report()
    assert rep["memory"]["plans"]["unit.prog"]["total_bytes"] == 1400
    assert any(e["kind"] == "memory_plan" for e in flight.events())
    # the breakdown string names every category
    for cat in ("argument", "output", "temp", "total"):
        assert cat in plan.breakdown()


def test_budget_check_raises_with_breakdown(monkeypatch):
    plan = tmem.plan_of(_FakeCompiled(), "unit.budget")
    # capacity unknown: inert
    monkeypatch.delenv("MXNET_TPU_HBM_LIMIT_BYTES", raising=False)
    tmem.check_budget(plan)
    # explicit capacity below the plan: descriptive raise
    monkeypatch.setenv("MXNET_TPU_HBM_LIMIT_BYTES", "1000")
    with pytest.raises(MXNetError) as ei:
        tmem.check_budget(plan)
    msg = str(ei.value)
    assert "unit.budget" in msg
    assert "argument=" in msg and "temp=" in msg
    assert "MXNET_BACKWARD_DO_MIRROR" in msg     # remat advice
    assert "batch size" in msg
    # disabled check never raises
    monkeypatch.setenv("MXNET_TPU_MEMORY_BUDGET", "0")
    tmem.check_budget(plan)
    monkeypatch.setenv("MXNET_TPU_MEMORY_BUDGET", "2.0")
    tmem.check_budget(plan)                      # 2x1000 covers 1400


def test_planned_executable_real_jit():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(a):
        return (a @ a).sum()

    x = jnp.ones((8, 8))
    exe = tmem.planned_executable("unit.jit", f, (x,))
    assert float(exe(x)) == 512.0
    plan = tmem.get_plan("unit.jit")
    assert plan is not None and (plan.memory or plan.cost)
    # a function with no .lower degrades to itself, no plan
    calls = []

    def plain(a):
        calls.append(1)
        return a

    assert tmem.planned_executable("unit.plain", plain, (x,)) is plain
    assert tmem.get_plan("unit.plain") is None


def test_annotate_oom_message_counter_and_passthrough():
    tmem.register_plan(tmem.plan_of(_FakeCompiled(), "unit.oom"))
    with pytest.raises(tmem.HbmOomError) as ei:
        with tmem.annotate_oom("unit.oom"):
            raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory while "
                               "trying to allocate 9437184 bytes.")
    msg = str(ei.value)
    assert "RESOURCE_EXHAUSTED" in msg
    assert "static memory plan" in msg and "argument=" in msg
    assert "live device memory" in msg
    assert isinstance(ei.value.__cause__, RuntimeError)
    assert telemetry.counter("mxtpu_oom_total").labels(
        program="unit.oom").get() == 1
    assert any(e["kind"] == "oom" for e in flight.events())
    # non-OOM errors pass through untouched
    with pytest.raises(ValueError, match="plain"):
        with tmem.annotate_oom("unit.oom"):
            raise ValueError("plain failure")
    assert telemetry.counter("mxtpu_oom_total").labels(
        program="unit.oom").get() == 1


# --------------------------------------------------- flight recorder

def test_flight_ring_wraparound():
    r = flight.FlightRecorder(capacity_=16)
    for i in range(50):
        r.record("unit", i=i)
    evs = r.events()
    assert len(evs) == 16
    assert evs[0]["i"] == 34 and evs[-1]["i"] == 49
    assert evs[-1]["seq"] == 50          # seq keeps counting past drops
    r.clear()
    assert len(r.events()) == 0


def test_flight_thread_safety():
    r = flight.FlightRecorder(capacity_=100_000)
    n_threads, n_iter = 8, 500

    def work(tid):
        for i in range(n_iter):
            r.record("unit", tid=tid, i=i)

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = r.events()
    assert len(evs) == n_threads * n_iter
    assert len({e["seq"] for e in evs}) == len(evs)   # no seq collisions


def test_flight_dump_schema_and_reader(tmp_path):
    flight.record("unit", detail="x")
    assert flight.dump("unit") is None        # no dir configured: no-op
    path = flight.dump("unit test!", directory=str(tmp_path))
    assert path and os.path.exists(path)
    assert "unit-test-" in os.path.basename(path)     # slugged reason
    fr = _load_tool("flight_read")
    doc = fr.load(path)
    assert doc["schema"] == "mxtpu-flight/1"
    assert doc["pid"] == os.getpid()
    assert any(e["kind"] == "unit" for e in doc["events"])
    text = fr.format_dump(doc)
    assert "reason=unit test!" in text and "events" in text
    assert telemetry.counter("mxtpu_flight_dumps_total").labels(
        reason="unit-test-").get() == 1
    # a malformed file is rejected with a named error
    bad = tmp_path / "bad.json"
    bad.write_text("{\"schema\": \"nope\"}")
    with pytest.raises(ValueError, match="schema"):
        fr.load(str(bad))


def test_crash_guard_dumps_once_and_only_mxnet_errors(tmp_path,
                                                      monkeypatch):
    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", str(tmp_path))
    with pytest.raises(MXNetError, match="boom"):
        with flight.crash_guard("outer"):
            with flight.crash_guard("inner"):
                raise MXNetError("boom")
    dumps = [f for f in os.listdir(str(tmp_path))
             if f.startswith("flight-")]
    assert len(dumps) == 1                     # nested guards dedup
    doc = _load_tool("flight_read").load(str(tmp_path / dumps[0]))
    assert doc["reason"] == "error"
    assert doc["error"] == "boom"
    errs = [e for e in doc["events"] if e["kind"] == "error"]
    assert errs and errs[0]["site"] == "inner"
    # non-framework errors are not black-boxed by the guard
    with pytest.raises(ValueError):
        with flight.crash_guard("outer"):
            raise ValueError("not ours")
    assert len([f for f in os.listdir(str(tmp_path))
                if f.startswith("flight-")]) == 1


def test_step_end_records_flight_event_with_deltas():
    telemetry.step_end(samples=8, step_time=0.01)
    telemetry.counter("mxtpu_io_records_total").labels(
        source="native").inc(5)
    telemetry.step_end(samples=8, step_time=0.01)
    ends = [e for e in flight.events() if e["kind"] == "step_end"]
    assert len(ends) == 2
    d = ends[1]["counter_deltas"]
    assert d["mxtpu_step_total"] == 1
    assert d['mxtpu_io_records_total{source="native"}'] == 5
    assert ends[1]["step"] == 2


# ------------------------------------------------- absorbed counters

def test_kvstore_push_pull_bytes():
    kv = mx.kv.create("local")
    a = mx.nd.ones((4, 8))
    kv.init("w", a)
    kv.push("w", mx.nd.ones((4, 8)))
    out = mx.nd.zeros((4, 8))
    kv.pull("w", out=out)
    pushed = telemetry.counter(
        "mxtpu_kvstore_push_bytes_total").labels(store="local").get()
    pulled = telemetry.counter(
        "mxtpu_kvstore_pull_bytes_total").labels(store="local").get()
    assert pushed == 4 * 8 * 4
    assert pulled == 4 * 8 * 4


def test_recordio_read_counter(tmp_path):
    path = str(tmp_path / "t.rec")
    w = mx.recordio.MXRecordIO(path, "w")
    for i in range(5):
        w.write(b"payload-%d" % i)
    w.close()
    r = mx.recordio.MXRecordIO(path, "r")
    n = 0
    while r.read() is not None:
        n += 1
    r.close()
    assert n == 5
    got = telemetry.counter("mxtpu_io_records_total").labels(
        source="recordio").get()
    assert got == 5


def test_fault_and_retry_counters():
    from mxnet_tpu import resilience
    resilience.configure_faults("recordio.read:n=2")
    try:
        for _ in range(2):
            with pytest.raises(resilience.FaultInjected):
                resilience.fault_point("recordio.read")
    finally:
        resilience.clear_faults()
    assert telemetry.counter("mxtpu_fault_injected_total").labels(
        site="recordio.read").get() == 2

    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ValueError("transient")
        return "ok"

    assert resilience.retry_call(flaky, retries=3, base_delay=0.001,
                                 jitter=0, name="unit.flaky") == "ok"
    assert telemetry.counter("mxtpu_retry_total").labels(
        site="unit.flaky").get() == 2


def test_prefetch_stall_and_depth():
    x = np.random.RandomState(0).rand(64, 4).astype(np.float32)
    y = np.zeros(64, np.float32)
    it = mx.io.PrefetchingIter(
        mx.io.NDArrayIter(x, y, batch_size=16))
    n = sum(1 for _ in it)
    assert n == 4
    stalls = telemetry.counter(
        "mxtpu_io_prefetch_stall_seconds_total").labels(iter="host")
    assert stalls.get() >= 0.0    # present and non-negative
    # the gauge exists and ends drained
    depth = telemetry.gauge("mxtpu_io_prefetch_depth").labels(iter="host")
    assert depth.get() in (0.0, 1.0)


def test_monitor_stats_become_gauges():
    mon = mx.mon.Monitor(interval=1)
    mon.tic()
    mon.stat_helper("fc1_output", mx.nd.ones((2, 2)))
    res = mon.toc()
    assert res, "monitor recorded nothing"
    g = telemetry.gauge("mxtpu_monitor_stat").labels(tensor="fc1_output")
    assert abs(g.get() - 1.0) < 1e-6


# ------------------------------------------------------------ e2e fit

def test_module_fit_e2e_report_and_jsonl(tmp_path, monkeypatch):
    """Acceptance: Module.fit on a zoo model with the JSONL step-log —
    one parseable record per step carrying span timings and the
    absorbed counters; report() shows the step count, >=1 compile, and
    nonzero throughput."""
    path = str(tmp_path / "fit.jsonl")
    monkeypatch.setenv("MXNET_TPU_TELEMETRY_JSONL", path)

    from mxnet_tpu import models
    net = models.get_model("mlp", num_classes=10)
    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (96, 64)).astype(np.float32)
    y = rng.randint(0, 10, 96).astype(np.float32)
    train = mx.io.NDArrayIter(x, y, batch_size=32,
                              last_batch_handle="discard")
    # two impersonated devices so the local kvstore path runs (single
    # device skips the store) and its traffic lands in the step-log
    mod = mx.module.Module(net, context=[mx.cpu(0), mx.cpu(1)])
    mod.fit(train, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05},
            initializer=mx.initializer.Xavier())

    rep = telemetry.report()
    assert rep["steps"] == 6                      # 3 batches x 2 epochs
    assert rep["compile"]["count"] >= 1
    assert rep["throughput"]["samples_per_sec"] > 0
    # the instrumented phases all appear in the breakdown
    for phase in ("module.forward_backward", "module.update",
                  "executor.forward_backward", "data.fetch"):
        assert phase in rep["phases"], rep["phases"]

    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert len(recs) == 6
    for i, rec in enumerate(recs):
        assert rec["step"] == i + 1
        assert rec["samples"] == 32
        assert rec["step_time_s"] > 0
        assert "module.forward_backward" in rec["spans"]
        assert "mxtpu_kvstore_push_bytes_total{store=\"local\"}" \
            in rec["counters"]
        assert "mxtpu_watchdog_restarts" in rec["gauges"]
    # samples counter is cumulative across the run
    assert recs[-1]["counters"]["mxtpu_samples_total"] == 6 * 32
