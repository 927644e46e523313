"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no child.  Everything that belongs to one cell is found by the
names in ``BENCHMARK.json``: ``configs/<configuration>.{json,py}``,
``references/<configuration>.py``, ``traffic/<mix>.json``,
``runners/<runner>.py`` (named by the mix), ``layer_metrics/<reader>.py`` (the
part of a per-layer metric's name before its first dot) and ``peaks.json``.
Off the chip the command fails; it never falls back to the CPU.

Order of a run: reference's first steps (its time is not set-up) -> build the
runner's session -> the session's own first steps, compared with the
reference's -> the timed window -> result line (last line of stdout).
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):  # the program's package, then the benchmark's own modules
    if _p not in sys.path:
        sys.path.insert(0, _p)

import check  # noqa: E402
import traffic  # noqa: E402
import xtrace  # noqa: E402
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_PREFIX = "/jax/compilation_cache/"
#: seconds of the window that a --trace 1 run keeps under the profiler
TRACED_SECONDS = 4.0


def say(tag, **fields):
    print("bench %s %s" % (tag, json.dumps(fields, sort_keys=True)), flush=True)


def load_module(kind, name):
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location("bench_%s_%s" % (kind, name.replace("-", "_")
                                                                  .replace(".", "_")), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything its names lead to."""

    def __init__(self, name, bench=None):
        self.bench = bench or load_json(ROOT, "BENCHMARK.json")
        found = [w for w in self.bench["workloads"] if w["name"] == name]
        if not found:
            raise SystemExit("no workload %r in BENCHMARK.json" % name)
        self.name, self.chips = name, int(found[0]["chips"])
        entry = next(c for c in self.bench["configs"] if c["name"] == found[0]["config"])
        self.cfg = load_json(ROOT, entry["file"])
        self.mix = load_json(HERE, "traffic", found[0]["traffic"] + ".json")
        # a configuration's code is found by its name; a cut-down copy (smoke.py's)
        # names the configuration whose code it shares
        code = self.cfg.get("code", entry["name"])
        self.cfgmod = load_module("configs", code)
        refs = os.path.join(HERE, "references")
        if refs not in sys.path:
            sys.path.insert(0, refs)
        self.refmod = load_module("references", code)
        self.runner = load_module("runners", self.mix["runner"])

    def metrics(self, group):
        """Names of the metrics of ``end_to_end`` or ``per_layer`` that this cell reports."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]


class Events:
    """The benchmark's own listener on JAX's compile and cache events
    (the arithmetic of ``mxnet_tpu/telemetry/compile.py``, copied)."""

    def __init__(self):
        import jax
        self.compiles, self.compile_s = 0, 0.0
        self.cache = {"cache_hits": 0, "cache_misses": 0}
        self.saved_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_kw):
        if name == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += float(secs)
        elif name == CACHE_PREFIX + "compile_time_saved_sec":
            self.saved_s += float(secs)

    def _event(self, name, **_kw):
        if name.startswith(CACHE_PREFIX):
            key = name[len(CACHE_PREFIX):]
            self.cache[key] = self.cache.get(key, 0) + 1

    def snapshot(self):
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache["cache_hits"], "cache_misses": self.cache["cache_misses"],
                "cache_saved_s": self.saved_s}


def place_cache():
    """JAX's persistent cache at a fixed path inside the checkout (the path is part
    of the key), whatever ``JAX_COMPILATION_CACHE_DIR`` says, with no limit on its
    size and no program too quick or too small to be kept: after the first run of a
    cell in a checkout every program comes from it."""
    import jax
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def chips_or_die(n, allow_cpu=False):
    """The first ``n`` accelerator devices and their row of ``peaks.json``."""
    import jax
    devs = jax.devices()
    peaks = load_json(HERE, "peaks.json")
    kind = devs[0].device_kind
    if allow_cpu:
        return devs[:n], None
    if devs[0].platform != "tpu" or kind not in peaks or len(devs) < n:
        sys.stderr.write("benchmark: needs %d chip(s) of %s; JAX reports %d x %s (%s)\n"
                         % (n, sorted(peaks), len(devs), kind, devs[0].platform))
        raise SystemExit(3)
    return devs[:n], peaks[kind]


def peak_bytes(devices, plan_bytes=0):
    """Peak bytes on the fullest chip.  On this runtime ``peak_bytes_in_use`` counts
    live arrays and not the scratch memory of a running program (chip call 1, PR 23:
    0.64 GB after ResNet-50 at batch 128), so the live bytes plus the scratch bytes
    of the window's program (the program's own memory plan) stand beside it."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)),
                   int(stats.get("bytes_in_use", 0)) + int(plan_bytes))
    return peak


def seed_key(seed):
    """``(key, offset)`` from the seed, both passed as *arguments*: no compiled
    program holds the seed, so every seed finds the same programs in the cache.
    The key makes the weights; the offset picks the gradient elements compared."""
    import jax
    import jax.numpy as jnp
    import common
    words = common.seed_words(seed)
    return jax.random.PRNGKey(words[0]), jnp.int32(words[1] % (2 ** 31))


def reference_first_steps(cell, seed, host_batch, steps, devices, quant=None):
    """The plain reference's first ``steps`` steps; frees all it made.

    The batch's rows are laid over the cell's chips and the weights copied to each
    (a placement of the arguments: the reference's code stays plain ``jax.numpy``),
    so that 4 x 128 float32 rows fit where one chip holds 128."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import common
    mesh = Mesh(np.array(list(devices)), ("rows",))
    whole = NamedSharding(mesh, P())
    batch = {k: jax.device_put(v, NamedSharding(mesh, P("rows"))) for k, v in host_batch.items()}
    rows = next(iter(host_batch.values())).shape[0]
    key, offset = jax.device_put(seed_key(seed), whole)
    out = common.follow(lambda p, b, qn: cell.refmod.loss(p, b, cell.cfg, qn),
                        lambda k: cell.refmod.init_params(cell.cfg, k),
                        key, offset, batch, cell.cfg["optimizer"], steps, rows, quant)
    del batch
    return out


def percentile(values, pct):
    """Nearest-rank percentile."""
    s = sorted(values)
    return s[max(0, min(len(s) - 1, math.ceil(pct / 100.0 * len(s)) - 1))]


def measure(session, seconds, traced_seconds=0.0, trace_dir=None):
    """The timed window: chains dispatched and fetched one after another until
    ``seconds`` have passed.  Returns the samples ``(enqueue start, enqueue end,
    fetch end, losses)`` and the traced part's last sample index."""
    import jax
    samples, traced_upto = [], 0
    annotate = jax.profiler.TraceAnnotation
    if traced_seconds:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    tracing = bool(traced_seconds)
    while True:
        a = time.perf_counter()
        with annotate("bench:enqueue"):
            handle = session.dispatch()
        b = time.perf_counter()
        with annotate("bench:fetch"):
            losses = session.fetch(handle)
        c = time.perf_counter()
        samples.append((a, b, c, losses))
        if tracing and c - t0 >= traced_seconds:
            jax.profiler.stop_trace()
            tracing, traced_upto = False, len(samples)
        if c - t0 >= seconds:
            break
    if tracing:
        jax.profiler.stop_trace()
        traced_upto = len(samples)
    return t0, samples, traced_upto


def run_cell(cell, seed, seconds, trace, keep_trace=None, on_chip=True):
    """One run of one cell; returns the result object.  ``on_chip=False`` is
    ``smoke.py``'s: the same path on whatever JAX has, reporting counts only."""
    import jax
    cache_dir = place_cache() if on_chip else None
    events = Events()
    devices, peak = chips_or_die(cell.chips, allow_cpu=not on_chip)
    say("start", workload=cell.name, seed=seed, seconds=seconds, trace=trace,
        cache_dir=cache_dir,
        cache_entries=len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(cache_dir) else 0,
        jax=jax.__version__, device_kind=devices[0].device_kind, chips=cell.chips)

    host_batch = traffic.host_batch(cell.cfg, cell.mix, cell.chips, seed)
    steps_per_dispatch = int(cell.mix["chain"])

    # -- the plain reference, before the program's state is made; not set-up
    t_ref = time.perf_counter()
    ref = reference_first_steps(cell, seed, host_batch, 1 + steps_per_dispatch, devices)
    ref_s = time.perf_counter() - t_ref
    say("reference", seconds=ref_s, losses=ref["losses"], footprint_bytes=ref["footprint_bytes"],
        **events.snapshot())

    # -- the system under test: one session for the comparison and the window
    session = cell.runner.open(
        cell.cfg, cell.cfgmod, cell.mix, devices, seed,
        lambda key: cell.refmod.init_params(cell.cfg, key), seed_key(seed), host_batch)
    say("built", seconds=time.perf_counter() - T0 - ref_s, **events.snapshot())
    prog = session.first_steps()
    say("first_steps", losses=prog["losses"], **events.snapshot())
    correct = check.compare(prog, ref, cell.cfg["limits"])
    setup = events.snapshot()

    trace_dir = os.path.join(ROOT, ".bench_trace", cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = time.perf_counter() - T0 - ref_s
    t0, samples, traced_upto = measure(
        session, seconds, min(TRACED_SECONDS, seconds) if trace else 0.0, trace_dir)
    after = events.snapshot()

    # -- reduction
    window_s = samples[-1][2] - t0
    n_disp = len(samples)
    failed = sum(1 for s in samples if not all(math.isfinite(x) for x in s[3]))
    compiles_in_window = after["compiles"] - setup["compiles"]
    units = cell.cfgmod.units_per_step(cell.cfg, cell.mix, cell.chips)
    flops = cell.cfgmod.step_flops(cell.cfg, cell.mix, cell.chips)
    steps = n_disp * steps_per_dispatch
    rate_chip = steps * units / window_s / cell.chips
    step_ms = [(c - s) / steps_per_dispatch * 1e3 for s, _b, c, _l in samples]
    print("check failed dispatches = %d (limit 0), compiles in window = %d (limit 0)"
          % (failed, compiles_in_window))
    correct = correct and failed == 0 and compiles_in_window == 0
    counters = session.counters()
    mem_peak = peak_bytes(devices, (counters.get("memory_plan") or {}).get("temp_bytes", 0))
    p95 = percentile(step_ms, 95)
    timed = dict(seconds=window_s, step_ms_p50=statistics.median(step_ms),
                 step_ms_p95=p95, step_ms_top5=sorted(step_ms)[-5:],
                 units_per_s_chip=rate_chip, setup_s=setup_s, reference_s=ref_s,
                 model_flops_utilization_pct=100.0 * flops * steps / window_s
                 / (cell.chips * peak["bf16_flops"])) if on_chip else {}
    say("window", dispatches=n_disp, steps=steps, failed=failed, samples=n_disp,
        compiles_in_window=compiles_in_window, last_loss=float(samples[-1][3][-1]),
        program_counters=counters, memory_stats=devices[0].memory_stats(), **timed, **after)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": mem_peak}
    result = {"correct": bool(correct), "attempted": n_disp, "failed": failed, "device": device}
    if not on_chip:
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        session.close()
        return result
    if not trace:
        e2e = {"setup_s": setup_s, "images_per_s_chip": rate_chip, "tokens_per_s_chip": rate_chip}
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                             for m in cell.metrics("end_to_end")}
    else:
        tr = xtrace.load(xtrace.newest_xplane(trace_dir))
        if keep_trace:
            xtrace.save(tr, keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        red = xtrace.reduce(tr)
        if red is None:
            sys.stderr.write("benchmark: the trace holds no device operation\n")
            raise SystemExit(4)
        ctx = {"cell": cell, "samples": samples, "traced_samples": samples[:traced_upto],
               "setup_events": setup, "trace": red,
               "counters": counters, "step_flops": flops, "peak": peak,
               "steps_per_dispatch": steps_per_dispatch, "memory_peak_bytes": mem_peak,
               "compiles_in_window": compiles_in_window}
        metrics = {}
        for m in cell.metrics("per_layer"):
            value = load_module("layer_metrics", m["name"].split(".")[0]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    session.close()
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the traced window in normal form to this .json.gz")
    a = ap.parse_args(argv)
    result = run_cell(Cell(a.workload), a.seed, a.seconds, a.trace, a.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
