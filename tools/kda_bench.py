#!/usr/bin/env python
"""The gated delta rule's chunked scan alone, forward and forward +
backward: the two Pallas kernels (``mxtpu_kda_fwd`` / ``mxtpu_kda_bwd``)
against the ``jax.numpy`` form of ``mxnet_tpu/ops/delta_rule.py``.

Each variant is one jitted call of ``gated_delta_rule`` as the model makes
it (``qk_l2norm``, the head-major transposes round the scan included), run
``--reps`` times under one profiler session and timed by its device events:
the union of the op intervals of a call (the ``jax.numpy`` form is hundreds
of small ops, some inside ``while`` loops), median over the calls; for the
kernels also their own custom calls' time (``kernels_ms``; the two kernels'
own beside it, ``fwd_kernel_ms`` / ``bwd_kernel_ms``), ``bwd_hi_products``,
the highest-precision products in the backward kernel's body as the timed
program traced it, and ``kept_mb``, what the forward keeps for the backward
beyond its inputs (both from the layer's plan entry).  The share of the roofline
divides what the algorithm needs (``kernel_costs`` of
``benchmark/configs/kimi-linear-48b-a3b.py``: a layer's forward + backward;
forward alone a third of its operations and the bytes of q, k, v, o, g, beta
and the kept states) by the time.

Usage (on the TPU host; prints one JSON line a variant):
    python tools/kda_bench.py --shape 1x8192x32x128
The shape is ``B x T x heads x head_dim``.
"""
from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import statistics
import sys
import tempfile

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)


def device_events(trace_dir):
    """``[(start_ns, end_ns, name)]`` of the first device's ops in the
    newest trace under ``trace_dir``, by start."""
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for line in plane.lines if line.name == "XLA Ops"
                          for e in line.events)
    return []


def busy_ns(events):
    """Length of the union of the events' intervals."""
    total, until = 0, 0
    for start, end, _name in events:
        if end > until:
            total += end - max(start, until)
            until = end
    return total


def split_calls(events, reps):
    """The events of each of ``reps`` calls: cut at the ``reps - 1`` longest
    idle gaps (the host waits for a call's result before it sends the
    next)."""
    gaps, until = [], events[0][1]
    for i, (start, end, _name) in enumerate(events[1:], 1):
        gaps.append((start - until, i))
        until = max(until, end)
    cuts = sorted(i for _gap, i in sorted(gaps)[len(gaps) - reps + 1:])
    return [events[a:b] for a, b in zip([0] + cuts, cuts + [len(events)])]


def needed(shape):
    """``{"fwd": (flops, bytes), "both": (flops, bytes)}`` of one layer."""
    b, t, h, d = shape
    bench = os.path.join(ROOT, "benchmark", "configs")
    spec = importlib.util.spec_from_file_location(
        "kimi_costs", os.path.join(bench, "kimi-linear-48b-a3b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.path.join(bench, "kimi-linear-48b-a3b.json")) as f:
        cfg = json.load(f)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"],
                                     num_heads=h, head_dim=d)
    cost = mod.kernel_costs(cfg, {"batch_per_chip": b, "seq": t})[
        "mxtpu.block.kda"]
    wide = b * t * h * d
    fwd_bytes = 2.0 * 4 * wide + 4.0 * wide + 2.0 * b * t * h \
        + 4.0 * (t // 512) * b * h * d * d
    return {"fwd": (cost["flops"] / cost["calls"] / 3.0, fwd_bytes),
            "both": (cost["flops"] / cost["calls"],
                     cost["bytes"] / cost["calls"])}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="1x8192x32x128")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--lowerings", default="pallas,xla")
    ap.add_argument("--groups", default=None,
                    help="positions whose state-free part is made at once, "
                         "e.g. 256,512,1024 (default: the module's GROUP)")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops import delta_rule
    if jax.default_backend() != "tpu":
        sys.exit("kda_bench: no TPU attached (backend %s); a time from "
                 "another device is not these kernels' time"
                 % jax.default_backend())
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)[jax.devices()[0].device_kind]
    shape = tuple(int(n) for n in args.shape.split("x"))
    b, t, h, d = shape
    rng = np.random.RandomState(0)
    dtype = jnp.dtype(args.dtype)
    q, k, v, cot = (jnp.asarray(rng.normal(0, 1, shape), dtype)
                    for _ in range(4))
    # the decay of the configuration's assumed initialisation: up to 1.6 a
    # position on some channels
    g = -jnp.asarray(rng.uniform(0.001, 0.1, shape) * rng.uniform(1, 16, (h, d)),
                     jnp.float32)
    beta = jnp.asarray(1 / (1 + np.exp(-rng.normal(0, 1, shape[:3]))), dtype)
    need = needed(shape)
    chosen = delta_rule._lowering_for
    variants = []
    groups = [int(n) for n in (args.groups or str(delta_rule.GROUP)).split(",")]
    for lowering, group in [(lo, gr) for lo in args.lowerings.split(",")
                            for gr in groups]:
        # the choice is the module's own (backend and shapes); here both
        # forms are wanted on one backend
        delta_rule._lowering_for = lambda *_a, _l=lowering: _l

        def op(q, k, v, g, beta, group=group):
            return delta_rule.gated_delta_rule(
                q, k, v, g, beta, group=group, qk_l2norm=True,
                scale=d ** -0.5)

        fwd = jax.jit(op)
        both = jax.jit(lambda *a: jax.vjp(op, *a[:5])[1](a[5]))
        out = jax.block_until_ready(fwd(q, k, v, g, beta))      # compiles
        with delta_rule.plan_recording():
            grads = jax.block_until_ready(both(q, k, v, g, beta, cot))
        # of the backward kernel's body as this variant traced it (None:
        # the jax.numpy form has no such body)
        plan = delta_rule.last_plan_summary()
        variants.append((lowering, group, fwd, both, (out,) + tuple(grads),
                         (plan.get("bwd_hi_products"), plan["state_bytes"])))
    delta_rule._lowering_for = chosen
    first = variants[-1][4]              # the jax.numpy form where asked for
    f32 = lambda x: np.asarray(x, np.float32)
    for lowering, group, fwd, both, outs, (hi, kept) in variants:
        for which, fn, extra in (("fwd", fwd, ()), ("both", both, (cot,))):
            with tempfile.TemporaryDirectory() as trace_dir:
                jax.profiler.start_trace(trace_dir)
                for _ in range(args.reps):
                    jax.block_until_ready(fn(q, k, v, g, beta, *extra))
                jax.profiler.stop_trace()
                events = device_events(trace_dir)
            calls = split_calls(events, args.reps)
            per = statistics.median(len(c) for c in calls)
            ms = statistics.median(busy_ns(c) for c in calls) / 1e6
            own, own_fwd, own_bwd = (
                statistics.median(sum(e - s for s, e, n in c if name in n)
                                  for c in calls) / 1e6
                for name in ("mxtpu_kda_", delta_rule.KDA_FWD,
                             delta_rule.KDA_BWD))
            flops, moved = need[which]
            floor_ms = 1e3 * max(flops / (peaks["bf16_flops"]),
                                 moved / (peaks["hbm_bytes_per_s"]))
            print(json.dumps({
                "lowering": lowering, "group": group, "what": which,
                "shape": list(shape), "bwd_hi_products": hi,
                "kept_mb": round(kept / 1e6, 3),
                "ms": round(ms, 4), "kernels_ms": round(own, 4),
                "fwd_kernel_ms": round(own_fwd, 4),
                "bwd_kernel_ms": round(own_bwd, 4),
                "device_ops": per, "needed_gflop": round(flops / 1e9, 1),
                "needed_gb": round(moved / 1e9, 3),
                "roofline_ms": round(floor_ms, 4),
                "roofline_pct": round(100 * floor_ms / ms, 2),
                "max_diff_vs_xla": [
                    float(np.abs(f32(a) - f32(r)).max())
                    for a, r in zip(outs, first)][:(1 if which == "fwd" else 6)],
            }), flush=True)


if __name__ == "__main__":
    main()
