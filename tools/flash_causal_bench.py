#!/usr/bin/env python
"""Each flash attention kernel alone, causal, by the number of static
ranges a diagonal tile's Q blocks are split into (``_causal_plan``).

For every shape and every count of ranges the forward and the backward
kernel are compiled with that count, run ``--reps`` times under one
profiler session, and timed by their own device events (the custom
call's ``name=``), so the transposes and the row sums round the kernels
stay out of the number.  The share of the peak divides the operations
the algorithm needs (the causal half of the square, no recomputation:
2 products forward, 4 backward, the convention of the benchmark's
``kernel_costs``) by the time, not the operations the kernel executes.

Usage (on the TPU host; prints one JSON line a kernel and count):
    python tools/flash_causal_bench.py \
        --shapes 4x2048x32x32x64,1x8192x32x8x64 --ranges 1,2,4,8,16
    python tools/flash_causal_bench.py --shapes 1x8192x32x4x128 \
        --window 2048 --ranges 4 --blocks 128x2048,128x1024,128x512
    python tools/flash_causal_bench.py --shapes 1x4096x20x20x256 \
        --ranges 4 --blocks 512x2048,256x2048,512x1024
(the last: GLM-4.7-Flash's latent attention, two lane tiles on both sides.)
Shapes are ``B x T x query heads x key/value heads x head_dim``: the
head grouping is the two head counts; a sixth number is the values'
width where it is not the queries' (``1x8192x32x32x192x128``).  Every
record says beside the blocks it timed the pair the kernels' own rule
takes for the shape (``rule_blocks``: ``_flash_blocks``, under a window
``_window_blocks``), so that
``--ranges 4 --blocks 128x2048,256x2048,512x2048`` makes a shape's rows
of the table the rule was decided by.  ``--window``: a sliding window
(the kernels ``mxtpu_flash_{fwd,bwd}_window`` past one K/V panel); the
needed operations are then the band's, ``0 <= t - j < window``.
``--blocks`` takes several pairs, each measured in turn.
``--diffusion-block B``: the block-diffusion mask in blocks of ``B`` over
``T = 2L`` rows, clean copy first (``mxtpu_flash_{fwd,bwd}_blockdiff``,
``mxnet_tpu.ops.flash_blockdiff``); the needed operations are the mask's
``L^2 + L B`` pairs, and ``--ranges`` is not read (the kernels keep
``_causal_plan``'s own).  Run the same shape without the switch for the
causal kernels over the same rows.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

PEAK_FLOPS = 197e12          # TPU v5e, bf16 (benchmark/peaks.json)


def kernel_events(trace_dir):
    """``[(start_ns, duration_ns, name)]`` of the flash kernels' device
    events in the newest trace under ``trace_dir``, by start."""
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                out += [(e.start_ns, e.duration_ns, e.name.split(" = ")[0])
                        for e in line.events if "mxtpu_flash_" in e.name]
    return sorted(out)


def bench(pk, shape, ranges, reps, dtype, blocks=None, window=0):
    """``[record]`` for one shape: forward and backward at each count
    (``blocks``: a (block_q, block_k) other than the heuristic's;
    ``window``: a sliding window, 0 for none)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    b, t, hq, hk, d = shape[:5]
    dv = shape[5] if len(shape) > 5 else d
    rng = np.random.RandomState(0)
    mk = lambda h, d: jnp.asarray(rng.normal(0, 1, (b, t, h, d)), dtype)
    q, k, v, g = mk(hq, d), mk(hk, d), mk(hk, dv), mk(hq, dv)
    # the rule's pair by kernel: the backward's differs from the forward's
    # where both widths take two lane tiles
    rule = {which: pk._window_blocks(t) if 0 < window < t else
            pk._flash_blocks(t, d, dv, hq // hk, True, backward=which == "bwd")
            for which in ("fwd", "bwd")}
    kw = {} if blocks is None else {"blocks": tuple(blocks)}
    if window:
        kw["window"] = window
    timed = {which: blocks or rule[which] for which in rule}
    variants = []
    for s in ranges:
        fwd = jax.jit(lambda q, k, v, s=s: pk._flash_attention_fwd_pallas(
            q, k, v, True, False, ranges=s, **kw))
        bwd = jax.jit(lambda q, k, v, o, lse, g, s=s:
                      pk._flash_attention_bwd_pallas(
                          q, k, v, o, lse, g, True, False, ranges=s, **kw))
        o, lse = jax.block_until_ready(fwd(q, k, v))          # compiles
        grads = jax.block_until_ready(bwd(q, k, v, o, lse, g))
        variants.append((s, fwd, bwd, (o, lse), grads))
    first = variants[0]
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for _s, fwd, bwd, (o, lse), _g in variants:
            for _ in range(reps):
                jax.block_until_ready(fwd(q, k, v))
            for _ in range(reps):
                jax.block_until_ready(bwd(q, k, v, o, lse, g))
        jax.profiler.stop_trace()
        events = kernel_events(trace_dir)
    assert len(events) == 2 * reps * len(variants), len(events)
    records = []
    f32 = lambda x: np.asarray(x, np.float32)
    for i, (s, _f, _b, outs, grads) in enumerate(variants):
        # multiply-adds a head needs: the causal half, or the band
        pairs = t * t / 2 if not 0 < window < t else \
            window * (window + 1) / 2 + (t - window) * window
        # products needed, as (over the query/key width, over the values'):
        # forward QK^T and PV; backward dQ, dK and dV, dP (the recomputed
        # scores are not needed work), as the configurations' kernel_costs
        for j, (which, (over_d, over_dv), got, ref) in enumerate((
                ("fwd", (1, 1), outs[:1], first[3][:1]),
                ("bwd", (2, 2), grads, first[4]))):
            blocks = timed[which]
            plan = pk._causal_plan(blocks[0], blocks[1], s)
            pct = pk._scores_computed_pct(
                t, blocks[0], blocks[1], plan,
                window if 0 < window < t and t > blocks[1] else 0)
            chunk = events[(2 * i + j) * reps:(2 * i + j + 1) * reps]
            names = {n.rstrip(".0123456789") for _t0, _d, n in chunk}
            assert len(names) == 1 and which in min(names), names
            ms = statistics.median(dur for _t0, dur, _n in chunk) / 1e6
            needed = 2 * b * hq * pairs * (over_d * d + over_dv * dv)
            records.append({
                "kernel": min(names).lstrip("%"), "shape": list(shape),
                "window": window,
                "blocks": list(blocks), "rule_blocks": list(rule[which]),
                "ranges": len(plan[1]),
                "scores_computed_pct": round(pct, 2), "ms": round(ms, 4),
                "needed_gflop": round(needed / 1e9, 1),
                "peak_pct_needed": round(
                    100 * needed / (ms / 1e3) / PEAK_FLOPS, 2),
                "max_diff_vs_first": max(
                    float(np.abs(f32(a) - f32(r)).max())
                    for a, r in zip(got, ref))})
    return records


def _blockdiff_diffs(bd, pk, arrays, group, block, blocks):
    """Largest difference of the compiled kernels from the plain path,
    forward and backward, on one key/value head's group over the first
    rows of each half (at most 4096 rows: the plain path holds the whole
    float32 score square); None where ``blocks`` do not tile that many."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    t = arrays[0].shape[1]
    rows = min(t, 4096)
    if not bd._tiles_the_mask(rows, block, *blocks):
        return {"fwd": None, "bwd": None}
    half = t // 2
    q, k, v, g = (jnp.concatenate(
        [x[:, :rows // 2, :n], x[:, half:half + rows // 2, :n]], axis=1)
        for x, n in zip(arrays, (group, 1, 1, group)))
    o, lse = bd.fwd(q, k, v, block, False, blocks)
    grads = bd.bwd(q, k, v, o, lse, g, block, False, blocks)
    ref, vjp = jax.vjp(lambda *a: pk._attention_jnp(*a, False, 0, block),
                       *(x.astype(jnp.float32) for x in (q, k, v)))
    f32 = lambda x: np.asarray(x, np.float32)
    return {"fwd": float(np.abs(f32(o) - f32(ref)).max()),
            "bwd": max(float(np.abs(f32(a) - f32(r)).max())
                       for a, r in zip(grads, vjp(g.astype(jnp.float32))))}


def bench_blockdiff(shape, block, reps, dtype, blocks=None):
    """``[record]`` for one shape under the block-diffusion mask: the
    forward and the backward kernel at ``blocks`` (default: the
    kernels' own rule)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops import flash_blockdiff as bd, pallas_kernels as pk

    b, t, hq, hk, d = shape[:5]
    rng = np.random.RandomState(0)
    mk = lambda h: jnp.asarray(rng.normal(0, 1, (b, t, h, d)), dtype)
    q, k, v, g = mk(hq), mk(hk), mk(hk), mk(hq)
    rule = bd.blocks_for(t, block)
    blocks = tuple(blocks or rule)
    fwd = jax.jit(lambda q, k, v: bd.fwd(q, k, v, block, False, blocks))
    bwd = jax.jit(lambda q, k, v, o, lse, g: bd.bwd(q, k, v, o, lse, g, block,
                                                   False, blocks))
    o, lse = jax.block_until_ready(fwd(q, k, v))
    grads = jax.block_until_ready(bwd(q, k, v, o, lse, g))
    diffs = _blockdiff_diffs(bd, pk, (q, k, v, g), hq // hk, block, blocks)
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for _ in range(reps):
            jax.block_until_ready(fwd(q, k, v))
        for _ in range(reps):
            jax.block_until_ready(bwd(q, k, v, o, lse, g))
        jax.profiler.stop_trace()
        events = kernel_events(trace_dir)
    assert len(events) == 2 * reps, len(events)
    half = t // 2
    pairs = half * half + half * block
    pct = bd.scores_computed_pct(t, blocks[0], blocks[1], block,
                                 pk._causal_plan(*blocks))
    records = []
    for j, (which, products) in enumerate((("fwd", 2), ("bwd", 4))):
        chunk = events[j * reps:(j + 1) * reps]
        names = {n.rstrip(".0123456789") for _t0, _d, n in chunk}
        assert len(names) == 1 and which in min(names), names
        ms = statistics.median(dur for _t0, dur, _n in chunk) / 1e6
        needed = 2 * b * hq * pairs * products * d
        records.append({
            "kernel": min(names).lstrip("%"), "shape": list(shape),
            "diffusion_block": block, "blocks": list(blocks),
            "rule_blocks": list(rule) if rule else None,
            "scores_computed_pct": round(pct, 2),
            "scores_needed_pct": round(100.0 * pairs / (t * t), 2),
            "ms": round(ms, 4), "needed_gflop": round(needed / 1e9, 1),
            "peak_pct_needed": round(
                100 * needed / (ms / 1e3) / PEAK_FLOPS, 2),
            "max_diff_vs_jnp": diffs[which]})
    return records


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="4x2048x32x32x64,1x8192x32x8x64")
    ap.add_argument("--ranges", default="1,2,4,8,16")
    ap.add_argument("--blocks", default=None,
                    help="block_q x block_k, e.g. 256x2048, or several "
                         "(128x2048,128x512), each measured in turn "
                         "(default: the heuristic's for each length)")
    ap.add_argument("--window", type=int, default=0,
                    help="sliding window in keys (default 0: none)")
    ap.add_argument("--diffusion-block", type=int, default=0,
                    help="the block-diffusion mask in blocks of this many "
                         "positions over T = 2L rows (default 0: none)")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)
    import jax
    from mxnet_tpu.ops import pallas_kernels as pk
    if jax.default_backend() != "tpu":
        sys.exit("flash_causal_bench: no TPU attached (backend %s); a time "
                 "from another device is not these kernels' time"
                 % jax.default_backend())
    pairs = [tuple(int(n) for n in pair.split("x"))
             for pair in args.blocks.split(",")] if args.blocks else [None]
    for spec in args.shapes.split(","):
        shape = tuple(int(n) for n in spec.split("x"))
        for blocks in pairs:
            if args.diffusion_block:
                for rec in bench_blockdiff(shape, args.diffusion_block,
                                           args.reps, args.dtype, blocks):
                    print(json.dumps(rec), flush=True)
                continue
            for rec in bench(pk, shape,
                             [int(s) for s in args.ranges.split(",")],
                             args.reps, args.dtype, blocks, args.window):
                print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
