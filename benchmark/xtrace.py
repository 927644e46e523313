"""Reduction of a profiler trace to the numbers the per-layer readers use.

The plane loading is copied from ``tools/xprof_top.py``, which keeps only
``(name, duration)``; here every event keeps its start, so busy time, idle
share and gaps can be taken.  All functions below ``load`` work on plain lists
and are checked on the recorded trace under ``testdata/`` by ``tests/``.

Normal form: ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, dur_ns, category], ...]}]}]}``.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

#: containers on the "XLA Ops" line: their time is their children's
CONTAINERS = ("while", "conditional", "call")
#: name parts that mark a collective operation between chips
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
HOST_SPANS = ("bench:enqueue", "bench:fetch")


def newest_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_KIND = re.compile(r"kind=k([A-Za-z]+)")


def short(text):
    """``(name, category)`` of a device event whose name is the HLO instruction's text:
    ``%fusion.12 = bf16[...] fusion(...), kind=kLoop`` -> ``("%fusion.12", "fusion:Loop")``;
    a custom call's category carries its target (``custom-call:tpu_custom_call``)."""
    name, _, rest = text.partition(" = ")
    if not rest:
        return name, ""
    m = _OPCODE.search(" " + rest)
    cat = m.group(1) if m else ""
    extra = _TARGET.search(rest) if cat == "custom-call" else _KIND.search(rest)
    return name, cat + (":" + extra.group(1) if extra else "")


def load(path):
    """An ``.xplane.pb`` (or a normal-form ``.json``/``.json.gz``) in normal form.

    Only the lines the reduction reads are kept: each device's "XLA Ops" (names
    cut to the instruction's own name, see ``short``), and host events named as
    one of ``HOST_SPANS``."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData
    planes = []
    for p in ProfileData.from_file(path).planes:
        device = p.name.startswith("/device:")
        lines = []
        for l in p.lines:
            if device and l.name == "XLA Ops":
                ev = [list(short(e.name)) + [float(e.start_ns), float(e.duration_ns)]
                      for e in l.events]
                ev = [[n, s, d, c] for n, c, s, d in ev]
            elif not device:
                ev = [[e.name, float(e.start_ns), float(e.duration_ns), ""]
                      for e in l.events if e.name in HOST_SPANS]
            else:
                continue
            if ev:
                lines.append({"name": l.name, "events": ev})
        if lines:
            planes.append({"name": p.name, "lines": lines})
    return {"planes": planes}


def save(trace, path):
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def device_ops(trace):
    """``{device plane name: [(name, start, end, category)]}`` sorted by start."""
    out = {}
    for p in trace["planes"]:
        if not p["name"].startswith("/device:"):
            continue
        ev = [(n, s, s + d, c) for l in p["lines"] if l["name"] == "XLA Ops"
              for n, s, d, c in l["events"]]
        if ev:
            out[p["name"]] = sorted(ev, key=lambda e: (e[1], -e[2]))
    return out


def host_spans(trace):
    """``[(name, start, end)]`` of the benchmark's own host annotations."""
    ev = [(n, s, s + d) for p in trace["planes"] if not p["name"].startswith("/device:")
          for l in p["lines"] for n, s, d, _c in l["events"] if n in HOST_SPANS]
    return sorted(ev, key=lambda e: e[1])


def union(intervals):
    """Merged ``[(start, end)]`` of possibly overlapping ``(start, end)`` pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The parts of merged intervals ``a`` that no interval of merged ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(ops):
    """``{name: seconds}`` of each op's own time: its interval less its children's.

    Ops of one device nest (a ``while`` holds the ops of its body); sorted by
    start, a stack gives each op its parent."""
    out, stack = {}, []

    def close(item):
        name, s, e, child = item
        out[name] = out.get(name, 0.0) + max(0.0, (e - s) - child) / 1e9

    for name, s, e, _c in ops:
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def is_container(name, category=""):
    return (category.split(":")[0] or name.lstrip("%").split(".")[0]) in CONTAINERS


def is_pallas(name, category=""):
    """A Pallas kernel on the device line: a Mosaic custom call
    (``custom_call_target="tpu_custom_call"``)."""
    return category == "custom-call:tpu_custom_call"


def is_collective(name, category=""):
    base = category.split(":")[0] or name.lstrip("%").split(".")[0]
    return any(base.startswith(c) for c in COLLECTIVES)


def window_of(trace):
    """``(start, end)`` of the traced window: first enqueue's start to last fetch's end;
    without host spans, the extent of the device ops."""
    spans = host_spans(trace)
    if spans:
        return spans[0][1], max(e for _n, _s, e in spans)
    ops = [e for v in device_ops(trace).values() for e in v]
    return min(e[1] for e in ops), max(e[2] for e in ops)


def reduce(trace):
    """The trace's numbers, averaged over the devices that ran operations.

    ``busy_s``: union of the device-op intervals inside the window.
    ``idle_pct``: 100 * (1 - busy / window).  ``pallas_pct``: share of busy time
    that is the own time of Pallas custom calls.  ``collective_exposed_pct``:
    100 * time in which a collective runs and no other op does / window.
    ``device_ops``/``idle_gaps``: the ten largest, for the breakdown."""
    lo, hi = window_of(trace)
    window_s = (hi - lo) / 1e9
    per_dev = device_ops(trace)
    if not per_dev or window_s <= 0:
        return None
    spans = host_spans(trace)
    busy, pallas, exposed = [], [], []
    op_self, gaps = {}, {}
    for ops in per_dev.values():
        inside = [(n, max(s, lo), min(e, hi), c) for n, s, e, c in ops if e > lo and s < hi]
        leaves = [(n, s, e, c) for n, s, e, c in inside if not is_container(n, c)]
        merged = union([(s, e) for _n, s, e, _c in leaves])
        busy.append(total(merged) / 1e9)
        own = self_times(inside)
        cats = {n: c for n, _s, _e, c in inside}
        for n, sec in own.items():
            if not is_container(n, cats.get(n, "")):
                label = "%s %s" % (n, cats.get(n, ""))
                op_self[label] = op_self.get(label, 0.0) + sec / len(per_dev)
        pallas.append(sum(sec for n, sec in own.items() if is_pallas(n, cats.get(n, ""))))
        coll = union([(s, e) for n, s, e, c in leaves if is_collective(n, c)])
        other = union([(s, e) for n, s, e, c in leaves if not is_collective(n, c)])
        exposed.append(total(subtract(coll, other)) / 1e9)
        for s, e in subtract([(lo, hi)], merged):
            rest = e - s
            for n, a, b in spans:   # what the host was doing while the device waited
                part = min(e, b) - max(s, a)
                if part > 0:
                    what = n.split(":", 1)[1]
                    gaps[what] = gaps.get(what, 0.0) + part / 1e9 / len(per_dev)
                    rest -= part
            if rest > 0:
                gaps["between"] = gaps.get("between", 0.0) + rest / 1e9 / len(per_dev)
    n = len(busy)
    busy_s = sum(busy) / n
    top = sorted(op_self.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_s, "busy_s": busy_s, "devices": n,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "pallas_pct": 100.0 * (sum(pallas) / n) / busy_s if busy_s > 0 else 0.0,
        "collective_exposed_pct": 100.0 * (sum(exposed) / n) / window_s,
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": [[k, v] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
    }
