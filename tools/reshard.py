#!/usr/bin/env python
"""Offline checkpoint resharder (elastic training, docs/api/reshard.md).

Rewrites a manifest-verified checkpoint for a DIFFERENT target mesh
without any devices: checkpoint files hold full (gathered) arrays, so
the conversion validates the target partition layout array-by-array
(``parallel/reshard.plan_reshard``), streams the arrays through —
never holding more than the file's worth of host memory — and commits
a new CRC manifest whose schema-v2 mesh descriptor makes any later
``ShardedTrainer.load_checkpoint`` on that mesh a plain (non-reshaping)
load.  The ``reshard.gather``/``reshard.scatter`` fault seams fire per
array, so ``tools/chaos_run.py`` specs cover this path too.

Usage::

    # convert epoch 12 of ./job for a {data:4, model:2} mesh
    python tools/reshard.py ./job --epoch 12 --out ./job_v2 \
        --mesh data=4,model=2

    # with a hand-written rule table (regex=axis,axis;... or @file.json)
    python tools/reshard.py ./job --out ./job_v2 --mesh data=8 \
        --rules '.*fc1_weight=model;.*='

    # prove the conversion: bit-compare out vs src, then roundtrip back
    python tools/reshard.py ./job --out ./job_v2 --mesh data=8 --verify

    # CI gate (tools/ci_check.py stage 10): save on a fake
    # {data:2, model:2} mesh, reshard-load on {data:4} and on a single
    # device, bit-exact against a gather reference, plus a --verify
    # roundtrip — needs no hardware (virtual CPU devices)
    python tools/reshard.py --selfcheck

Exit code 0 = converted (and verified when asked); nonzero with a
descriptive message otherwise.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def parse_mesh(spec):
    """``"data=4,model=2"`` → ``{"data": 4, "model": 2}`` (the
    build_mesh_from_axes/mesh-descriptor axes form); ``""``/``"1"`` →
    ``{}`` (single device).  Delegates to the shared
    ``parallel.reshard.parse_axes`` grammar."""
    from mxnet_tpu.parallel.reshard import parse_axes
    return parse_axes(spec)


def _read_arrays(prefix, epoch):
    """(arrays, states, manifest): {name: np.ndarray} from the params
    file (names keep their arg:/aux: prefixes), the .states dict or
    None, and the parsed manifest.  CRC-verifies first; the
    reshard.gather seam fires per array."""
    import numpy as np
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu import resilience
    from mxnet_tpu.base import MXNetError

    manifest = resilience.verify_manifest(prefix, epoch)
    path = "%s-%04d.params" % (prefix, epoch)
    try:
        loaded = nd.load(path)
    except FileNotFoundError as e:
        raise MXNetError("checkpoint params file %r is missing for "
                         "epoch %d" % (path, epoch)) from e
    arrays = {}
    for k in sorted(loaded):
        resilience.fault_point("reshard.gather")
        arrays[k] = np.asarray(loaded[k].asnumpy())
    states = None
    spath = "%s-%04d.states" % (prefix, epoch)
    if os.path.exists(spath):
        states = {}
        for k, v in sorted(nd.load(spath).items()):
            resilience.fault_point("reshard.gather")
            states[k] = np.asarray(v.asnumpy())
    return arrays, states, manifest


def convert(prefix, epoch, out_prefix, axes, rules=None, kind="offline"):
    """Convert one checkpoint epoch for the target mesh ``axes``.

    Returns the reshard plan (``parallel/reshard.plan_reshard`` form).
    Raises :class:`~mxnet_tpu.base.MXNetError` when the target layout
    is infeasible (nothing is written) — the offline twin of the
    trainer's reshard-on-load."""
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu import resilience
    from mxnet_tpu.parallel import reshard as R

    t0 = time.perf_counter()
    arrays, states, manifest = _read_arrays(prefix, epoch)
    src_desc = R.manifest_mesh(manifest)

    # target specs: an explicit rule table wins; otherwise carry the
    # saved specs forward, dropping entries whose axis the target mesh
    # does not have (they degenerate to replicated)
    param_shapes = {k.split(":", 1)[1]: arrays[k].shape
                    for k in arrays if k.startswith("arg:")}
    if rules:
        specs = R.match_partition_rules(R.parse_rules(rules),
                                        param_shapes, default=())
    else:
        saved_specs = (src_desc or {}).get("specs") or {}

        def carry(a):
            # spec entries whose axes the target mesh lacks degenerate
            # to replicated (multi-axis entries drop unless EVERY axis
            # survives — a partial product would shard wrong)
            if isinstance(a, (list, tuple)):
                return [str(x) for x in a] \
                    if all(str(x) in axes for x in a) else None
            return a if a in axes else None

        specs = {}
        for name in param_shapes:
            spec = [carry(a) for a in (saved_specs.get(name) or ())]
            specs[name] = tuple(spec) if any(
                a is not None for a in spec) else ()
    dst_desc = {"format": R.MESH_SCHEMA, "axes": dict(axes),
                "world": (src_desc or {}).get("world", 1),
                "specs": {n: R.spec_to_json(s)
                          for n, s in specs.items()}}

    # validate BEFORE writing anything: shapes of every array the files
    # carry.  Param specs apply to the arg: entry AND its slotN: twins
    # (optimizer slots shard like their param); aux replicates.
    shapes = {k: v.shape for k, v in arrays.items()}
    if states:
        shapes.update({k: v.shape for k, v in states.items()})

    def flat(specs_map):
        out = {}
        for key in shapes:
            tag, _, name = key.partition(":")
            if tag == "arg" or tag.startswith("slot"):
                s = specs_map.get(name)
                if s:
                    out[key] = R.spec_to_json(s)
        return out

    saved_specs_src = (src_desc or {}).get("specs") or {}
    src_flat = {"axes": (src_desc or {}).get("axes") or {},
                "specs": flat(saved_specs_src)}
    plan = R.plan_reshard(
        src_flat if src_desc is not None else None,
        {"axes": dict(axes), "specs": flat(specs)}, shapes)

    out_dir = os.path.dirname(os.path.abspath(out_prefix))
    os.makedirs(out_dir, exist_ok=True)
    src_sym = "%s-symbol.json" % prefix
    if os.path.exists(src_sym):
        shutil.copyfile(src_sym, "%s-symbol.json" % out_prefix)
    files = []
    out_params = "%s-%04d.params" % (out_prefix, epoch)
    # the scatter seam fires per array AROUND the staged writes: an
    # injected fault with after=K lands before the params write, or —
    # past len(arrays) — between the params and states files (a real
    # mid-conversion crash window; the unwritten manifest keeps the
    # partial output epoch invisible to loaders)
    for _k in sorted(arrays):
        resilience.fault_point("reshard.scatter")
    resilience.atomic_write(
        out_params,
        lambda tmp: nd.save(tmp, {k: nd.array(v)
                                  for k, v in arrays.items()}),
        fault_site="checkpoint.save")
    files.append(out_params)
    all_arrays = dict(arrays)
    if states is not None:
        for _k in sorted(states):
            resilience.fault_point("reshard.scatter")
        out_states = "%s-%04d.states" % (out_prefix, epoch)
        resilience.atomic_write(
            out_states,
            lambda tmp: nd.save(tmp, {k: nd.array(v)
                                      for k, v in states.items()}))
        files.append(out_states)
        all_arrays.update(states)
    meta = dict((manifest or {}).get("meta") or {})
    meta["mesh"] = dst_desc
    resilience.write_manifest(out_prefix, epoch, files,
                              arrays=all_arrays, meta=meta)
    R.note_reshape(kind, plan, seconds=time.perf_counter() - t0,
                   epoch=epoch)
    return plan


def verify_roundtrip(prefix, epoch, out_prefix, say=print):
    """Bit-compare the converted checkpoint against the source, then
    convert it BACK onto the source mesh into a scratch prefix and
    bit-compare again.  Returns a list of problem strings."""
    import numpy as np
    from mxnet_tpu.parallel import reshard as R

    problems = []
    src_arrays, src_states, src_man = _read_arrays(prefix, epoch)
    out_arrays, out_states, out_man = _read_arrays(out_prefix, epoch)

    def compare(leg, a, b):
        if set(a) != set(b):
            problems.append("%s: key sets differ (only in src: %s; "
                            "only in out: %s)"
                            % (leg, sorted(set(a) - set(b)),
                               sorted(set(b) - set(a))))
            return
        for k in a:
            if not np.array_equal(a[k], b[k]):
                problems.append("%s: array %r is not bit-identical"
                                % (leg, k))

    compare("out-vs-src params", src_arrays, out_arrays)
    if (src_states is None) != (out_states is None):
        problems.append("states file present on only one side")
    elif src_states is not None:
        compare("out-vs-src states", src_states, out_states)

    src_axes = R.normalized_axes(
        (R.manifest_mesh(src_man) or {}).get("axes"))
    back_prefix = out_prefix + ".roundtrip"
    convert(out_prefix, epoch, back_prefix, src_axes)
    back_arrays, back_states, _ = _read_arrays(back_prefix, epoch)
    compare("roundtrip params", src_arrays, back_arrays)
    if src_states is not None and back_states is not None:
        compare("roundtrip states", src_states, back_states)
    for f in os.listdir(os.path.dirname(os.path.abspath(back_prefix))):
        if f.startswith(os.path.basename(back_prefix)):
            os.remove(os.path.join(
                os.path.dirname(os.path.abspath(back_prefix)), f))
    if not problems:
        say("verify: out-vs-src and roundtrip both bit-identical "
            "(%d params%s)" % (len(src_arrays),
                               "" if src_states is None else
                               ", %d state arrays" % len(src_states)))
    return problems


def selfcheck():
    """The CI gate (ci_check stage 10): on virtual CPU devices, save a
    small trainer on a {data:2, model:2} mesh, reshard-load on {data:4}
    and on a single device with bit-exact params/aux/optimizer state
    against a gather reference, step once on each target mesh, and run
    an offline --verify roundtrip.  Prints ``reshard selfcheck OK`` and
    returns 0 on success."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import tempfile

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel import (ShardedTrainer, build_mesh_from_axes,
                                    multihost)

    def make(axes):
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, name="fc1", num_hidden=32)
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, name="fc2", num_hidden=10)
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        return ShardedTrainer(
            net, build_mesh_from_axes(axes),
            data_shapes={"data": (8, 64)},
            label_shapes={"softmax_label": (8,)},
            learning_rate=0.1, momentum=0.9, seed=1)

    rng = np.random.RandomState(0)
    batch = {"data": rng.rand(8, 64).astype(np.float32),
             "softmax_label": (np.arange(8) % 10).astype(np.float32)}
    workdir = tempfile.mkdtemp(prefix="mxtpu_reshard_selfcheck_")
    prefix = os.path.join(workdir, "job")

    src = make({"data": 2, "model": 2})
    if not src.tp_rules:
        print("selfcheck FAILED: source trainer derived no tp_rules — "
              "the reshape would not move any shards")
        return 1
    for _ in range(2):
        src.step(batch)
    src.save_checkpoint(prefix, 2, save_optimizer_states=True)

    def gather(t):
        out = {k: multihost.gather_to_host(v) for k, v in t.params.items()}
        out.update({"aux:" + k: multihost.gather_to_host(v)
                    for k, v in t.aux.items()})
        for k, slots in t.opt_state.items():
            for i, s in enumerate(slots):
                out["slot%d:%s" % (i, k)] = multihost.gather_to_host(s)
        return out

    ref = gather(src)
    for axes in ({"data": 4}, {}):
        t = make(axes)
        t.load_checkpoint(prefix, 2, load_optimizer_states=True)
        got = gather(t)
        for k in ref:
            if not np.array_equal(ref[k], got[k]):
                print("selfcheck FAILED: %r differs after reshard onto "
                      "%r" % (k, axes))
                return 1
        t.step(batch)          # the resumed trainer must actually run
        print("selfcheck: reshard onto %s bit-exact (params+aux+opt)"
              % (axes or {"1": 1}))

    n_reshards = telemetry.counter("mxtpu_reshard_total").labels(
        kind="load").get()
    if n_reshards < 2:
        print("selfcheck FAILED: expected >= 2 reshard-load events, "
              "metrics saw %s" % n_reshards)
        return 1

    out_prefix = os.path.join(workdir, "conv", "job")
    convert(prefix, 2, out_prefix, {"data": 4})
    problems = verify_roundtrip(prefix, 2, out_prefix)
    for p in problems:
        print("selfcheck FAILED: %s" % p)
    if problems:
        return 1
    print("reshard selfcheck OK")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="reshard", description=__doc__.splitlines()[0])
    ap.add_argument("prefix", nargs="?",
                    help="source checkpoint prefix")
    ap.add_argument("--epoch", type=int, default=None,
                    help="epoch to convert (default: newest epoch that "
                         "passes full CRC verification)")
    ap.add_argument("--out", default=None,
                    help="output checkpoint prefix")
    ap.add_argument("--mesh", default="",
                    help="target mesh axes, e.g. data=4,model=2 "
                         "(empty = single device)")
    ap.add_argument("--rules", default=None,
                    help="partition rule table for the target mesh "
                         "(parallel.reshard grammar: "
                         "'regex=axis,axis;...' or @file.json); "
                         "default: carry the saved specs forward")
    ap.add_argument("--verify", action="store_true",
                    help="bit-compare out vs src and roundtrip back")
    ap.add_argument("--selfcheck", action="store_true",
                    help="run the CI end-to-end gate on virtual devices")
    args = ap.parse_args(argv)

    if args.selfcheck:
        return selfcheck()
    if not args.prefix or not args.out:
        ap.error("prefix and --out are required (or use --selfcheck)")
    try:
        axes = parse_mesh(args.mesh)
    except ValueError as e:
        ap.error(str(e))

    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.model import find_latest_checkpoint

    epoch = args.epoch
    if epoch is None:
        epoch = find_latest_checkpoint(args.prefix)
        if epoch is None:
            print("reshard: no CRC-verified checkpoint under %r"
                  % args.prefix, file=sys.stderr)
            return 1
    try:
        plan = convert(args.prefix, epoch, args.out, axes,
                       rules=args.rules)
    except MXNetError as e:
        print("reshard: %s" % e, file=sys.stderr)
        return 1
    print("reshard: epoch %d %s -> %s (%d arrays, %d respec'd, "
          "%d bytes)" % (epoch, plan["src"], plan["dst"],
                         plan["n_params"], plan["n_resharded"],
                         plan["bytes"]))
    if args.verify:
        problems = verify_roundtrip(args.prefix, epoch, args.out)
        for p in problems:
            print("reshard --verify: %s" % p, file=sys.stderr)
        if problems:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
