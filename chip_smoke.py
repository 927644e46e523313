#!/usr/bin/env python
"""chip_smoke — the quickest proof that the training path starts on the chip.

One process, which imports JAX itself and starts no child, drives the
program's main paths once through the entry points a user would call, at
the widths the repo ships (ResNet-50 at 224x224, the transformer at
d=1024 / 16 heads / S=1024; only steps and transformer depth are cut),
with random weights and data made from fixed seeds.  Needs no native
library (``src/*.so``), no network, and nothing outside the checkout.

Phases (``python chip_smoke.py``, one TPU chip):

* ``device``      platform, kind, count; ``mx.tpu(0)`` is that device.
* ``module_fit``  the README's path: ``mx.mod.Module(resnet50,
                  context=mx.tpu(0))``, ``fit`` + ``score`` at batch 32,
                  arrays on the chip, checkpoint round trip.
* ``fused_train`` ``bench.py``'s program: ``ShardedTrainer`` on
                  ResNet-50, bf16 NHWC batch 128, AUTO layouts, fused
                  blocks; loss falls, zero compiles after warm-up.
* ``flash``       the transformer step holds the Pallas flash-attention
                  custom call, and the compiled kernels agree with the
                  ``jnp`` reference at T=1024 (panel) and T=4096
                  (streaming), forward and gradients.
* ``predict``     ``mx.predictor.Predictor(..., ctx=mx.tpu(0))`` on
                  ResNet-50 at batch 32.

``python chip_smoke.py --chips 4`` runs one other phase and nothing
else: ``ShardedTrainer`` over ``build_mesh(tp=2)`` on four chips against
the same seeds and global batch on one chip.

Any phase that raises fails the run.  Without a TPU the script exits
non-zero at ``device`` and prints no result.  The last line of standard
output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

#: the kernel may differ from the f32 reference by this share of the
#: reference's largest magnitude: bf16 keeps 8 bits (2^-8 = 0.004), and
#: the backward sums T such terms
FLASH_TOL = 2e-2


def say(phase, **fields):
    print("chip_smoke %s %s" % (phase, json.dumps(fields, sort_keys=True)),
          flush=True)


#: JAX's own count of persistent-cache hits and misses in this process,
#: and the seconds it reports for reading entries and for compiles saved
CACHE_EVENTS = collections.Counter()


def compiles():
    """(count, seconds, persistent-cache hits, misses) of backend
    compiles so far in this process; a hit counts as a compile, a
    short one."""
    from mxnet_tpu import telemetry
    return (int(telemetry.counter("mxtpu_compile_total").get()),
            float(telemetry.counter("mxtpu_compile_seconds_total").get()),
            CACHE_EVENTS["cache_hits"], CACHE_EVENTS["cache_misses"])


def on_device(arr, dev):
    """``arr`` (NDArray or jax array) lives on ``dev`` and nowhere else."""
    data = getattr(arr, "data", arr)
    return set(data.devices()) == {dev}


#: the image model of every phase but ``flash``: ResNet-50 at 224x224
RESNET_LAYERS = 50
IMAGE = (3, 224, 224)


def resnet50():
    from mxnet_tpu import models
    return models.get_model("resnet%d" % RESNET_LAYERS, num_classes=1000,
                            image_shape="%d,%d,%d" % IMAGE)


def image_batch(seed, n):
    rng = np.random.RandomState(seed)
    return (rng.uniform(-1, 1, (n,) + IMAGE).astype(np.float32),
            rng.randint(0, 1000, n).astype(np.float32))


# ---------------------------------------------------------------- phases

def phase_device(n_chips):
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(
            "chip_smoke FAILED device: jax found platform %r (%s x%d), "
            "not 'tpu'; this script has no CPU mode"
            % (dev.platform, dev.device_kind, len(devs)))
    if len(devs) < n_chips:
        raise SystemExit("chip_smoke FAILED device: --chips %d needs %d "
                         "chips, jax found %d"
                         % (n_chips, n_chips, len(devs)))
    import mxnet_tpu as mx
    assert mx.tpu(0).jax_device() == dev, mx.tpu(0).jax_device()
    assert mx.num_tpus() == len(devs), (mx.num_tpus(), len(devs))
    assert mx.context.on_tpu()
    from importlib.metadata import version
    say("device", platform=dev.platform, kind=dev.device_kind,
        count=len(devs), jax=jax.__version__, jaxlib=version("jaxlib"),
        libtpu=version("libtpu"))
    return dev


def phase_module_fit(dev, batch=32, n_batches=4):
    """README path: Module.fit + score on ResNet-50, then a checkpoint
    round trip.  Returns (symbol, arg_params, aux_params) for
    ``predict``."""
    import mxnet_tpu as mx
    x, y = image_batch(0, batch * n_batches)
    it = mx.io.NDArrayIter(x, y, batch, label_name="softmax_label")
    mx.random.seed(0)
    np.random.seed(0)
    mod = mx.mod.Module(resnet50(), context=mx.tpu(0))
    t0 = time.perf_counter()
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
            initializer=mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2.0),
            eval_metric="ce")
    fit_s = time.perf_counter() - t0
    (name, value), = mod.score(it, "ce")
    assert np.isfinite(value), (name, value)

    # the README's context really is the chip: nothing the executor
    # computes with, and nothing it returns, lives on the host
    exe = mod._exec_group.execs[0]
    for kind, arrays in (("arg", exe.arg_dict), ("aux", exe.aux_dict),
                         ("grad", exe.grad_dict)):
        off = [n for n, a in arrays.items() if not on_device(a, dev)]
        assert not off, "%s arrays not on %s: %s" % (kind, dev, off[:5])
    outs = mod.get_outputs()
    assert outs and all(on_device(o, dev) for o in outs), \
        [o.data.devices() for o in outs]
    assert all(np.isfinite(o.asnumpy()).all() for o in outs)

    arg_params, aux_params = mod.get_params()
    with tempfile.TemporaryDirectory() as tmp:
        prefix = os.path.join(tmp, "smoke")
        mod.save_checkpoint(prefix, 1)
        sym, args2, aux2 = mx.model.load_checkpoint(prefix, 1)
    assert sym.list_arguments() == mod.symbol.list_arguments()
    for saved, live in ((args2, arg_params), (aux2, aux_params)):
        assert sorted(saved) == sorted(live)
        for n in live:
            np.testing.assert_array_equal(saved[n].asnumpy(),
                                          live[n].asnumpy(), err_msg=n)
    say("module_fit", fit_s=round(fit_s, 2), batches=n_batches,
        batch=batch, metric=name, value=float(value),
        params=len(arg_params), aux=len(aux_params))
    return mod.symbol, arg_params, aux_params


def bench_trainer(mesh, batch=128):
    """bench.py's trainer (bench.py main(), its defaults)."""
    from mxnet_tpu.parallel import ShardedTrainer
    return ShardedTrainer(
        resnet50(), mesh,
        data_shapes={"data": (batch,) + IMAGE},
        label_shapes={"softmax_label": (batch,)},
        optimizer="sgd", learning_rate=0.1, momentum=0.9,
        weight_decay=1e-4, dtype="bfloat16", layout="NHWC",
        stem_space_to_depth=True, fuse_blocks=True)


def step_text(trainer):
    """The text of ``trainer.step``'s compiled program."""
    exes = [e for (prog, _), e in trainer._aot_exes.items()
            if prog == "trainer.step"]
    assert exes, "the step has no AOT executable"
    return exes[0].as_text()


def phase_fused_train(dev, batch=128, scan=10, steps=20):
    import jax
    from mxnet_tpu.parallel import build_mesh
    t0 = time.perf_counter()
    trainer = bench_trainer(build_mesh(n_devices=1), batch)
    build_s = time.perf_counter() - t0
    x, y = image_batch(1, batch)
    staged = trainer.put_batch({"data": x, "softmax_label": y})

    # warm-up: both compiled entry points, and the step once more after
    # the scan chain (live state migrates back into the step's layouts)
    t0 = time.perf_counter()
    losses = [float(trainer.step(staged)), float(trainer.step(staged))]
    losses += [float(v) for v in
               np.asarray(trainer.run_steps(staged, scan))]
    losses.append(float(trainer.step(staged)))
    warm_s = time.perf_counter() - t0
    warm_compiles = compiles()

    t0 = time.perf_counter()
    for _ in range(steps):
        losses.append(float(trainer.step(staged)))
    steady_s = time.perf_counter() - t0
    after = compiles()

    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], (losses[0], losses[-1])
    assert after[0] == warm_compiles[0], \
        "%d compiles after warm-up" % (after[0] - warm_compiles[0])
    assert all(on_device(a, dev) for a in jax.tree.leaves(
        (trainer.params, trainer.opt_state, trainer.aux)))
    text = step_text(trainer)
    summary = trainer.fusion_summary()
    say("fused_train", build_s=round(build_s, 2),
        warmup_s=round(warm_s, 2), steady_s=round(steady_s, 3),
        steps=steps, first_loss=losses[0], last_loss=losses[-1],
        compiles_in_steady=after[0] - warm_compiles[0],
        step_tpu_custom_calls=text.count("tpu_custom_call"),
        fusion_summary=summary)
    assert summary and summary.get("blocks"), summary


def flash_compare(shape, causal=True):
    """Compiled flash kernel vs the f32 ``jnp`` reference at ``shape``
    = (batch, seq, heads, head_dim): output and q/k/v gradients."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk

    ks = jax.random.split(jax.random.PRNGKey(shape[1]), 4)
    q, k, v, g = (jax.random.normal(kk, shape, jnp.float32)
                  .astype(jnp.bfloat16) for kk in ks)

    def kernel(q, k, v):
        o, vjp = jax.vjp(lambda *a: pk.flash_attention(*a, causal), q, k, v)
        return (o,) + vjp(g)

    def reference(q, k, v):
        f32 = [a.astype(jnp.float32) for a in (q, k, v)]
        o, vjp = jax.vjp(lambda *a: pk._attention_jnp(*a, causal), *f32)
        return (o,) + vjp(g.astype(jnp.float32))

    compiled = jax.jit(kernel).lower(q, k, v).compile()
    n_calls = compiled.as_text().count("tpu_custom_call")
    assert n_calls, "no Pallas custom call in the flash fwd+bwd program"
    got = compiled(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference)(q, k, v)
    errs = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a = np.asarray(a.astype(jnp.float32))
        b = np.asarray(b)
        assert a.shape == tuple(shape) and np.isfinite(a).all(), name
        errs[name] = float(np.abs(a - b).max() / max(1.0, np.abs(b).max()))
        assert errs[name] <= FLASH_TOL, (shape, name, errs[name])
    return {"shape": list(shape), "tpu_custom_calls": n_calls,
            "max_err_over_max_ref": errs}


def phase_flash(dev, layers=4, steps=3):
    sys.path.insert(0, os.path.join(ROOT, "examples", "transformer"))
    from train_lm import build_bench_trainer
    t0 = time.perf_counter()
    trainer, staged = build_bench_trainer(layers=layers)
    losses = [float(trainer.step(staged)) for _ in range(steps)]
    lm_s = time.perf_counter() - t0
    assert np.isfinite(losses).all(), losses
    n_calls = step_text(trainer).count("tpu_custom_call")
    # context.on_tpu() was true and the jnp attention was NOT taken
    assert n_calls, "no Pallas custom call in the transformer step"
    del trainer, staged
    gc.collect()
    say("flash", lm_s=round(lm_s, 2), layers=layers, steps=steps,
        losses=losses, step_tpu_custom_calls=n_calls,
        panel=flash_compare((16, 1024, 16, 64)),
        streaming=flash_compare((2, 4096, 16, 64)))


def phase_predict(dev, symbol, arg_params, aux_params, batch=32):
    import mxnet_tpu as mx
    params = dict(arg_params)
    params.update(aux_params)
    t0 = time.perf_counter()
    pred = mx.predictor.Predictor(symbol.tojson(), params,
                                  {"data": (batch,) + IMAGE},
                                  ctx=mx.tpu(0))
    x, _ = image_batch(2, batch)
    outs = []
    for _ in range(2):
        pred.forward(data=x)
        outs.append(pred.get_output(0))
    predict_s = time.perf_counter() - t0
    exe = pred._executor
    off = [n for n, a in list(exe.arg_dict.items())
           + list(exe.aux_dict.items()) if not on_device(a, dev)]
    assert not off, "predictor arrays not on %s: %s" % (dev, off[:5])
    assert all(on_device(o, dev) for o in exe.outputs)
    assert outs[0].shape == (batch, 1000), outs[0].shape
    assert np.isfinite(outs[0]).all()
    np.testing.assert_allclose(outs[0].sum(axis=1), 1.0, rtol=1e-3)
    np.testing.assert_array_equal(outs[0], outs[1])
    say("predict", predict_s=round(predict_s, 2), batch=batch,
        out_shape=list(outs[0].shape))


def shard_report(trainer):
    """Bytes each device holds of params + optimizer state + aux, from
    ``addressable_shards``, checked leaf by leaf against what the
    leaf's sharding spec implies."""
    import jax
    held = {}
    for a in jax.tree.leaves((trainer.params, trainer.opt_state,
                              trainer.aux)):
        mesh = a.sharding.mesh
        split = 1
        for axis in jax.tree.leaves(tuple(a.sharding.spec)):
            split *= mesh.shape[axis]
        shards = a.addressable_shards
        assert {s.device for s in shards} == set(mesh.devices.flat), a.shape
        for s in shards:
            assert s.data.nbytes * split == a.nbytes, \
                (a.shape, a.sharding.spec, s.data.shape)
            held[s.device.id] = held.get(s.device.id, 0) + s.data.nbytes
    return held


def phase_four_chips(steps=10, batch=128):
    """ShardedTrainer over ('data': 2, 'model': 2) on four real chips
    against one chip, same seeds and global batch.  The loss
    trajectories must agree within four times the band two one-chip
    runs show, floored at bf16's resolution of the loss."""
    import jax
    from mxnet_tpu.parallel import build_mesh
    x, y = image_batch(1, batch)

    def run(mesh):
        trainer = bench_trainer(mesh, batch)
        staged = trainer.put_batch({"data": x, "softmax_label": y})
        losses = [float(trainer.step(staged)) for _ in range(steps)]
        assert np.isfinite(losses).all(), losses
        return trainer, np.asarray(losses)

    t0 = time.perf_counter()
    one_a = run(build_mesh(n_devices=1))[1]
    one_b = run(build_mesh(n_devices=1))[1]
    gc.collect()
    trainer, four = run(build_mesh(n_devices=4, tp=2))
    wall_s = time.perf_counter() - t0

    assert dict(trainer.mesh.shape) == {"data": 2, "model": 2}
    assert trainer.tp_rules, "no graph-derived tp_rules on a tp=2 mesh"
    held = shard_report(trainer)
    assert len(held) == 4 and min(held.values()) > 0, held
    total = sum(a.nbytes for a in jax.tree.leaves(
        (trainer.params, trainer.opt_state, trainer.aux)))
    # tensor-sharded leaves are halved over 'model': every device holds
    # less than the whole state, and all four hold the same amount
    assert max(held.values()) < total, (held, total)
    assert len(set(held.values())) == 1, held

    band = max(float(np.abs(one_a - one_b).max()),
               2.0 ** -8 * float(np.abs(one_a).max()))
    diff = float(np.abs(four - one_a).max())
    say("four_chips", wall_s=round(wall_s, 2), steps=steps,
        mesh=dict(trainer.mesh.shape), tp_rules=len(trainer.tp_rules),
        state_bytes=total, bytes_per_device=held,
        one_chip_losses=one_a.tolist(), four_chip_losses=four.tolist(),
        one_chip_rerun_max_diff=float(np.abs(one_a - one_b).max()),
        band=band, max_diff=diff)
    assert diff <= 4 * band, (diff, band)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run the four-chip mesh phase and nothing "
                         "else (default 1: the one-chip phases)")
    opts = ap.parse_args(argv)

    t_start = time.perf_counter()
    import jax
    from mxnet_tpu.base import use_compile_cache
    cache_dir = use_compile_cache()
    jax.monitoring.register_event_listener(
        lambda name, **kw: CACHE_EVENTS.update([name.rsplit("/", 1)[-1]])
        if name.startswith("/jax/compilation_cache/") else None)
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw:
        CACHE_EVENTS.update({name.rsplit("/", 1)[-1]: secs})
        if name.startswith("/jax/compilation_cache/") else None)
    dev = phase_device(opts.chips)
    say("cache", dir=cache_dir,
        entries=len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
        else 0)

    if opts.chips == 4:
        phases = [("four_chips", phase_four_chips)]
    else:
        state = {}
        phases = [
            ("module_fit",
             lambda: state.update(ckpt=phase_module_fit(dev))),
            ("fused_train", lambda: phase_fused_train(dev)),
            ("flash", lambda: phase_flash(dev)),
            ("predict", lambda: phase_predict(dev, *state["ckpt"])),
        ]
    for name, fn in phases:
        t0 = time.perf_counter()
        c0 = compiles()
        fn()
        gc.collect()
        c1 = compiles()
        say("phase_done", name=name,
            seconds=round(time.perf_counter() - t0, 2),
            compiles=c1[0] - c0[0],
            compile_seconds=round(c1[1] - c0[1], 2),
            cache_hits=c1[2] - c0[2], cache_misses=c1[3] - c0[3])
    n, secs, hits, misses = compiles()
    say("total", seconds=round(time.perf_counter() - t_start, 2),
        compiles=n, compile_seconds=round(secs, 2), cache_hits=hits,
        cache_misses=misses,
        cache_read_seconds=round(
            CACHE_EVENTS["cache_retrieval_time_sec"], 2),
        cache_saved_seconds=round(
            CACHE_EVENTS["compile_time_saved_sec"], 2))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
