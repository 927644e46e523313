"""Worker for the multi-host FUSED-path (ShardedTrainer) parity test.

Reference: multi-machine training composes the training loop with
kvstore dist_sync (src/kvstore/kvstore_dist.h:192-238).  Here the
TPU-native performance path itself — ShardedTrainer's single jitted
step — runs over a PROCESS-SPANNING mesh: every process executes the
same XLA program, the data axis spans the processes, and GSPMD's
gradient psum crosses them.  The launcher (tools/launch.py) may start
this worker with any -n; each process gets FUSED_DEVS_PER_PROC virtual
CPU devices, so the global mesh is n*FUSED_DEVS_PER_PROC devices on a
(data x model) grid with tp=2.

The parent test runs this script at n=1 and n=2 with the SAME global
mesh shape and asserts step-for-step loss parity, plus the in-run
resume leg below: rank 0 saves a mid-run checkpoint (gathering the
process-sharded tensor-parallel weights), every rank reloads it into a
FRESH trainer and replays the remaining steps to identical losses.
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_devs = int(os.environ.get("FUSED_DEVS_PER_PROC", "2"))
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=%d" % _devs
    ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.parallel import ShardedTrainer, build_mesh, multihost  # noqa: E402

GBATCH = 64
STEPS = 8
CKPT_STEP = 3          # save after the 4th update
_PROTOS = np.random.RandomState(42).rand(10, 64).astype("f")


def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, name="fc1", num_hidden=64)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=32)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc3", num_hidden=10)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _global_batch(step):
    rng = np.random.RandomState(1000 + step)
    y = rng.randint(0, 10, GBATCH)
    x = (_PROTOS[y] + rng.randn(GBATCH, 64) * 0.25).astype("f")
    return x, y.astype("f")


def _build_trainer(mesh):
    return ShardedTrainer(
        _mlp(), mesh,
        data_shapes={"data": (GBATCH, 64)},
        label_shapes={"softmax_label": (GBATCH,)},
        learning_rate=0.1, momentum=0.9, weight_decay=1e-4, seed=3)


def main():
    multihost.ensure_initialized()
    import jax

    rank, nproc = jax.process_index(), jax.process_count()
    devices = jax.devices()
    assert len(devices) % 2 == 0, devices
    mesh = build_mesh(tp=2, devices=devices)   # (data x model), tp=2

    ckpt = os.environ["FUSED_CKPT_PREFIX"]
    trainer = _build_trainer(mesh)
    # with tp=2 the classifier FC is model-sharded; on the n=2 launch
    # the checkpoint gather below must cross processes
    assert trainer.tp_rules, trainer.tp_rules

    def shard(a):
        per = GBATCH // nproc
        return a[rank * per:(rank + 1) * per]

    losses = []
    for step in range(STEPS):
        x, y = _global_batch(step)
        loss = trainer.step({"data": shard(x),
                             "softmax_label": shard(y)})
        losses.append(float(loss))
        if step == CKPT_STEP:
            trainer.save_checkpoint(ckpt, 0, save_optimizer_states=True)
    assert losses[-1] < losses[0], losses

    # ---- resume leg: fresh trainer, restore, replay steps 4..7
    resumed = _build_trainer(mesh)
    resumed.load_checkpoint(ckpt, 0, load_optimizer_states=True)
    relosses = []
    for step in range(CKPT_STEP + 1, STEPS):
        x, y = _global_batch(step)
        relosses.append(float(resumed.step({"data": shard(x),
                                            "softmax_label": shard(y)})))
    np.testing.assert_allclose(relosses, losses[CKPT_STEP + 1:],
                               rtol=0, atol=1e-6)

    multihost.process_barrier("fused_worker_done")
    print("fused-dist worker %d/%d OK losses=%s"
          % (rank, nproc, json.dumps(losses)))


if __name__ == "__main__":
    main()
