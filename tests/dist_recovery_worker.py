"""Worker for the kill-a-worker recovery test (VERDICT r3 #5).

Reference behavior being matched: ps-lite heartbeats detect a dead
node and the job surfaces a failure (src/kvstore/kvstore_dist.h:39-80);
recovery is restart-from-checkpoint.  Here the fused multi-host path
trains with periodic rank-0 checkpoints; in ``crash`` mode one rank
SIGKILLs itself mid-run — the launcher (tools/launch.py supervision)
must tear the job down with a clear error — and in ``resume`` mode a
fresh job loads the last complete checkpoint and trains on to a loss
threshold, proving the checkpoint/resume recovery story end to end.
"""
import glob
import json
import os
import re
import signal
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.parallel import ShardedTrainer, build_mesh, multihost  # noqa: E402

GBATCH = 64
STEPS = 14
CKPT_EVERY = 3
_PROTOS = np.random.RandomState(42).rand(10, 64).astype("f")


def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, name="fc1", num_hidden=64)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=10)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _batch(step):
    rng = np.random.RandomState(500 + step)
    y = rng.randint(0, 10, GBATCH)
    x = (_PROTOS[y] + rng.randn(GBATCH, 64) * 0.2).astype("f")
    return x, y.astype("f")


def _build(mesh):
    return ShardedTrainer(
        _mlp(), mesh,
        data_shapes={"data": (GBATCH, 64)},
        label_shapes={"softmax_label": (GBATCH,)},
        learning_rate=0.15, momentum=0.9, seed=5)


def _latest_epoch(prefix):
    eps = []
    for f in glob.glob(prefix + "-*.params"):
        m = re.search(r"-(\d{4})\.params$", f)
        # only checkpoints whose .states also landed are complete
        if m and os.path.exists("%s-%s.states" % (prefix, m.group(1))):
            eps.append(int(m.group(1)))
    return max(eps) if eps else None


def main():
    # crash: SIGKILL one rank mid-run (launcher must tear the job down)
    # resume: load the last complete checkpoint, finish training
    # auto: the watchdog-restart path — resume from the latest verified
    #       checkpoint if one exists, and crash only on the FIRST
    #       launch attempt (MXNET_TPU_RESTART_COUNT=0); the restarted
    #       job trains to completion
    mode = os.environ["RECOVERY_MODE"]          # crash | resume | auto
    prefix = os.environ["RECOVERY_CKPT"]
    kill_rank = int(os.environ.get("KILL_RANK", "1"))
    kill_step = int(os.environ.get("KILL_STEP", "7"))
    restart_count = int(os.environ.get("MXNET_TPU_RESTART_COUNT", "0"))

    multihost.ensure_initialized()
    import jax

    rank, nproc = jax.process_index(), jax.process_count()
    mesh = build_mesh(devices=jax.devices(),
                      axis_names=("data", "model"), tp=1)
    trainer = _build(mesh)

    start = 0
    if mode == "resume":
        ep = _latest_epoch(prefix)
        assert ep is not None, "no complete checkpoint to resume from"
        trainer.load_checkpoint(prefix, ep, load_optimizer_states=True)
        start = ep
    elif mode == "auto":
        ep = trainer.load_latest_checkpoint(prefix,
                                            load_optimizer_states=True)
        if ep is not None:
            start = ep

    may_kill = mode == "crash" or (mode == "auto" and restart_count == 0)

    def shard(a):
        per = GBATCH // nproc
        return a[rank * per:(rank + 1) * per]

    losses = []
    for step in range(start, STEPS):
        x, y = _batch(step)
        loss = float(trainer.step({"data": shard(x),
                                   "softmax_label": shard(y)}))
        losses.append(loss)
        done = step + 1
        if done % CKPT_EVERY == 0 and done < STEPS:
            trainer.save_checkpoint(prefix, done,
                                    save_optimizer_states=True)
        if may_kill and rank == kill_rank and done == kill_step:
            sys.stderr.write("worker %d: simulating node failure "
                             "(SIGKILL self) at step %d\n" % (rank, done))
            sys.stderr.flush()
            os.kill(os.getpid(), signal.SIGKILL)

    assert losses[-1] < 0.35, losses
    multihost.process_barrier("recovery_done")
    print("recovery worker %d/%d OK mode=%s start=%d losses=%s"
          % (rank, nproc, mode, start, json.dumps(losses)))


if __name__ == "__main__":
    main()
