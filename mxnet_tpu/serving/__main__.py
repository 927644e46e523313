"""Serving replica CLI: ``python -m mxnet_tpu.serving --model mlp``.

Builds a zoo model with freshly initialized weights (or loads a
checkpoint prefix), AOT-compiles the batch ladder, and serves forever.
Designed to run under ``tools/launch.py --fleet -n N``: each replica
reads its rank from ``MXNET_TPU_PROCESS_ID`` and binds ``--port`` +
rank; a SIGKILLed replica is respawned by the fleet watchdog and
re-warms its ladder while its peers keep serving.

SIGTERM exits 0 after closing the batcher (queued requests fail fast
with "batcher stopped"), so supervised teardown is clean.
"""
from __future__ import annotations

import argparse
import os
import signal
import sys


def _build_predictor(opts):
    import mxnet_tpu as mx
    from mxnet_tpu import models

    dims = tuple(int(d) for d in str(opts.data_shape).split(",")
                 if d.strip())
    data_shapes = {"data": (1,) + dims}
    if opts.checkpoint:
        sym_path = opts.checkpoint + "-symbol.json"
        params = "%s-%04d.params" % (opts.checkpoint, opts.epoch)
        return mx.predictor.Predictor(sym_path, params, data_shapes)
    net = models.get_model(opts.model, num_classes=opts.classes)
    mod = mx.module.Module(net, context=mx.cpu())
    label_names = [n for n in net.list_arguments()
                   if n.endswith("label")]
    mod.bind(data_shapes=[("data", (1,) + dims)],
             label_shapes=[(n, (1,)) for n in label_names])
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2.0))
    arg_params, aux_params = mod.get_params()
    params = {}
    for d in (arg_params, aux_params):
        for k, v in d.items():
            params[k] = v
    return mx.predictor.Predictor(net.tojson(), params, data_shapes)


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.serving",
        description="serve a model behind the batch-ladder runtime "
                    "(docs/api/serving.md)")
    parser.add_argument("--model", default="mlp",
                        help="zoo model name (models.get_model)")
    parser.add_argument("--classes", type=int, default=10)
    parser.add_argument("--data-shape", default="64",
                        help="comma-separated non-batch dims of the "
                             "'data' input (e.g. '64' or '3,32,32')")
    parser.add_argument("--checkpoint", default=None,
                        help="checkpoint prefix to serve instead of a "
                             "fresh zoo model (expects "
                             "<prefix>-symbol.json + "
                             "<prefix>-NNNN.params)")
    parser.add_argument("--epoch", type=int, default=0)
    parser.add_argument("--port", type=int, default=None,
                        help="base port (default MXNET_TPU_SERVE_PORT; "
                             "replicas add their launcher rank)")
    parser.add_argument("--ladder", default=None,
                        help="rung spec, e.g. '1,4,16' (default "
                             "MXNET_TPU_SERVE_LADDER)")
    parser.add_argument("--window-ms", type=float, default=None)
    parser.add_argument("--queue-depth", type=int, default=None)
    parser.add_argument("--deadline-ms", type=float, default=None)
    parser.add_argument("--no-budget-check", action="store_true",
                        help="skip the memlive MXG017 gate on the "
                             "largest rung")
    opts = parser.parse_args(argv)

    from mxnet_tpu.base import use_compile_cache
    use_compile_cache()

    # deterministic replica identity: every restart serves the same net
    from mxnet_tpu import random as mx_random
    mx_random.seed(0)

    from mxnet_tpu.serving import BatchLadder, Batcher, Server
    pred = _build_predictor(opts)
    ladder = BatchLadder(pred, rungs=opts.ladder,
                         budget_check=not opts.no_budget_check)
    batcher = Batcher(ladder, window_ms=opts.window_ms,
                      queue_depth=opts.queue_depth,
                      default_deadline_ms=opts.deadline_ms)
    server = Server(ladder, batcher=batcher, port=opts.port)

    def _term(signum, frame):
        batcher.close(timeout=1.0)
        os._exit(0)

    signal.signal(signal.SIGTERM, _term)
    print("serving: model=%s rungs=%s port=%d pid=%d rank=%s"
          % (opts.model, ladder.rungs, server.port, os.getpid(),
             os.environ.get("MXNET_TPU_PROCESS_ID", "0")), flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    batcher.close(timeout=1.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
