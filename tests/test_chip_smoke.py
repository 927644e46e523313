"""What the chip bring-up rests on, as far as a CPU can check it.

``chip_smoke.py`` and plain ``bench.py`` refuse to run without a TPU;
the compile cache is placed from outside; a context names a device and
writes keep arrays there; the Pallas kernels partition themselves under
a mesh (GSPMD cannot partition a Mosaic kernel).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import base, context
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.parallel import build_mesh
from mxnet_tpu.parallel.mesh import kernel_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd=ROOT, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    for name in ("BENCH_DRYRUN", "JAX_COMPILATION_CACHE_DIR"):
        full.pop(name, None)
    full.update(env)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


# ------------------------------------------------------- entry points

@pytest.mark.parametrize("args", [[], ["--chips", "4"]], ids=["1", "4"])
def test_chip_smoke_fails_without_a_tpu(args):
    res = _run(["chip_smoke.py"] + args)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "platform 'cpu'" in res.stderr          # names what it found
    assert "FAILED device" in res.stderr


def test_bench_without_dry_run_fails_without_a_tpu():
    res = _run(["bench.py"])
    assert res.returncode != 0
    assert '"metric"' not in res.stdout
    assert "'cpu'" in res.stderr and "--dry-run" in res.stderr


def test_the_fused_train_phase_runs_at_a_toy_size(monkeypatch, capsys):
    """``phase_fused_train`` as the chip runs it, but ResNet-18 at 32x32 on
    the CPU: the trainer builds with the options left, both entry points
    compile once, the loss falls and the step's text is read from its AOT
    executable (PR 43's first chip run of it read ``_step_fn.as_text()``,
    which only the removed AUTO-layout step had)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    monkeypatch.setattr(smoke, "RESNET_LAYERS", 18)
    monkeypatch.setattr(smoke, "IMAGE", (3, 32, 32))
    smoke.phase_fused_train(jax.devices()[0], batch=4, scan=2, steps=2)
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("chip_smoke fused_train ")][-1]
    said = json.loads(line.split(" ", 2)[2])
    assert said["compiles_in_steady"] == 0
    assert said["fusion_summary"]["blocks"] > 0
    assert said["step_tpu_custom_calls"] == 0      # the CPU takes no kernel


# ------------------------------------------------------ compile cache

_PRINT_CACHE = ("import jax; from mxnet_tpu.base import use_compile_cache;"
                " print(use_compile_cache());"
                " print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_env_set_sets_nothing_in_code(tmp_path, monkeypatch):
    res = _run(["-c", _PRINT_CACHE],
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert res.returncode == 0, res.stderr
    # the directory jax holds is the one it read from the environment
    assert res.stdout.split() == [str(tmp_path), str(tmp_path)]
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert base.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev   # untouched


def test_compile_cache_unset_is_one_fixed_path_in_the_checkout():
    want = os.path.join(ROOT, ".jax_cache")
    for res in (_run(["-c", _PRINT_CACHE]),
                _run(["-c", _PRINT_CACHE], cwd="/")):
        assert res.returncode == 0, res.stderr
        assert res.stdout.split() == [want, want]


# ---------------------------------------------------------- placement

def _mlp():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_module_and_predictor_stay_on_their_context():
    """``mx.tpu(1)`` is virtual device 1 here; host batches and host
    params written into the executor must not drag it to device 0."""
    dev = jax.devices()[1]
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rng.rand(32, 8).astype("f"),
                           rng.randint(0, 4, 32).astype("f"), 8,
                           label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.tpu(1))
    mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1})
    mod.score(it, "acc")
    exe = mod._exec_group.execs[0]
    for arrays in (exe.arg_dict, exe.aux_dict, exe.grad_dict):
        assert {n for n, a in arrays.items()
                if a.data.devices() != {dev}} == set()
    assert all(o.data.devices() == {dev} for o in mod.get_outputs())

    args, auxs = mod.get_params()
    pred = mx.predictor.Predictor(mod.symbol.tojson(), {**args, **auxs},
                                  {"data": (8, 8)}, ctx=mx.tpu(1))
    pred.forward(data=rng.rand(8, 8).astype("f"))
    exe = pred._executor
    assert all(a.data.devices() == {dev} for a in exe.arg_dict.values())
    assert all(o.data.devices() == {dev} for o in exe.outputs)


def test_ndarray_write_keeps_the_array_on_its_device():
    d0, d1 = jax.devices()[:2]
    a = mx.nd.zeros((4, 3), ctx=mx.tpu(1))
    a[:] = mx.nd.ones((4, 3), ctx=mx.cpu(0))      # full slice, NDArray
    assert a.data.devices() == {d1} and a.asnumpy().sum() == 12
    a[1:3] = np.full((2, 3), 2.0, "f")            # view write, numpy
    assert a.data.devices() == {d1} and a.asnumpy().sum() == 18
    a[0, 0] = 5.0                                 # element, scalar
    assert a.data.devices() == {d1}
    b = mx.nd.ones((4, 3), ctx=mx.cpu(0))
    b[:] = a
    assert b.data.devices() == {d0}


# ------------------------------------------- kernels under a mesh

def test_flash_op_partitions_itself_under_a_mesh(monkeypatch):
    mesh = build_mesh(n_devices=4, tp=2)
    # on the CPU the op takes its jnp branch and the kernel refuses to
    # lower: steer the probe, and run the kernel interpreted
    monkeypatch.setattr(context, "on_tpu", lambda: True)
    real = pk.flash_attention
    monkeypatch.setattr(
        pk, "flash_attention",
        lambda q, k, v, causal, _interpret=False, window=0: real(
            q, k, v, causal, True, window))
    q, k, v = (jax.random.normal(kk, (4, 128, 2, 32), jnp.float32)
               for kk in jax.random.split(jax.random.PRNGKey(0), 3))

    def loss(q, k, v):
        with kernel_mesh(mesh):
            out = pk.flash_attention_op({"causal": True}, None, q, k, v)
        return (out * out).sum(), out

    def ref_loss(q, k, v):
        out = pk._attention_jnp(q, k, v, True)
        return (out * out).sum(), out

    assert "shard_map" in str(jax.make_jaxpr(loss)(q, k, v))
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, want), want_grads = jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    for g, r in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-3, atol=2e-4)


# ------------------------------------------------ the step and the cache

def test_step_compile_goes_through_the_persistent_cache(tmp_path):
    """The step has one compile path and it uses JAX's persistent cache
    (the AUTO-layout path that had to bypass it went with PR 43): the first
    trainer's step writes an entry, the second's is a request the cache
    serves, and the cache is as it was afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    from mxnet_tpu.parallel import ShardedTrainer
    seen = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: seen.append(name))
    prev = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    batch = {"data": np.zeros((8, 8), np.float32),
             "softmax_label": np.zeros((8,), np.float32)}
    try:
        for _ in range(2):       # the second is the cache hit
            seen.clear()
            ShardedTrainer(_mlp(), build_mesh(n_devices=1),
                           data_shapes={"data": (8, 8)},
                           label_shapes={"softmax_label": (8,)}).step(batch)
            assert "/jax/compilation_cache/compile_requests_use_cache" in seen
        assert [f for f in os.listdir(tmp_path) if "train_step" in f]
        assert "/jax/compilation_cache/cache_hits" in seen
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
