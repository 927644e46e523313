"""Runtime kernel compilation (``mx.rtc``) — Pallas edition.

Reference: ``python/mxnet/rtc.py`` + ``src/common/mxrtc.cc`` — the
reference takes CUDA C source at runtime, NVRTC-compiles it, caches by
source, and launches via the engine.  The TPU-native equivalent takes a
**Pallas kernel body** (python source or a callable) at runtime,
Mosaic-compiles it on first launch (jit tracing = the NVRTC step), and
runs it on NDArrays.

API shape mirrors the reference::

    x = mx.nd.zeros((1000, 10))
    y = mx.nd.zeros((1000, 10))
    rtc = mx.rtc.Rtc('abs', [('x', x)], [('y', y)], '''
        y_ref[:] = jnp.abs(x_ref[:])
    ''')
    rtc.push([x], [y], (1, 1, 1), (1, 1, 1))

The kernel body sees ``<name>_ref`` for every input/output (Pallas
``pl.Ref``), plus ``pl`` / ``pltpu`` / ``jnp`` / ``jax`` and
``grid_dims``/``block_dims`` are accepted for API parity (the TPU grid
is derived from ``grid_dims[0]`` when > 1: the kernel is then launched
over a 1-d grid with ``pl.program_id(0)`` available, like blockIdx.x).
"""
from __future__ import annotations

import numpy as np

from .base import MXNetError
from . import ndarray as _nd

_CACHE = {}


class Rtc:
    """Runtime-compiled kernel over NDArrays (reference rtc.py Rtc)."""

    def __init__(self, name, inputs, outputs, kernel):
        self.name = name
        self._in_names = [n for n, _ in inputs]
        self._out_names = [n for n, _ in outputs]
        if callable(kernel):
            self._kernel = kernel
        else:
            # cache by (name, source, arg names), as mxrtc.cc caches PTX
            # by source: re-creating an Rtc with identical source skips
            # the compile.  Arg names are part of the key because the
            # compiled function's parameters are built from them — same
            # source with different variable names is a different kernel.
            key = (name, kernel,
                   tuple(self._in_names), tuple(self._out_names))
            cached = _CACHE.get(key)
            if cached is None:
                cached = self._compile_source(kernel)
                _CACHE[key] = cached
            self._kernel = cached
        self._call_cache = {}

    def _compile_source(self, source):
        """'NVRTC' step: build a python kernel function from the body
        source with the ref-naming convention."""
        args = ", ".join("%s_ref" % n
                         for n in self._in_names + self._out_names)
        body = "\n".join("    " + line
                         for line in source.strip("\n").split("\n"))
        code = "def _rtc_kernel(%s):\n%s\n" % (args, body)
        ns = {}
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        glb = {"jax": jax, "jnp": jnp, "pl": pl, "pltpu": pltpu,
               "np": np}
        try:
            exec(compile(code, "<mx.rtc:%s>" % self.name, "exec"),
                 glb, ns)
        except SyntaxError as e:
            raise MXNetError("rtc kernel %r failed to compile: %s"
                             % (self.name, e))
        return ns["_rtc_kernel"]

    def push(self, ins, outs, grid_dims=(1, 1, 1), block_dims=(1, 1, 1)):
        """Launch on the given NDArrays; results are written into
        ``outs`` (reference push semantics).  ``grid_dims[0] > 1`` runs a
        1-d Pallas grid (blockIdx.x ≙ pl.program_id(0)); block_dims is
        accepted for parity (the VPU has no thread blocks)."""
        import jax
        from jax.experimental import pallas as pl

        if len(ins) != len(self._in_names) or \
                len(outs) != len(self._out_names):
            raise MXNetError("rtc push: argument count mismatch")
        grid = int(grid_dims[0]) if grid_dims and grid_dims[0] > 1 else None
        out_shapes = tuple(jax.ShapeDtypeStruct(o.shape, o.data.dtype)
                           for o in outs)
        key = (tuple((i.shape, str(i.data.dtype)) for i in ins),
               tuple((o.shape, str(o.data.dtype)) for o in outs), grid)
        fn = self._call_cache.get(key)
        if fn is None:
            interpret = ins[0].context.device_type == "cpu" if ins else True
            kw = {"grid": grid} if grid is not None else {}
            call = pl.pallas_call(self._kernel,
                                  out_shape=list(out_shapes),
                                  interpret=interpret, **kw)
            fn = jax.jit(lambda *a: call(*a))
            self._call_cache[key] = fn
        results = fn(*[i.data for i in ins])
        if not isinstance(results, (list, tuple)):
            results = [results]
        for o, r in zip(outs, results):
            # on-device writeback (no host roundtrip) — same pattern as
            # the imperative aux writeback in ops/__init__.py
            o._set_data(r)
        return outs
