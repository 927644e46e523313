"""Base types, dtype table and error classes.

TPU-native re-design of the reference's core type layer
(`include/mxnet/base.h`, `python/mxnet/base.py`).  There is no C ABI here:
the framework is a single Python package over JAX/XLA, so `base` only holds
the shared primitives every layer uses — dtype mapping, shape type, errors,
and the generic registry (reference: dmlc-core ``Registry`` role, SURVEY §2.2).
"""
from __future__ import annotations

import numpy as _np

__all__ = [
    "MXNetError", "TShape", "DTYPE_TO_NP", "NP_TO_DTYPE", "dtype_np",
    "dtype_id", "string_types", "numeric_types", "use_compile_cache",
]


class MXNetError(RuntimeError):
    """Framework error type (reference: dmlc::Error surfaced via MXGetLastError)."""


def use_compile_cache():
    """Place JAX's persistent compilation cache; returns its directory.

    Called by the entry points that compile real programs
    (``chip_smoke.py``, ``bench.py``, ``python -m mxnet_tpu.serving``,
    the image-classification ``fit``, ``tools/transformer_mfu.py``,
    ``tools/xprof_top.py``) — never at package import, so tests and
    AOT compiles for a described chip stay uncached.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this
    sets nothing; otherwise the cache goes to ``.jax_cache`` in the
    checkout: the path is part of the cache key, so it is fixed."""
    import os
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


string_types = (str,)
numeric_types = (float, int, _np.generic)

# Reference dtype ids (mshadow `kFloat32..kInt8` order used in saved params
# and the C API).  Kept numerically identical so checkpoint interop works.
DTYPE_ID_TO_NP = {
    0: _np.float32,
    1: _np.float64,
    2: _np.float16,
    3: _np.uint8,
    4: _np.int32,
    5: _np.int8,
    6: _np.int64,
    # TPU-native extension: bfloat16 (no reference id; appended after int64).
    7: "bfloat16",
}


def _bfloat16():
    import jax.numpy as jnp
    return jnp.bfloat16


def dtype_np(dtype):
    """Normalize a user dtype spec (str, np.dtype, id) to a numpy dtype."""
    if dtype is None:
        return _np.dtype(_np.float32)
    if isinstance(dtype, int) and not isinstance(dtype, _np.dtype):
        dtype = DTYPE_ID_TO_NP[dtype]
    if dtype == "bfloat16" or getattr(dtype, "__name__", None) == "bfloat16":
        return _np.dtype(_bfloat16())
    return _np.dtype(dtype)


def dtype_id(dtype):
    """Numpy dtype -> reference dtype id (for save format parity)."""
    d = dtype_np(dtype)
    for k, v in DTYPE_ID_TO_NP.items():
        if v == "bfloat16":
            if d.name == "bfloat16":
                return k
        elif _np.dtype(v) == d:
            return k
    raise MXNetError(f"unsupported dtype {dtype}")


# Convenience maps (strings only; bfloat16 resolved lazily)
DTYPE_TO_NP = {v if isinstance(v, str) else _np.dtype(v).name: v
               for v in DTYPE_ID_TO_NP.values()}
NP_TO_DTYPE = {}


class TShape(tuple):
    """Shape tuple (reference: nnvm TShape).  Plain tuple with helpers."""

    @property
    def ndim(self):
        return len(self)

    @property
    def size(self):
        s = 1
        for x in self:
            s *= int(x)
        return s


class _Registry:
    """Generic name->object registry (reference: dmlc Registry / python/mxnet/registry.py)."""

    def __init__(self, kind):
        self.kind = kind
        self._map = {}

    def register(self, obj, name=None, override=False):
        key = (name or getattr(obj, "__name__", None) or str(obj)).lower()
        if key in self._map and not override:
            import warnings
            warnings.warn(f"{self.kind} {key} already registered; overriding")
        self._map[key] = obj
        return obj

    def get(self, name):
        key = str(name).lower()
        if key not in self._map:
            raise MXNetError(f"unknown {self.kind}: {name}. "
                             f"known: {sorted(self._map)}")
        return self._map[key]

    def find(self, name):
        return self._map.get(str(name).lower())

    def names(self):
        return sorted(self._map)
