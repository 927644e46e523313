"""NDArray: the imperative tensor, backed by an immutable ``jax.Array``.

Reference: ``include/mxnet/ndarray.h`` + ``src/ndarray/ndarray.cc`` +
``python/mxnet/ndarray.py``.  TPU-native re-design:

* The reference NDArray is a mutable buffer whose reads/writes are ordered by
  the threaded dependency engine (``ndarray.h:366-427`` Chunk{storage, var}).
  Here an NDArray is a *mutable handle to an immutable jax.Array*: every
  "in-place" op rebinds the handle.  XLA's async dispatch plays the engine's
  role — ops return immediately, ``wait_to_read`` == ``block_until_ready``
  (reference ``WaitToRead``, ``engine.h:186``).
* Views (``Slice/At/Reshape``, ``ndarray.h:297-331``) share their parent
  handle: writes through a view functionally update the parent and are seen by
  all other views, matching the reference's shared-Chunk semantics.
* ``save``/``load`` keep the reference's name-prefixed container layout
  (``src/c_api/c_api.cc:204-252``, ``ndarray.cc`` NDArray::Save) so Module
  checkpoints interop at the file level.

Op functions (``mx.nd.conv2d`` style) are generated from the op registry at
import time, mirroring ``python/mxnet/ndarray.py:2281-2423``'s codegen over the
C op registry.
"""
from __future__ import annotations

import struct
import sys

import numpy as _np

from .base import MXNetError, dtype_np, dtype_id, DTYPE_ID_TO_NP, numeric_types
from .context import Context, current_context

__all__ = ["NDArray", "array", "zeros", "ones", "full", "empty", "arange",
           "concatenate", "save", "load", "waitall", "onehot_encode", "imdecode"]


def _jnp():
    import jax.numpy as jnp
    return jnp


# Generated op functions (mx.nd.slice, mx.nd.sum, ...) are injected into this
# module's namespace and would shadow python builtins for code below — capture
# the builtins we use first.
_py_slice = slice


class NDArray:
    """Multi-dimensional array on a device context."""

    __slots__ = ("_data", "_view_of", "_index", "_writable", "__weakref__")
    # numpy should defer binary ops to us
    __array_priority__ = 100.0

    def __init__(self, data, view_of=None, index=None, writable=True):
        self._data = data          # jax.Array (None when this is a view)
        self._view_of = view_of    # parent NDArray for writeback views
        self._index = index        # basic-index tuple into parent
        self._writable = writable

    # ------------------------------------------------------------------ core
    @property
    def data(self):
        """The underlying jax.Array (resolving views lazily)."""
        if self._view_of is not None:
            return self._view_of.data[self._index]
        return self._data

    def _set_data(self, new_data):
        """Rebind the handle (the 'write' half of the engine var protocol)."""
        if not self._writable:
            raise MXNetError("NDArray is not writable")
        if self._view_of is not None:
            parent = self._view_of
            parent._set_data(parent.data.at[self._index].set(new_data))
        else:
            self._data = new_data

    @property
    def shape(self):
        return tuple(self.data.shape)

    @property
    def dtype(self):
        return _np.dtype(self.data.dtype)

    @property
    def ndim(self):
        return self.data.ndim

    @property
    def size(self):
        return int(self.data.size)

    @property
    def context(self):
        dev = next(iter(self.data.devices()))
        import jax
        # report the LOCAL index (multi-process global device ids are not
        # valid per-node context ids; reference ctx ids are per-node)
        if dev.platform == "cpu":
            local = jax.local_devices(backend="cpu")
            return Context("cpu", local.index(dev) if dev in local else dev.id)
        # single accelerator platform: report as tpu (gpu alias resolves there)
        accels = [d for d in jax.local_devices() if d.platform != "cpu"]
        idx = accels.index(dev) if dev in accels else dev.id
        return Context("tpu", idx)

    ctx = context

    @property
    def T(self):
        return NDArray(self.data.T)

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __bool__(self):
        if self.size != 1:
            raise ValueError("The truth value of an NDArray with multiple "
                             "elements is ambiguous.")
        return bool(self.asscalar())

    def __repr__(self):
        return f"<NDArray {'x'.join(map(str, self.shape))} @{self.context} " \
               f"{self.dtype.name}>\n{self.asnumpy()!r}"

    # -------------------------------------------------------------- host sync
    def asnumpy(self):
        """Copy to host numpy array (blocks; reference WaitToRead + SyncCopyToCPU)."""
        return _np.asarray(self.data)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def wait_to_read(self):
        self.data.block_until_ready()

    wait_to_write = wait_to_read

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # ------------------------------------------------------------- conversion
    def astype(self, dtype):
        return NDArray(self.data.astype(dtype_np(dtype)))

    def copy(self):
        return NDArray(_jnp().array(self.data))

    def copyto(self, other):
        """Copy into an existing NDArray (in-place write) or to a Context."""
        if isinstance(other, NDArray):
            if other.shape != self.shape:
                raise MXNetError(
                    f"copyto shape mismatch {self.shape} vs {other.shape}")
            other._write(self)
            return other
        if isinstance(other, Context):
            import jax
            return NDArray(jax.device_put(self.data, other.jax_device()))
        raise TypeError(f"copyto does not support type {type(other)}")

    def _target_device(self):
        return next(iter(self.data.devices()))

    def as_in_context(self, ctx):
        if self.context == ctx:
            return self
        return self.copyto(ctx)

    def reshape(self, shape, **kwargs):
        if isinstance(shape, int):
            shape = (shape,)
        from . import ops
        return ops.imperative_invoke("Reshape", self, shape=tuple(shape))

    def broadcast_to(self, shape):
        return NDArray(_jnp().broadcast_to(self.data, tuple(shape)))

    # --------------------------------------------------------------- indexing
    def __getitem__(self, key):
        if isinstance(key, NDArray):
            key = key.asnumpy()
        basic = isinstance(key, (int, _py_slice)) or (
            isinstance(key, tuple) and all(isinstance(k, (int, _py_slice))
                                           for k in key))
        if basic and self._view_of is None:
            # basic indexing -> writeback view (reference Slice/At share Chunk)
            return NDArray(None, view_of=self, index=key,
                           writable=self._writable)
        # nested view or advanced indexing: plain copy (reads only)
        return NDArray(self.data[key])

    def __setitem__(self, key, value):
        import jax
        if isinstance(key, NDArray):
            key = key.asnumpy()
        if isinstance(value, NDArray):
            value = value.data
        elif isinstance(value, numeric_types):
            pass
        else:
            value = _np.asarray(value)
        # a write lands on THIS array's device wherever the value lives
        # (reference: an NDArray's context is fixed at creation): a host
        # batch written into an executor's input must not drag the
        # input, and with it the whole program, onto the host
        root = self._view_of if self._view_of is not None else self
        dev = root._target_device()
        if isinstance(value, jax.Array):
            value = jax.device_put(value, dev)
        if self._view_of is not None:
            sub = root.data[self._index]
            sub = sub.at[key].set(value) if not _is_full_slice(key, sub.ndim) \
                else _jnp().broadcast_to(_jnp().asarray(value, sub.dtype), sub.shape)
            new = root.data.at[self._index].set(sub)
        elif _is_full_slice(key, self.ndim):
            new = _jnp().broadcast_to(
                _jnp().asarray(value, self.dtype), self.shape).astype(self.dtype)
        else:
            new = self.data.at[key].set(value)
        root._set_data(jax.device_put(new, dev))

    def _write(self, value):
        """Rebind to ``value`` (NDArray, jax or host array) cast to this
        array's dtype and moved to this array's device; unlike
        ``self[:] = value`` the value's shape wins."""
        import jax
        if isinstance(value, NDArray):
            value = value.data
        elif not isinstance(value, jax.Array):
            value = _np.asarray(value)
        self._set_data(jax.device_put(
            value, self._target_device()).astype(self.dtype))

    def slice(self, start, stop):
        return self[int(start):int(stop)]

    def at(self, idx):
        return self[int(idx)]

    # ------------------------------------------------------------- arithmetic
    # Routed through the op registry so the autograd tape sees them
    # (reference: python operators dispatch to registered ops,
    # python/mxnet/ndarray.py _ufunc_helper).
    def _binary_op(self, other, op, scalar_op, rscalar_op=None, reverse=False):
        from . import ops
        if isinstance(other, numeric_types):
            name = (rscalar_op or scalar_op) if reverse else scalar_op
            return ops.imperative_invoke(name, self, scalar=float(other))
        if not isinstance(other, NDArray):
            other = array(other)
        a, b = (other, self) if reverse else (self, other)
        return ops.imperative_invoke(op, a, b)

    def _binary(self, other, fn, reverse=False):
        if isinstance(other, NDArray):
            other = other.data
        a, b = (other, self.data) if reverse else (self.data, other)
        return NDArray(fn(a, b))

    def __add__(self, o): return self._binary_op(o, "broadcast_add", "_plus_scalar")
    def __radd__(self, o): return self.__add__(o)
    def __sub__(self, o): return self._binary_op(o, "broadcast_sub", "_minus_scalar", "_rminus_scalar")
    def __rsub__(self, o): return self._binary_op(o, "broadcast_sub", "_minus_scalar", "_rminus_scalar", True)
    def __mul__(self, o): return self._binary_op(o, "broadcast_mul", "_mul_scalar")
    def __rmul__(self, o): return self.__mul__(o)
    def __truediv__(self, o): return self._binary_op(o, "broadcast_div", "_div_scalar", "_rdiv_scalar")
    def __rtruediv__(self, o): return self._binary_op(o, "broadcast_div", "_div_scalar", "_rdiv_scalar", True)
    def __div__(self, o): return self.__truediv__(o)
    def __rdiv__(self, o): return self.__rtruediv__(o)
    def __mod__(self, o): return self._binary_op(o, "broadcast_mod", "_mod_scalar")
    def __pow__(self, o): return self._binary_op(o, "broadcast_power", "_power_scalar", "_rpower_scalar")
    def __rpow__(self, o): return self._binary_op(o, "broadcast_power", "_power_scalar", "_rpower_scalar", True)

    def __neg__(self):
        from . import ops
        return ops.imperative_invoke("_mul_scalar", self, scalar=-1.0)

    def __abs__(self):
        from . import ops
        return ops.imperative_invoke("abs", self)

    def __iadd__(self, o):
        self._set_data((self + o).data.astype(self.dtype))
        return self

    def __isub__(self, o):
        self._set_data((self - o).data.astype(self.dtype))
        return self

    def __imul__(self, o):
        self._set_data((self * o).data.astype(self.dtype))
        return self

    def __itruediv__(self, o):
        self._set_data((self / o).data.astype(self.dtype))
        return self

    def __eq__(self, o):
        if isinstance(o, (NDArray,) + numeric_types) or isinstance(o, _np.ndarray):
            return self._binary(o, _jnp().equal)
        return NotImplemented

    def __ne__(self, o):
        if isinstance(o, (NDArray,) + numeric_types) or isinstance(o, _np.ndarray):
            return self._binary(o, _jnp().not_equal)
        return NotImplemented

    def __gt__(self, o): return self._binary(o, _jnp().greater)
    def __ge__(self, o): return self._binary(o, _jnp().greater_equal)
    def __lt__(self, o): return self._binary(o, _jnp().less)
    def __le__(self, o): return self._binary(o, _jnp().less_equal)
    __hash__ = None

    # ---------------------------------------------------------- reduce sugar
    def _reduce(self, op, axis, keepdims):
        from . import ops
        return ops.imperative_invoke(op, self, axis=axis, keepdims=keepdims)

    def sum(self, axis=None, keepdims=False):
        return self._reduce("sum", axis, keepdims)

    def mean(self, axis=None, keepdims=False):
        return self._reduce("mean", axis, keepdims)

    def max(self, axis=None, keepdims=False):
        return self._reduce("max", axis, keepdims)

    def min(self, axis=None, keepdims=False):
        return self._reduce("min", axis, keepdims)

    def argmax(self, axis=None):
        return NDArray(_jnp().argmax(self.data, axis=axis))

    def argmin(self, axis=None):
        return NDArray(_jnp().argmin(self.data, axis=axis))

    def flatten(self):
        return self.reshape((self.shape[0], -1)) if self.ndim > 1 \
            else self.reshape((self.size,))

    # ------------------------------------------------------------- autograd
    def attach_grad(self, grad_req="write"):
        from . import autograd
        autograd.mark_variables([self], [zeros_like(self)], grad_req)

    @property
    def grad(self):
        from . import autograd
        return autograd._get_grad(self)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from . import autograd
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)


def _is_full_slice(key, ndim):
    return key == _py_slice(None) or (
        isinstance(key, tuple) and len(key) == 0)


# ---------------------------------------------------------------- creation

def _device_for(ctx):
    ctx = ctx or current_context()
    return ctx.jax_device()


def array(source, ctx=None, dtype=None):
    """Create an NDArray from any array-like."""
    import jax
    if isinstance(source, NDArray):
        source = source.asnumpy()
    keep_dtype = isinstance(source, _np.ndarray)
    arr = _np.asarray(source, dtype=dtype_np(dtype) if dtype is not None else None)
    if dtype is None:
        # reference default: python lists become float32; numpy arrays keep
        # their dtype except float64 -> float32 (mx default real type)
        if arr.dtype == _np.float64 or not keep_dtype:
            arr = arr.astype(_np.float32)
    return NDArray(jax.device_put(arr, _device_for(ctx)))


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx, dtype)


def zeros(shape, ctx=None, dtype=None):
    import jax
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    with jax.default_device(_device_for(ctx)):
        return NDArray(_jnp().zeros(shape, dtype_np(dtype)))


def ones(shape, ctx=None, dtype=None):
    import jax
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    with jax.default_device(_device_for(ctx)):
        return NDArray(_jnp().ones(shape, dtype_np(dtype)))


def full(shape, val, ctx=None, dtype=None):
    import jax
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    with jax.default_device(_device_for(ctx)):
        return NDArray(_jnp().full(shape, val, dtype_np(dtype)))


def zeros_like(arr):
    return NDArray(_jnp().zeros_like(arr.data))


def ones_like(arr):
    return NDArray(_jnp().ones_like(arr.data))


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    import jax
    with jax.default_device(_device_for(ctx)):
        out = _jnp().arange(start, stop, step, dtype_np(dtype))
        if repeat > 1:
            out = _jnp().repeat(out, repeat)
        return NDArray(out)


def concatenate(arrays, axis=0, always_copy=True):
    return NDArray(_jnp().concatenate([a.data for a in arrays], axis=axis))


def onehot_encode(indices, out):
    depth = out.shape[1]
    import jax.nn as jnn
    out._set_data(jnn.one_hot(indices.data.astype(_np.int32), depth,
                              dtype=out.dtype))
    return out


def imdecode(buf, **kwargs):  # minimal parity hook; full version in image.py
    from . import image
    return image.imdecode(buf, **kwargs)


def waitall():
    """Block until all async work is done (reference Engine::WaitForAll)."""
    import jax
    jax.effects_barrier()


# ------------------------------------------------------------------ save/load
# Container layout follows the reference (`c_api.cc:204-252`):
#   u64 magic, u64 reserved, u64 n_arrays, arrays..., u64 n_names, names...
# Each array (`ndarray.cc` NDArray::Save):
#   u32 ndim, u32*ndim shape, i32 dev_type, i32 dev_id, i32 type_flag, raw data
_LIST_MAGIC = 0x112


def _write_str(f, s):
    b = s.encode("utf-8")
    f.write(struct.pack("<Q", len(b)))
    f.write(b)


def _read_str(f):
    n, = struct.unpack("<Q", f.read(8))
    return f.read(n).decode("utf-8")


def _save_one(f, arr: NDArray):
    np_arr = _np.ascontiguousarray(arr.asnumpy())
    tid = dtype_id(np_arr.dtype)
    f.write(struct.pack("<I", np_arr.ndim))
    f.write(struct.pack(f"<{np_arr.ndim}I", *np_arr.shape))
    ctx = arr.context
    f.write(struct.pack("<ii", ctx.device_typeid, ctx.device_id))
    f.write(struct.pack("<i", tid))
    if np_arr.dtype.name == "bfloat16":
        f.write(np_arr.view(_np.uint16).tobytes())
    else:
        f.write(np_arr.tobytes())


def _load_one(f):
    ndim, = struct.unpack("<I", f.read(4))
    shape = struct.unpack(f"<{ndim}I", f.read(4 * ndim)) if ndim else ()
    dev_type, dev_id = struct.unpack("<ii", f.read(8))
    tid, = struct.unpack("<i", f.read(4))
    np_dt = dtype_np(DTYPE_ID_TO_NP[tid])
    count = 1
    for s in shape:
        count *= s
    if np_dt.name == "bfloat16":
        raw = _np.frombuffer(f.read(count * 2), dtype=_np.uint16)
        data = raw.view(np_dt).reshape(shape)
    else:
        data = _np.frombuffer(f.read(count * np_dt.itemsize),
                              dtype=np_dt).reshape(shape)
    return array(data, dtype=np_dt)


def save(fname, data):
    """Save a list of NDArrays or dict of str->NDArray (reference MXNDArraySave)."""
    if isinstance(data, NDArray):
        data = [data]
    names, arrays = [], []
    if isinstance(data, dict):
        for k in sorted(data):
            names.append(k)
            arrays.append(data[k])
    else:
        arrays = list(data)
    with open(fname, "wb") as f:
        f.write(struct.pack("<QQ", _LIST_MAGIC, 0))
        f.write(struct.pack("<Q", len(arrays)))
        for a in arrays:
            _save_one(f, a)
        f.write(struct.pack("<Q", len(names)))
        for n in names:
            _write_str(f, n)


def _load_stream(f, what):
    magic, _ = struct.unpack("<QQ", f.read(16))
    if magic != _LIST_MAGIC:
        raise MXNetError(f"invalid NDArray {what}")
    n, = struct.unpack("<Q", f.read(8))
    arrays = [_load_one(f) for _ in range(n)]
    m, = struct.unpack("<Q", f.read(8))
    names = [_read_str(f) for _ in range(m)]
    if names:
        return dict(zip(names, arrays))
    return arrays


def load(fname):
    """Load from :func:`save`'s format; returns list or dict matching input."""
    with open(fname, "rb") as f:
        return _load_stream(f, f"file {fname}")


def load_buffer(buf):
    """Load NDArrays from in-memory bytes (reference
    MXNDArrayLoadFromBuffer, c_api.cc) — the C predict API hands the
    .params content as a buffer, not a path."""
    import io
    return _load_stream(io.BytesIO(buf), "buffer")


# Op functions (mx.nd.relu etc.) are attached by ops/__init__ at import time.
def _register_op_functions(fns):
    mod = sys.modules[__name__]
    for name, fn in fns.items():
        setattr(mod, name, fn)
