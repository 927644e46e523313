"""Device contexts: ``mx.cpu() / mx.gpu() / mx.tpu()``.

Reference: ``include/mxnet/base.h:117-208`` (Context{dev_type, dev_id}) and
``python/mxnet/context.py``.  TPU-native design: a Context is a *name* for a
JAX device.  ``tpu`` is first class; ``gpu`` resolves to an accelerator if one
exists (so reference scripts written against ``mx.gpu(0)`` run unchanged on a
TPU chip); ``cpu`` is the host platform.  Multi-device placement and sharding
live in :mod:`mxnet_tpu.parallel`; a plain Context maps to exactly one
``jax.Device``.
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]

_DEVTYPE2ID = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}
_ID2DEVTYPE = {v: k for k, v in _DEVTYPE2ID.items()}


class Context:
    """A device context.  With-statement scoping matches the reference."""

    _default = threading.local()
    devtype2str = _ID2DEVTYPE
    devstr2type = _DEVTYPE2ID

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type, self.device_id = device_type.device_type, device_type.device_id
        else:
            if device_type not in _DEVTYPE2ID:
                raise MXNetError(f"unknown device type {device_type}")
            self.device_type = device_type
            self.device_id = int(device_id)
        self._old_ctx = None

    @property
    def device_typeid(self):
        return _DEVTYPE2ID[self.device_type]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __str__(self):
        return self.__repr__()

    def __enter__(self):
        self._old_ctx = getattr(Context._default, "value", None)
        Context._default.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default.value = self._old_ctx

    # -- JAX device resolution -------------------------------------------
    def jax_device(self):
        """Resolve this context to a concrete jax.Device.

        ``tpu``/``gpu`` -> i-th accelerator (any non-cpu platform, so code
        written for ``mx.gpu()`` runs on a TPU chip); ``cpu`` -> host device.
        """
        import jax
        # local_devices: in a multi-process job (dist kvstore) the global
        # enumeration starts with process 0's devices, which other ranks
        # cannot address — a context always means a device THIS host owns
        # (reference: Context device ids are per-node)
        if self.device_type in ("cpu", "cpu_pinned"):
            devs = (jax.local_devices(backend="cpu") if _has_platform("cpu")
                    else jax.local_devices())
        else:
            devs = _accelerators()
            if not devs:  # CPU-only host: impersonate devices (SURVEY §4.2)
                devs = jax.local_devices()
        if self.device_id >= len(devs):
            raise MXNetError(
                f"context {self} out of range: only {len(devs)} device(s) available")
        return devs[self.device_id]

    @staticmethod
    def from_string(s):
        """Parse 'tpu(0)' / 'cpu' style strings (reference Context::FromString)."""
        s = s.strip()
        if "(" in s:
            name, _, rest = s.partition("(")
            return Context(name.strip(), int(rest.rstrip(")")))
        return Context(s, 0)


def _has_platform(name):
    import jax
    try:
        return bool(jax.devices(name))
    except RuntimeError:
        return False


def _accelerators():
    """This host's non-cpu jax devices, in enumeration order."""
    import jax
    return [d for d in jax.local_devices() if d.platform != "cpu"] or []


def on_tpu():
    """True when jax's default backend is a TPU: the one platform probe
    behind every Pallas-kernel-or-``jnp`` choice (``ops/fused.py``,
    ``ops/pallas_kernels.py``)."""
    import jax
    return jax.default_backend() == "tpu"


def cpu(device_id=0):
    return Context("cpu", device_id)


def gpu(device_id=0):
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def current_context():
    ctx = getattr(Context._default, "value", None)
    return ctx if ctx is not None else Context("cpu", 0)


def num_gpus():
    return len(_accelerators())


def num_tpus():
    return len(_accelerators())
