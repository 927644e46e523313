"""KVStore: parameter synchronization facade.

Reference: ``include/mxnet/kvstore.h`` + ``src/kvstore/`` (SURVEY §5.8).
TPU-native design: the reference's two-level hierarchy (intra-node Comm
reduce/broadcast + inter-node ps-lite) is replaced by

* ``local`` / ``device``: in-process reduce across per-device arrays — XLA
  executes the sum; ``device`` and ``local`` coincide because jax.Arrays
  already live on device (the CPU-staging split of CommCPU vs CommDevice,
  `comm.h:60-385`, is moot on TPU).
* ``dist_sync`` / ``dist_async``: multi-host collectives over ICI/DCN via
  ``jax.distributed`` — see :mod:`mxnet_tpu.parallel`.  The ps-lite
  push/pull RPC protocol (`kvstore_dist.h`) is replaced wholesale by psum;
  sync semantics (sum over exactly-N workers) match the reference's server
  aggregation (`kvstore_dist_server.h:164-199`).

The user-facing API (init/push/pull/set_updater/rank/num_workers/barrier)
keeps the reference's shape so Module and user scripts port unchanged;
per-worker per-key push→pull ordering holds trivially (synchronous calls).
"""
from __future__ import annotations

import pickle

import numpy as np

from .base import MXNetError
from . import ndarray
from . import telemetry
from .ndarray import NDArray
from . import optimizer as opt

__all__ = ["KVStore", "create"]

_PUSH_BYTES = telemetry.counter("mxtpu_kvstore_push_bytes_total")
_PULL_BYTES = telemetry.counter("mxtpu_kvstore_pull_bytes_total")


def _nbytes(arr):
    """Size in bytes of one pushed/pulled array (traffic accounting)."""
    n = 1
    for d in arr.shape:
        n *= int(d)
    try:
        return n * np.dtype(arr.dtype).itemsize
    except TypeError:
        return n * 4


def _ctype_key_value(keys, vals):
    """Normalize (key(s), value(s)) into parallel flat lists."""
    if isinstance(keys, (int, str)):
        keys_flat = []
        vals_flat = []
        if isinstance(vals, NDArray):
            return [keys], [vals]
        for v in vals:
            keys_flat.append(keys)
            vals_flat.append(v)
        return keys_flat, vals_flat
    assert len(keys) == len(vals)
    keys_flat, vals_flat = [], []
    for k, v in zip(keys, vals):
        kf, vf = _ctype_key_value(k, v)
        keys_flat.extend(kf)
        vals_flat.extend(vf)
    return keys_flat, vals_flat


def _group_kv_pairs(keys, vals):
    """Group values by key preserving first-appearance order
    (reference GroupKVPairs, kvstore_local.h:92-118)."""
    uniq, grouped = [], {}
    for k, v in zip(keys, vals):
        if k not in grouped:
            uniq.append(k)
            grouped[k] = []
        grouped[k].append(v)
    return uniq, [grouped[k] for k in uniq]


class KVStore:
    """Single-process store (types 'local', 'device')."""

    def __init__(self, kv_type="local"):
        self._type = kv_type
        self._store = {}
        self._updater = None
        self._optimizer_states = None
        # label children bound once (push/pull run per parameter sync
        # per step — the hot-path pattern, see docs/api/telemetry.md)
        self._push_bytes = _PUSH_BYTES.labels(store=kv_type)
        self._pull_bytes = _PULL_BYTES.labels(store=kv_type)

    # ----------------------------------------------------------------- info
    @property
    def type(self):
        return self._type

    @property
    def rank(self):
        return 0

    @property
    def num_workers(self):
        return 1

    def get_rank(self):
        return self.rank

    def get_group_size(self):
        return self.num_workers

    # ------------------------------------------------------------------ api
    def init(self, key, value):
        keys, vals = _ctype_key_value(key, value)
        for k, v in zip(keys, vals):
            if k in self._store:
                raise MXNetError("duplicate init of key %s" % str(k))
            self._store[k] = v.copy()

    def push(self, key, value, priority=0):
        keys, vals = _ctype_key_value(key, value)
        self._push_bytes.inc(sum(_nbytes(v) for v in vals))
        uniq, grouped = _group_kv_pairs(keys, vals)
        for k, group in zip(uniq, grouped):
            # per-device values reduce on the first one's device, and
            # the update runs where the stored weight lives (reference
            # comm.h: copy to the merge buffer, then reduce)
            merged = group[0].copy()
            for other in group[1:]:
                merged += other.as_in_context(merged.context)
            if self._updater is not None:
                if k not in self._store:
                    raise MXNetError("key %s has not been inited" % str(k))
                stored = self._store[k]
                self._updater(k, merged.as_in_context(stored.context),
                              stored)
            else:
                self._store[k] = merged

    def pull(self, key, out=None, priority=0):
        assert out is not None
        keys, outs = _ctype_key_value(key, out)
        self._pull_bytes.inc(sum(_nbytes(o) for o in outs))
        for k, o in zip(keys, outs):
            if k not in self._store:
                raise MXNetError("key %s has not been inited" % str(k))
            o[:] = self._store[k]

    # ------------------------------------------------------------- updater
    def set_updater(self, updater):
        self._updater = updater

    _set_updater = set_updater

    def set_optimizer(self, optimizer):
        """Use ``optimizer`` for server-side updates.  Single-process:
        equivalent to a local updater (reference routes this through a
        pickled command to dist servers, kvstore.py:226-270)."""
        self._updater_obj = opt.get_updater(optimizer)
        self.set_updater(
            lambda key, grad, weight: self._updater_obj(key, grad, weight))

    # ---------------------------------------------------------- distributed
    def barrier(self):
        pass

    def send_command_to_servers(self, head, body):
        pass

    def get_num_dead_node(self, node_id, timeout=0):
        return 0

    # ------------------------------------------------------- optim states
    def save_optimizer_states(self, fname):
        assert getattr(self, "_updater_obj", None) is not None, \
            "Cannot save states for distributed training"
        with open(fname, "wb") as fout:
            fout.write(self._updater_obj.get_states())

    def load_optimizer_states(self, fname):
        assert getattr(self, "_updater_obj", None) is not None, \
            "Cannot load states for distributed training"
        with open(fname, "rb") as fin:
            self._updater_obj.set_states(fin.read())


def create(name="local"):
    """Create a KVStore (reference kvstore.cc:17-45 name dispatch).

    ``dist_sync``: jitted pytree AllReduce over jax.distributed
    (parallel/dist_kvstore.py).  ``dist_async`` under a launch.py job:
    the host-driven asynchronous parameter server
    (parallel/async_kvstore.py — per-push server-side updates, the
    reference kvstore_dist_server.h:200-208 contract); single-process
    ``dist_async`` falls through to the sync facade with its warning.
    """
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    if "async" in name:
        from . import config
        if (config.get_int("MXNET_TPU_NUM_PROCESSES") or 1) > 1:
            from .parallel.async_kvstore import AsyncKVStore
            return AsyncKVStore(name)
    if "dist" in name:
        from .parallel.dist_kvstore import DistKVStore
        return DistKVStore(name)
    return KVStore(name)
