"""Executor: a bound, jit-compiled symbolic graph.

Reference: ``include/mxnet/executor.h`` + ``src/executor/graph_executor.cc``
(the Init pass pipeline, SURVEY §3.4) and the python wrapper
``python/mxnet/executor.py``.  TPU-native design: ``bind`` closes the Symbol
DAG over its argument arrays; ``forward`` runs one ``jax.jit``-compiled
function (XLA performs gradient, memory planning, fusion — the whole
reference pass pipeline); ``backward`` runs a jitted ``jax.vjp`` of the same
trace, re-using the forward PRNG key so stochastic ops (Dropout) replay
bit-identically (the reference reuses saved forward state instead,
``autograd.cc:149-240``).

grad_req semantics match the reference ``OpReqType`` (`operator.h:24`):
'write' overwrites the grad array, 'add' accumulates (kAddTo — model-parallel
LSTM relies on it), 'null' skips.
"""
from __future__ import annotations

import functools

from .base import MXNetError
from .context import Context, current_context
from .ndarray import NDArray, zeros as _nd_zeros
from .symbol import eval_graph, _classify_vars

__all__ = ["Executor"]


def _normalize(values, names, kind, default_ctor=None):
    """Accept list/tuple ordered by ``names`` or a dict; return dict."""
    if values is None:
        return {}
    if isinstance(values, dict):
        return dict(values)
    if isinstance(values, (list, tuple)):
        if len(values) != len(names):
            raise MXNetError(
                "%s: expected %d arrays, got %d" % (kind, len(names),
                                                    len(values)))
        return dict(zip(names, values))
    raise TypeError("%s must be list or dict" % kind)


class Executor:
    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None, shared_exec=None,
                 strict=False):
        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else current_context()
        self._group2ctx = group2ctx or {}
        self._monitor_callback = None
        self._monitor_all = False
        # jit-safe stats monitor (telemetry.numerics): per matched node
        # output, a small in-graph stat bundle returned as extra outputs
        # of ONE compiled program — the default Monitor path; the eager
        # per-node _forward_monitored route is opt-in (Monitor(eager=True))
        self._stats_cb = None
        self._stats_pattern = None
        self._stats_active = None
        self._stats_cache = {}

        # model-parallel placement: ctx_group attr -> device (reference
        # AssignContext + PlaceDevice, graph_executor.cc:249-341)
        self._device_map = {}
        if self._group2ctx:
            topo_nodes = symbol._topo()
            for node in topo_nodes:
                if node.is_variable:
                    continue
                grp = node.raw_attr.get("ctx_group")
                dev_ctx = self._group2ctx.get(grp, self._ctx) if grp \
                    else self._ctx
                self._device_map[id(node)] = dev_ctx.jax_device()

        self._topo = symbol._topo()
        self._arg_nodes, self._aux_nodes = _classify_vars(self._topo)
        self._arg_names = [n.name for n in self._arg_nodes]
        self._aux_names = [n.name for n in self._aux_nodes]
        self._output_names = symbol.list_outputs()

        self.arg_dict = _normalize(args, self._arg_names, "args")
        missing = [n for n in self._arg_names if n not in self.arg_dict]
        if missing:
            raise MXNetError("bind: missing argument arrays for %s" % missing)
        self.aux_dict = _normalize(aux_states, self._aux_names, "aux_states")
        for n in self._aux_names:
            if n not in self.aux_dict:
                raise MXNetError("bind: missing auxiliary state %r" % n)

        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in self._arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(self._arg_names, grad_req))
        else:
            self._grad_req = {n: grad_req.get(n, "null")
                              for n in self._arg_names}

        self.grad_dict = _normalize(args_grad, self._arg_names, "args_grad")
        for n, req in self._grad_req.items():
            if req != "null" and n not in self.grad_dict:
                src = self.arg_dict[n]
                self.grad_dict[n] = _nd_zeros(src.shape, ctx=self._ctx,
                                              dtype=src.dtype)

        # strict bind: run the static graph verifier over the EXACT
        # shapes/dtypes being bound, before any jit compile is attempted
        # (the bind-time equivalent of the reference's InferShape pass,
        # with node-level diagnostics instead of a mid-bind throw).
        # MXNET_TPU_STRICT_BIND=1 turns it on globally.
        from . import config as _config
        if strict or _config.get_bool("MXNET_TPU_STRICT_BIND"):
            from .analysis import verify_symbol
            shapes = {n: tuple(self.arg_dict[n].shape)
                      for n in self._arg_names}
            shapes.update({n: tuple(self.aux_dict[n].shape)
                           for n in self._aux_names})
            types = {n: self.arg_dict[n].dtype for n in self._arg_names}
            types.update({n: self.aux_dict[n].dtype
                          for n in self._aux_names})
            # memory-liveness leg (analysis.memlive, MXG017-021): armed
            # only when a budget signal exists — device capacity (or
            # MXNET_TPU_HBM_LIMIT_BYTES) with MXNET_TPU_MEMORY_BUDGET
            # > 0 — so an over-budget graph is rejected HERE, naming
            # its peak node, before any XLA compile is attempted.
            memory = None
            from .telemetry import memory as _tmem
            if _tmem.budget_fraction() > 0 \
                    and _tmem.device_capacity_bytes():
                is_train = any(req != "null"
                               for req in self._grad_req.values())
                memory = {
                    "is_train": is_train,
                    "inputs": {n for n in self._arg_names
                               if self._grad_req.get(n) == "null"},
                    "donate": (),
                    "record": True,
                    "program": ("executor.fused" if is_train
                                else "executor.forward"),
                }
            verify_symbol(symbol, shapes=shapes, types=types,
                          memory=memory).raise_if_errors(
                              "bind strict=True")

        # block-granularity fusion (analysis.fusion): the enable flag is
        # captured at bind time (trace flags are read when jit traces,
        # which happens lazily at first call — long after any caller's
        # context manager exited), and re-activated around every
        # eval_graph trace below so forward, backward, and the fused
        # train path all lower through the same plan.
        from .ops import fused as _fused_mod
        self._block_fusion = _fused_mod.block_fusion_enabled()
        # plan-search decisions (analysis.plansearch): an ambient
        # plan_decisions context is captured like the fusion flag;
        # otherwise the committed graph_plan tuning-cache entry for
        # this graph (keyed by structural digest + trace layout +
        # backend) is consulted ONCE here — a hit activates the
        # searched plan around every trace below, a miss stays greedy
        # with zero per-trace cost (MXNET_TPU_PLAN_SEARCH=off skips
        # the lookup entirely).
        from .analysis import fusion as _fusion_mod
        self._plan_decisions = _fusion_mod.active_decisions()
        if self._plan_decisions is None and self._block_fusion:
            from .analysis import plansearch as _plansearch
            from .ops.nn import current_image_layout
            self._plan_decisions = _plansearch.committed_decisions(
                self._topo, symbol._entries, current_image_layout())

        self._outputs = None
        self._last_key = None
        self._last_train = False
        self._fwd_cache = {}
        self._bwd_cache = {}
        # AOT executables keyed (program, id(jit fn)) — the memory plan
        # comes from the same compile that runs the graph (see
        # telemetry.memory.planned_executable)
        self._aot_exes = {}
        # costdb dispatch scope: process-unique per executor, so id(fn)
        # reuse after another instance's GC cannot alias its counters
        from .telemetry import costdb as _costdb
        self._costdb_scope = _costdb.next_scope()
        # is_loss flag per head (loss heads seed ones, others zeros, when
        # backward() is called without explicit head gradients)
        self._head_is_loss = tuple(
            bool(node.op is not None and node.op.is_loss)
            for (node, _i) in symbol._entries)

    # ------------------------------------------------------------- properties
    @property
    def outputs(self):
        if self._outputs is None:
            raise MXNetError("call forward() first")
        return self._outputs

    @property
    def output_dict(self):
        return dict(zip(self._output_names, self.outputs))

    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) if self._grad_req[n] != "null" else None
                for n in self._arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._aux_names]

    @property
    def grad_req(self):
        return dict(self._grad_req)

    # -------------------------------------------------------------- compile
    def _var_ids(self):
        return [id(n) for n in self._arg_nodes + self._aux_nodes]

    def _get_forward_fn(self, is_train):
        fn = self._fwd_cache.get(is_train)
        if fn is not None:
            return fn
        import jax
        topo, entries = self._topo, self._symbol._entries
        var_ids = self._var_ids()

        from .ops.fused import block_fusion
        from .analysis.fusion import plan_decisions

        def raw(vals, key):
            var_values = dict(zip(var_ids, vals))
            bsz = vals[0].shape[0] if vals and vals[0].ndim else None
            with block_fusion(self._block_fusion), \
                    plan_decisions(self._plan_decisions):
                heads, aux_updates = eval_graph(
                    topo, entries, var_values, is_train=is_train,
                    key=key, batch_size=bsz,
                    device_map=self._device_map)
            n_args = len(self._arg_nodes)
            aux_out = [aux_updates.get(id(n), vals[n_args + i])
                       for i, n in enumerate(self._aux_nodes)]
            return heads, aux_out

        fn = self._compile(raw)
        self._fwd_cache[is_train] = fn
        return fn

    def _multi_device_placed(self):
        return len(set(self._device_map.values())) > 1

    def _compile(self, raw):
        """One XLA program even for a ctx_group-placed graph: the per-node
        jax.device_put calls inside eval_graph become sharding constraints
        under jit, and the GSPMD partitioner pins each segment to its
        device with cross-device copies at the boundaries — the compiled
        equivalent of the reference's PlaceDevice + _CrossDeviceCopy pass
        (graph_executor.cc:249-341), with fusion and donation intact."""
        import jax
        return jax.jit(raw)

    def _place_heads(self, heads):
        """Reference parity: a head produced by a ctx_group-placed node
        lives on that group's device.  jit returns outputs on the default
        device, so placed heads take one device-to-device copy here."""
        if not self._multi_device_placed():
            return heads
        import jax
        placed = []
        for h, (node, _i) in zip(heads, self._symbol._entries):
            dev = self._device_map.get(id(node))
            placed.append(jax.device_put(h, dev) if dev is not None else h)
        return placed

    @staticmethod
    def _maybe_mirror(f):
        """See :func:`mxnet_tpu.ops.nn.maybe_mirror` (kept as a
        late-binding hook so tests can assert the wiring)."""
        from .ops import nn as _nn
        return _nn.maybe_mirror(f)

    def _get_backward_fn(self, with_head_grads):
        key_ = with_head_grads
        fn = self._bwd_cache.get(key_)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        topo, entries = self._topo, self._symbol._entries
        var_ids = self._var_ids()
        diff_idx = tuple(i for i, n in enumerate(self._arg_names)
                         if self._grad_req[n] != "null")
        head_is_loss = self._head_is_loss

        from .ops.fused import block_fusion
        from .analysis.fusion import plan_decisions

        def raw(vals, key, out_grads):
            diff_vals = tuple(vals[i] for i in diff_idx)

            def f(diff):
                full = list(vals)
                for j, i in enumerate(diff_idx):
                    full[i] = diff[j]
                var_values = dict(zip(var_ids, full))
                bsz = full[0].shape[0] if full and full[0].ndim else None
                with block_fusion(self._block_fusion), \
                        plan_decisions(self._plan_decisions):
                    heads, _aux = eval_graph(topo, entries, var_values,
                                             is_train=True, key=key,
                                             batch_size=bsz,
                                             device_map=self._device_map)
                return heads

            heads, vjp = jax.vjp(self._maybe_mirror(f), diff_vals)
            if with_head_grads:
                cot = list(out_grads)
            else:
                cot = [jnp.ones_like(h) if is_loss else jnp.zeros_like(h)
                       for h, is_loss in zip(heads, head_is_loss)]
            (grads,) = vjp(list(cot))
            return grads

        fn = self._compile(raw)
        self._bwd_cache[key_] = fn
        return fn

    def _get_fused_fn(self):
        """Forward + backward + aux update as ONE compiled program — the
        training hot path (Module.forward_backward).  XLA shares the
        forward computation between the primal and the vjp, which the
        separate forward()/backward() pair cannot."""
        fn = self._bwd_cache.get("fused")
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        topo, entries = self._topo, self._symbol._entries
        var_ids = self._var_ids()
        diff_idx = tuple(i for i, n in enumerate(self._arg_names)
                         if self._grad_req[n] != "null")
        head_is_loss = self._head_is_loss
        n_args = len(self._arg_nodes)

        from .ops.fused import block_fusion
        from .analysis.fusion import plan_decisions

        def raw(vals, key):
            diff_vals = tuple(vals[i] for i in diff_idx)

            def f(diff):
                full = list(vals)
                for j, i in enumerate(diff_idx):
                    full[i] = diff[j]
                var_values = dict(zip(var_ids, full))
                bsz = full[0].shape[0] if full and full[0].ndim else None
                with block_fusion(self._block_fusion), \
                        plan_decisions(self._plan_decisions):
                    heads, aux_upd = eval_graph(
                        topo, entries, var_values, is_train=True,
                        key=key, batch_size=bsz,
                        device_map=self._device_map)
                return heads, aux_upd

            heads, vjp, aux_upd = jax.vjp(self._maybe_mirror(f), diff_vals,
                                          has_aux=True)
            cot = [jnp.ones_like(h) if il else jnp.zeros_like(h)
                   for h, il in zip(heads, head_is_loss)]
            (grads,) = vjp(list(cot))
            aux_out = [aux_upd.get(id(n), vals[n_args + i])
                       for i, n in enumerate(self._aux_nodes)]
            return heads, aux_out, grads

        fn = self._compile(raw)
        self._bwd_cache["fused"] = fn
        return fn

    def _dispatch(self, program, fn, args):
        """Run a compiled graph function through its AOT executable,
        registering/budget-checking its memory plan on first use and
        annotating a backend RESOURCE_EXHAUSTED with the plan + live
        HBM forensics (telemetry.memory.dispatch_planned semantics:
        aval drift downgrades to the jit wrapper permanently).

        Cost-database seam (telemetry.costdb): fused blocks traced by
        the compile bind to this program, and sampled dispatches
        (MXNET_TPU_COSTDB_SAMPLE) measure a synchronized wall time
        that lands — with the program's cost_analysis flops/bytes —
        as persistent MFU/roofline records.  Off the hot path: the
        unsampled cost is one counter bump."""
        from .telemetry import costdb as _costdb, memory as _tmem
        obs = _costdb.begin_dispatch(
            program, key=(self._costdb_scope, id(fn)))
        try:
            with _tmem.annotate_oom(program):
                out = _tmem.dispatch_planned(self._aot_exes, program,
                                             fn, args)
        except BaseException:  # mxlint: allow-broad-except(re-raised unchanged — the handler only closes the costdb observation bind-only, so the compile's traced signatures cannot dangle and attach to the next program dispatched)
            _costdb.end_dispatch(obs, failed=True)
            raise
        _costdb.end_dispatch(obs, out=out, args=args)
        return out

    def forward_backward(self, **kwargs):
        """Fused training step: outputs + gradients in one XLA program.
        Equivalent to forward(is_train=True) followed by backward()."""
        from . import telemetry
        with telemetry.span("executor.forward_backward",
                            category="executor"):
            return self._forward_backward(**kwargs)

    def _forward_backward(self, **kwargs):
        if self._monitor_callback is not None or self._stats_active_now():
            self.forward(is_train=True, **kwargs)
            self.backward()
            return self._outputs
        for k, v in kwargs.items():
            self.arg_dict[k]._write(v)
        from . import random as _random
        key = _random.take_key()
        self._last_key = key
        self._last_train = True
        fn = self._get_fused_fn()
        heads, aux_out, grads = self._dispatch(
            "executor.fused", fn, (self._gather_vals(), key))
        for n, upd in zip(self._aux_names, aux_out):
            self.aux_dict[n]._set_data(upd)
        diff_names = [n for n in self._arg_names
                      if self._grad_req[n] != "null"]
        for n, g in zip(diff_names, grads):
            tgt = self.grad_dict[n]
            if self._grad_req[n] == "add":
                tgt._set_data(tgt.data + g)
            else:
                tgt._set_data(g.astype(tgt.dtype))
        self._outputs = [NDArray(h) for h in self._place_heads(heads)]
        return self._outputs

    # ---------------------------------------------------------------- run
    def _gather_vals(self):
        return tuple([self.arg_dict[n].data for n in self._arg_names] +
                     [self.aux_dict[n].data for n in self._aux_names])

    def forward(self, is_train=False, **kwargs):
        """Run the forward graph.  kwargs update named input arrays
        (reference python/mxnet/executor.py:95)."""
        from . import telemetry
        with telemetry.span("executor.forward", category="executor"):
            return self._forward(is_train, **kwargs)

    def _forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown input %r" % k)
            self.arg_dict[k]._write(v)

        from . import random as _random
        key = _random.take_key()
        self._last_key = key
        self._last_train = bool(is_train)

        if self._monitor_callback is not None:
            heads, aux_out = self._forward_monitored(is_train, key)
        elif self._stats_active_now():
            heads, aux_out = self._forward_stats(bool(is_train), key)
        else:
            fn = self._get_forward_fn(bool(is_train))
            heads, aux_out = self._dispatch(
                "executor.forward", fn, (self._gather_vals(), key))
        if is_train:
            for n, upd in zip(self._aux_names, aux_out):
                self.aux_dict[n]._set_data(upd)
        self._outputs = [NDArray(h) for h in self._place_heads(heads)]
        return self._outputs

    def _stats_active_now(self):
        """True when the jit-safe stats monitor should run THIS call
        (installed, and its activation gate — Monitor's interval —
        says so)."""
        return self._stats_cb is not None and \
            (self._stats_active is None or self._stats_active())

    def _get_forward_stats_fn(self, is_train):
        """The jit-safe monitored forward: the same graph trace with a
        per-matched-node stat bundle (telemetry.numerics.tensor_stats —
        a handful of scalar reductions each) as extra outputs.  ONE
        compiled program, no per-node host sync; the per-node monitor
        trace path stays unfused, so every output is visible exactly as
        in the eager route."""
        pattern = self._stats_pattern
        key_ = (bool(is_train), pattern.pattern)
        hit = self._stats_cache.get(key_)
        if hit is not None:
            return hit
        import jax
        from .telemetry import numerics as _numerics
        topo, entries = self._topo, self._symbol._entries
        var_ids = self._var_ids()
        # matched names in TRACE (graph/topo) order — jit returns the
        # stats dict with pytree-sorted keys, but callbacks must fire
        # in the same order the eager monitored route delivers them
        order = []

        def raw(vals, key):
            stats = {}
            order.clear()     # retrace (new shapes) rebuilds the order

            def mon(name, val):
                if pattern.match(str(name)):
                    order.append(str(name))
                    stats[str(name)] = _numerics.tensor_stats(val)

            var_values = dict(zip(var_ids, vals))
            bsz = vals[0].shape[0] if vals and vals[0].ndim else None
            heads, aux_updates = eval_graph(
                topo, entries, var_values, is_train=is_train,
                key=key, monitor=mon, batch_size=bsz,
                device_map=self._device_map)
            n_args = len(self._arg_nodes)
            aux_out = [aux_updates.get(id(n), vals[n_args + i])
                       for i, n in enumerate(self._aux_nodes)]
            return heads, aux_out, stats

        hit = (self._compile(raw), order)
        self._stats_cache[key_] = hit
        return hit

    def _forward_stats(self, is_train, key):
        """Dispatch the stats-monitored forward and deliver each
        matched tensor's host stat bundle to the installed callback
        (one device fetch for ALL bundles, then per-name invocation in
        topo order — non-finite anomalies feed telemetry.numerics)."""
        import jax
        fn, order = self._get_forward_stats_fn(is_train)
        heads, aux_out, stats = self._dispatch(
            "executor.forward_stats", fn, (self._gather_vals(), key))
        host = jax.device_get(stats)
        host = {n: {k: (int(v) if k == "nonfinite" else float(v))
                    for k, v in st.items()}
                for n, st in host.items()}
        from .telemetry import numerics as _numerics
        _numerics.note_monitored(host, program="executor.forward_stats")
        cb = self._stats_cb
        for name in order if len(order) == len(host) else sorted(host):
            cb(name, host[name])
        return heads, aux_out

    def _forward_monitored(self, is_train, key):
        """Eager per-node execution with the monitor callback installed
        (reference GraphExecutor::ExecuteMonCallback, disables bulk exec)."""
        cb = self._monitor_callback

        def monitor(name, val):
            cb(name, NDArray(val))

        vals = self._gather_vals()
        var_values = dict(zip(self._var_ids(), vals))
        bsz = vals[0].shape[0] if vals and vals[0].ndim else None
        heads, aux_updates = eval_graph(
            self._topo, self._symbol._entries, var_values,
            is_train=bool(is_train), key=key, monitor=monitor,
            batch_size=bsz, device_map=self._device_map)
        n_args = len(self._arg_nodes)
        vals = self._gather_vals()
        aux_out = [aux_updates.get(id(n), vals[n_args + i])
                   for i, n in enumerate(self._aux_nodes)]
        return heads, aux_out

    def backward(self, out_grads=None, is_train=True):
        """Accumulate gradients into the bound grad arrays."""
        from . import telemetry
        with telemetry.span("executor.backward", category="executor"):
            return self._backward(out_grads, is_train)

    def _backward(self, out_grads=None, is_train=True):
        if self._outputs is None:
            raise MXNetError("call forward(is_train=True) before backward()")
        if not self._last_train:
            raise MXNetError("backward() requires forward(is_train=True)")
        if out_grads is not None and not isinstance(out_grads, (list, tuple)):
            out_grads = [out_grads]

        with_heads = out_grads is not None
        fn = self._get_backward_fn(with_heads)
        og = tuple(g.data if isinstance(g, NDArray) else g
                   for g in (out_grads or ()))
        grads = self._dispatch("executor.backward", fn,
                               (self._gather_vals(), self._last_key, og))

        diff_names = [n for n in self._arg_names
                      if self._grad_req[n] != "null"]
        for n, g in zip(diff_names, grads):
            tgt = self.grad_dict[n]
            if self._grad_req[n] == "add":
                tgt._set_data(tgt.data + g)
            else:
                tgt._set_data(g.astype(tgt.dtype))

    # ------------------------------------------------------------- utility
    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for k, v in arg_params.items():
            if k in self.arg_dict:
                self.arg_dict[k]._write(v)
            elif not allow_extra_params:
                raise MXNetError("unknown argument %r" % k)
        if aux_params:
            for k, v in aux_params.items():
                if k in self.aux_dict:
                    self.aux_dict[k]._write(v)
                elif not allow_extra_params:
                    raise MXNetError("unknown aux state %r" % k)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Rebind with new input shapes (reference executor.py reshape).
        Returns a new Executor sharing parameter arrays whose shapes are
        unchanged."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args, new_grads, new_aux = {}, {}, {}
        for n, s in zip(self._arg_names, arg_shapes):
            cur = self.arg_dict[n]
            if tuple(cur.shape) == tuple(s):
                new_args[n] = cur
                if n in self.grad_dict:
                    new_grads[n] = self.grad_dict[n]
            else:
                if not (partial_shaping or n in kwargs):
                    raise MXNetError("unexpected shape change for %r" % n)
                old_size = 1
                for d in cur.shape:
                    old_size *= d
                new_size = 1
                for d in s:
                    new_size *= d
                if new_size > old_size:
                    # reference executor.py:402-407: growing an array needs
                    # an explicit opt-in (fresh allocation, values lost)
                    if not allow_up_sizing:
                        raise MXNetError(
                            "new shape of arg %r larger than original; set "
                            "allow_up_sizing=True to allocate new arrays" % n)
                    new_args[n] = _nd_zeros(s, ctx=self._ctx, dtype=cur.dtype)
                    if self._grad_req.get(n, "null") != "null":
                        new_grads[n] = _nd_zeros(s, ctx=self._ctx,
                                                 dtype=cur.dtype)
                else:
                    # same-or-smaller: reinterpret the existing storage
                    # (reference keeps memory shared via arr.reshape)
                    new_args[n] = cur.reshape(s) if new_size == old_size \
                        else _nd_zeros(s, ctx=self._ctx, dtype=cur.dtype)
                    if self._grad_req.get(n, "null") != "null":
                        g = self.grad_dict.get(n)
                        new_grads[n] = (g.reshape(s)
                                        if g is not None and new_size == old_size
                                        else _nd_zeros(s, ctx=self._ctx,
                                                       dtype=cur.dtype))
        for n, s in zip(self._aux_names, aux_shapes):
            cur = self.aux_dict[n]
            new_aux[n] = cur if tuple(cur.shape) == tuple(s) else \
                _nd_zeros(s, ctx=self._ctx, dtype=cur.dtype)
        return Executor(self._symbol, self._ctx, new_args, new_grads,
                        self._grad_req, new_aux, group2ctx=self._group2ctx)

    def set_monitor_callback(self, callback, monitor_all=False):
        """Install the EAGER per-node monitor (reference semantics:
        ``_forward_monitored`` executes node-by-node with a host sync
        per callback).  The jit-safe default is
        :meth:`set_stats_monitor`."""
        self._monitor_callback = callback
        self._monitor_all = monitor_all

    def set_stats_monitor(self, callback, pattern=".*", active=None):
        """Install the jit-safe stats monitor: ``callback(name,
        stats)`` fires per node output matching ``pattern`` with the
        in-graph stat bundle (``l2``/``mean_abs``/``max_abs``/
        ``nonfinite``/``zero_frac`` floats — telemetry.numerics), all
        computed inside ONE compiled forward.  ``active``: optional
        zero-arg gate (Monitor passes its interval latch) — when it
        returns False the plain forward program runs untouched.
        ``callback=None`` uninstalls."""
        import re as _re
        self._stats_cb = callback
        self._stats_pattern = (pattern if hasattr(pattern, "match")
                               else _re.compile(pattern))
        self._stats_active = active
        self._stats_cache = {}

    def debug_str(self):
        lines = ["Symbol Outputs:"]
        for n in self._output_names:
            lines.append("\toutput[%s]" % n)
        for node in self._topo:
            if node.is_variable:
                lines.append("Variable:%s" % node.name)
            else:
                lines.append("--------------------")
                lines.append("Op:%s, Name=%s" % (node.op.name, node.name))
                for (src, idx) in node.inputs:
                    lines.append("\targ[%d]=%s" % (idx, src.name))
        return "\n".join(lines)
