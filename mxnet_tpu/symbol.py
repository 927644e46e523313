"""Symbol: the declarative graph IR.

Reference: ``python/mxnet/symbol.py`` (2092 L) over nnvm's C++ ``Symbol``/
``Graph`` (SURVEY §2.2).  TPU-native re-design: a Symbol is a lightweight
python DAG of ``_Node``s (op + parsed attrs + input edges).  There is no
separate graph compiler — ``bind`` traces the DAG into one JAX function and
``jax.jit`` is the whole §3.4 pass pipeline (gradient, memory planning,
fusion, placement all happen inside XLA).  Shape inference is one walk in
topological order (:func:`_shape_walk`) that keeps each node's output
``jax.ShapeDtypeStruct``s and runs ``jax.eval_shape`` once per op node over
its own inputs' structs, with per-op parameter-shape hooks
(:mod:`mxnet_tpu.ops.shapes`) standing in for the reference's FInferShape;
:mod:`mxnet_tpu.analysis.verifier` reports from the same walk.

JSON serialization keeps the reference's node/arg_nodes/heads layout
(``nnvm::Symbol::Save``; ``src/c_api/c_api_symbolic.cc:400``) so checkpoints
interop at the file level.
"""
from __future__ import annotations

import contextlib
import json

from .base import MXNetError
from .context import current_context
from . import attribute, name as _name_mod
from .ops import registry as _registry
from .ops.registry import OpContext, apply_op, get_op
from .ops import shapes as _shapes
from .telemetry import plan as _plan

__all__ = ["Symbol", "Variable", "var", "Group", "load", "load_json",
           "zeros", "ones", "arange"]

_META_PREFIX = "__"

# generated op functions (mx.sym.slice, mx.sym.sum, ...) are injected into
# this module's globals and would shadow python builtins used below
_py_slice = slice


class _Node:
    """One graph node: a variable (op is None) or an op application."""
    __slots__ = ("op", "name", "attrs", "raw_attr", "inputs", "num_args")

    def __init__(self, op, name, attrs=None, raw_attr=None, inputs=None,
                 num_args=0):
        self.op = op                    # Operator | None (variable)
        self.name = name
        self.attrs = attrs or {}        # parsed op params
        self.raw_attr = raw_attr or {}  # meta attrs (ctx_group, lr_mult, ...)
        self.inputs = inputs or []      # list[(Node, out_index)]
        self.num_args = num_args        # inputs[:num_args] are args, rest aux

    @property
    def is_variable(self):
        return self.op is None

    def num_outputs(self):
        return 1 if self.op is None else self.op.get_num_outputs(self.attrs)

    def arg_names(self):
        return [] if self.op is None else self.op.get_arg_names(self.attrs)

    def aux_names(self):
        return [] if self.op is None else self.op.get_aux_names(self.attrs)

    def output_names(self):
        n = self.num_outputs()
        if self.op is None:
            return [self.name]
        if n == 1:
            return [self.name + "_output"]
        return ["%s_output%d" % (self.name, i) for i in range(n)]


def _topo_order(entries):
    """Iterative DFS post-order over the DAG (inputs before consumers)."""
    order, visited = [], set()
    stack = [(n, False) for (n, _) in reversed(entries)]
    while stack:
        node, expanded = stack.pop()
        if id(node) in visited:
            continue
        if expanded:
            visited.add(id(node))
            order.append(node)
        else:
            stack.append((node, True))
            for (src, _) in reversed(node.inputs):
                if id(src) not in visited:
                    stack.append((src, False))
    return order


def _classify_vars(topo):
    """Split variable nodes into (args, aux) in first-appearance order."""
    aux_ids = set()
    for node in topo:
        for (src, _) in node.inputs[node.num_args:]:
            if src.is_variable:
                aux_ids.add(id(src))
    args, aux = [], []
    for node in topo:
        if node.is_variable:
            (aux if id(node) in aux_ids else args).append(node)
    return args, aux


def _batch_attrs(node, batch_size):
    """``node.attrs`` with a source op's deferred batch dim filled in.

    A 0 in ``shape`` is deferred ONLY for source ops (zeros/ones/... with no
    inputs, e.g. RNN begin_state) — ops WITH inputs (Reshape, ...) give 0
    its own meaning ("copy this dim from the input") and resolve it
    themselves."""
    shp = node.attrs.get("shape")
    if (node.inputs or not isinstance(shp, (tuple, list))
            or all(s != 0 for s in shp)):
        return node.attrs
    if batch_size is None:
        raise MXNetError(
            "node %r has a deferred (0) dim in shape %s but no "
            "batch size is known" % (node.name, shp))
    return dict(node.attrs, shape=tuple(batch_size if s == 0 else int(s)
                                        for s in shp))


def _input_structs(node, structs):
    """The structs of ``node``'s inputs, or None while one is unknown."""
    ins = []
    for (src, idx) in node.inputs:
        st = structs.get(id(src))
        if st is None or len(st) <= idx:
            return None
        ins.append(st[idx])
    return ins


def _shape_walk(topo, known, types=None):
    """Shape inference: ONE walk in topological order.

    Keeps a ``jax.ShapeDtypeStruct`` tuple per node and evaluates each op
    node once, over its own inputs' structs.  A variable's shape comes from
    ``known`` ({name: shape}), else its ``__shape__``, else from the
    param-shape hook of the first op that consumes it, which reads its
    op-output inputs' shapes off the structs already held.  ``types``
    ({name: dtype}) overrides a variable's ``__dtype__``.

    Returns ``(var_shapes, structs, faults)``: ``{id(var): shape}``,
    ``{id(node): tuple(struct) | None}`` and, in walk order, what stopped a
    node from being evaluated as ``(node, stage, detail)`` — stage
    ``"hook"`` / ``"op"`` with the exception raised, or ``"unknown"`` with
    the ``[(slot, var)]`` inputs no rule or caller gave a shape.
    ``Symbol.infer_shape`` raises from these where ``analysis.verifier``
    adds a report entry.
    """
    import jax
    import jax.numpy as jnp

    types = types or {}
    var_shapes, structs = {}, {}

    def var_struct(node):
        dtype = types.get(node.name,
                          node.raw_attr.get("__dtype__", "float32"))
        return (jax.ShapeDtypeStruct(var_shapes[id(node)],
                                     jnp.dtype(dtype)),)

    for node in topo:
        if not node.is_variable:
            continue
        if node.name in known:
            var_shapes[id(node)] = tuple(known[node.name])
        elif "__shape__" in node.raw_attr:
            var_shapes[id(node)] = tuple(
                json.loads(node.raw_attr["__shape__"]))
        structs[id(node)] = var_struct(node) \
            if id(node) in var_shapes else None
    # deferred batch dims (``_batch_attrs``) take the first sized argument's
    batch_size = next((int(var_shapes[id(n)][0])
                       for n in _classify_vars(topo)[0]
                       if len(var_shapes.get(id(n), ())) > 0), None)
    octx = OpContext(is_train=False, key=None)

    while True:
        learnt, faults = len(var_shapes), []
        for node in topo:
            if node.is_variable or structs.get(id(node)) is not None:
                continue
            slots = list(zip(node.arg_names() + node.aux_names(),
                             node.inputs))
            unknown = [(nm, src) for nm, (src, _i) in slots
                       if src.is_variable and id(src) not in var_shapes]
            hook = _shapes.get_param_shapes(node.op.name) if unknown \
                else None
            if hook is not None:
                known_in = {}
                for nm, (src, idx) in slots:
                    st = structs.get(id(src))
                    if st is not None and len(st) > idx:
                        known_in[nm] = tuple(st[idx].shape)
                try:
                    inferred = hook(node.attrs, known_in)
                except Exception as e:  # mxlint: allow-broad-except(a hook runs user code e.g. CustomOpProp.infer_shape; infer_shape raises it again, the verifier reports it)
                    faults.append((node, "hook", e))
                    inferred = {}
                for nm, src in unknown:
                    if nm in inferred and id(src) not in var_shapes:
                        var_shapes[id(src)] = tuple(inferred[nm])
                        structs[id(src)] = var_struct(src)
                unknown = [(nm, src) for nm, src in unknown
                           if id(src) not in var_shapes]
            if unknown:
                faults.append((node, "unknown", unknown))
            ins = _input_structs(node, structs)
            if ins is None:
                continue
            try:
                attrs = _batch_attrs(node, batch_size)
                outs = jax.eval_shape(
                    lambda *xs: apply_op(node.op, attrs, octx, *xs), *ins)
            except Exception as e:  # mxlint: allow-broad-except(fcompute tracing raises arbitrary exception types; infer_shape raises it again, the verifier reports it)
                faults.append((node, "op", e))
                continue
            structs[id(node)] = tuple(outs) \
                if isinstance(outs, (tuple, list)) else (outs,)
        # a hook may size a variable that an earlier op of the walk also
        # consumes (a tied weight): only then is there a second pass
        if len(var_shapes) == learnt or \
                all(stage != "unknown" for _n, stage, _d in faults):
            return var_shapes, structs, faults


def eval_graph(topo, entries, var_values, is_train=False, key=None,
               monitor=None, batch_size=None, device_map=None,
               seed_vals=None):
    """Execute the DAG as a pure function.

    ``var_values``: dict id(var-node) -> array.  Returns (head values,
    aux-updates dict id(var-node) -> new array).  Stochastic nodes fold
    their topo index into ``key`` so replay is deterministic.

    ``batch_size`` specializes 0-dims in init-op shapes (the RNN toolkit's
    deferred begin_state zeros; the reference resolves these via nnvm
    backward shape inference).

    ``device_map`` (id(node) -> jax.Device) places each op on a device —
    the model-parallel ctx_group path (reference AssignContext +
    PlaceDevice inserting _CrossDeviceCopy, graph_executor.cc:249-341;
    here the copy is a jax.device_put and XLA async dispatch overlaps the
    per-device segments).
    """
    import jax
    # seed_vals: id(node) -> output tuple for nodes evaluated OUTSIDE this
    # call (the pipeline-parallel path seeds each stage's boundary input)
    vals = {} if seed_vals is None else dict(seed_vals)
    aux_updates = {}
    device_map = device_map or {}

    # conv-only rewrites of the stem (ops/fused.py)
    stem_plan = set()
    elide_plan = set()
    if is_train and not device_map:
        from .ops import fused as _fused
        from .ops.nn import current_image_layout
        if current_image_layout() == "NHWC":
            if _fused.stem_s2d_enabled():
                stem_plan = _fused.plan_stem_s2d(topo)
            if _fused.elide_names():
                # convs whose backward-data exists only to feed an input
                # BN's beta grad (ops/fused.py input-BN dX elision)
                elide_plan = _fused.plan_input_bn_elide(
                    topo, entries, _fused.elide_names())

    # block-granularity fusion pass (analysis.fusion): conv+BN+ReLU /
    # FC+act chains lowered to single custom-vjp regions with a pinned
    # layout per boundary.  Runs in train AND eval traces (eval keeps
    # the global-stats BN semantics inside the region); the per-node
    # monitor path stays unfused so callbacks see every output, and
    # seeded partial graphs (pipeline stages) never fuse — a chain can
    # straddle the stage boundary, whose members are outside this topo.
    block_plan = None
    if monitor is None and not device_map and seed_vals is None:
        from .ops import fused as _fused
        if _fused.block_fusion_enabled():
            from .ops.nn import current_image_layout
            from .analysis import fusion as _fusion
            block_plan = _fusion.plan_block_fusion(
                topo, entries, layout=current_image_layout(),
                is_train=is_train,
                exclude=stem_plan | elide_plan)
            if not block_plan.blocks:
                block_plan = None

    for i, node in enumerate(topo):
        if node.is_variable:
            try:
                vals[id(node)] = (var_values[id(node)],)
            except KeyError:
                raise MXNetError("no value bound for variable %r" % node.name)
            continue
        if id(node) in stem_plan:
            from .ops import fused as _fused
            s_ins = [vals[id(src)][idx] for (src, idx) in node.inputs]
            sx = s_ins[0]
            if sx.ndim == 4 and sx.shape[1] % 2 == 0 \
                    and sx.shape[2] % 2 == 0:
                vals[id(node)] = (_fused.stem_s2d_conv(
                    sx, s_ins[1], elide=id(node) in elide_plan),)
                continue
            # odd spatial size: fall through to the direct conv
        if id(node) in elide_plan:
            from .ops import fused as _fused
            e_ins = [vals[id(src)][idx] for (src, idx) in node.inputs]
            vals[id(node)] = (_fused.elided_conv_apply(
                node.attrs, e_ins[0], e_ins[1]),)
            continue
        if block_plan is not None:
            if id(node) in block_plan.skip:
                # interior of a fused block: evaluated at its terminal
                vals[id(node)] = (None,) * node.num_outputs()
                continue
            blk = block_plan.blocks.get(id(node))
            if blk is not None:
                from .analysis import fusion as _fusion
                out, bn_node, bn_aux = _fusion.apply_block(blk, vals,
                                                           is_train)
                vals[id(node)] = (out,)
                if bn_node is not None:
                    for (src, _), upd in zip(
                            bn_node.inputs[bn_node.num_args:], bn_aux):
                        if src.is_variable:
                            aux_updates[id(src)] = upd
                continue
        ins = [vals[id(src)][idx] for (src, idx) in node.inputs]
        dev = device_map.get(id(node))
        if dev is not None:
            ins = [jax.device_put(x, dev) for x in ins]
        node_attrs = _batch_attrs(node, batch_size)
        stoch = node.op.stochastic
        if callable(stoch):
            stoch = stoch(node_attrs)
        k = None
        if stoch and key is not None:
            k = jax.random.fold_in(key, i)
        octx = OpContext(is_train=is_train, key=k)
        # a block's builder names its ops' scope on the device and notes
        # what the block is (``models.decoder_blocks.block_scope`` and
        # ``plan_note``)
        scope = node.raw_attr.get("__scope__")
        with jax.named_scope(scope) if scope else contextlib.nullcontext():
            outs = apply_op(node.op, node_attrs, octx, *ins)
        if "__plan_note__" in node.raw_attr:
            scope, info = json.loads(node.raw_attr["__plan_note__"])
            _plan.note(scope, **info)
        n_vis = node.num_outputs()
        n_aux = len(node.inputs) - node.num_args
        vals[id(node)] = outs[:n_vis]
        for (src, _), upd in zip(node.inputs[node.num_args:],
                                 outs[n_vis:n_vis + n_aux]):
            if src.is_variable:
                aux_updates[id(src)] = upd
        if monitor is not None:
            for oname, val in zip(node.output_names(), outs[:n_vis]):
                monitor(oname, val)
    heads = [vals[id(n)][i] for (n, i) in entries]
    return heads, aux_updates


class Symbol:
    """An immutable multi-output handle into the graph."""
    __slots__ = ("_entries",)

    def __init__(self, entries):
        self._entries = list(entries)  # list[(Node, out_index)]

    # ------------------------------------------------------------- identity
    @property
    def name(self):
        if len(self._entries) == 1:
            return self._entries[0][0].name
        return None

    def __repr__(self):
        if len(self._entries) == 1:
            return "<Symbol %s>" % self._entries[0][0].name
        return "<Symbol group [%s]>" % ", ".join(
            n.name for (n, _) in self._entries)

    def __iter__(self):
        return (Symbol([e]) for e in self._entries)

    def __len__(self):
        return len(self._entries)

    def __getitem__(self, index):
        if isinstance(index, str):
            names = self.list_outputs()
            if index not in names:
                # allow bare node name too
                for i, (n, _) in enumerate(self._entries):
                    if n.name == index:
                        return Symbol([self._entries[i]])
                raise ValueError("cannot find output %r" % index)
            index = names.index(index)
        if isinstance(index, _py_slice):
            return Symbol(self._entries[index])
        return Symbol([self._entries[index]])

    # ---------------------------------------------------------- arithmetic
    def _binary(self, other, op_ss, op_s, swap=False):
        if isinstance(other, Symbol):
            return _create(op_ss, None, None, [self, other], {})
        if isinstance(other, (int, float)):
            return _create(op_s, None, None, [self], {"scalar": float(other)})
        raise TypeError("unsupported operand type %r" % type(other))

    def __add__(self, other):
        return self._binary(other, "elemwise_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, "elemwise_sub", "_minus_scalar")

    def __rsub__(self, other):
        return self._binary(other, "elemwise_sub", "_rminus_scalar")

    def __mul__(self, other):
        return self._binary(other, "elemwise_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, "elemwise_div", "_div_scalar")

    def __rtruediv__(self, other):
        return self._binary(other, "elemwise_div", "_rdiv_scalar")

    __div__ = __truediv__
    __rdiv__ = __rtruediv__

    def __pow__(self, other):
        return self._binary(other, "_power", "_power_scalar")

    def __neg__(self):
        return _create("negative", None, None, [self], {})

    def __copy__(self):
        return Symbol(list(self._entries))

    def __deepcopy__(self, memo):
        return load_json(self.tojson())

    def __eq__(self, other):
        if isinstance(other, Symbol):
            return self._entries == other._entries
        return NotImplemented

    def __hash__(self):
        return hash(tuple((id(n), i) for (n, i) in self._entries))

    # -------------------------------------------------------------- listing
    def _topo(self):
        return _topo_order(self._entries)

    def list_arguments(self):
        args, _ = _classify_vars(self._topo())
        return [n.name for n in args]

    def list_auxiliary_states(self):
        _, aux = _classify_vars(self._topo())
        return [n.name for n in aux]

    def list_outputs(self):
        out = []
        for (node, idx) in self._entries:
            out.append(node.output_names()[idx])
        return out

    def get_internals(self):
        """All internal outputs as a group (reference symbol.py
        get_internals; used for feature extraction and shared binding)."""
        entries = []
        for node in self._topo():
            for i in range(node.num_outputs()):
                entries.append((node, i))
        return Symbol(entries)

    def get_children(self):
        if len(self._entries) != 1:
            raise MXNetError("get_children requires a single-output symbol")
        node = self._entries[0][0]
        if not node.inputs:
            return None
        return Symbol(list(node.inputs))

    # ---------------------------------------------------------------- attrs
    def attr(self, key):
        if len(self._entries) == 1:
            node = self._entries[0][0]
            if key == "name":
                return node.name
            v = node.raw_attr.get(key)
            if v is None and node.op is not None and key in node.attrs:
                return _attr_str(node.attrs[key])
            return v
        return None

    def list_attr(self):
        if len(self._entries) != 1:
            return {}
        return dict(self._entries[0][0].raw_attr)

    def attr_dict(self):
        out = {}
        for node in self._topo():
            d = dict(node.raw_attr)
            if node.op is not None:
                d.update({k: _attr_str(v) for k, v in node.attrs.items()})
            if d:
                out[node.name] = d
        return out

    def _set_attr(self, **kwargs):
        if len(self._entries) != 1:
            raise MXNetError("_set_attr requires a single-output symbol")
        node = self._entries[0][0]
        for k, v in kwargs.items():
            if not isinstance(v, str):
                raise ValueError("attribute values must be strings")
            node.raw_attr[k] = v

    # ------------------------------------------------------------ inference
    def infer_shape(self, *args, **kwargs):
        res = self._infer_shape_impl(False, *args, **kwargs)
        return res

    def infer_shape_partial(self, *args, **kwargs):
        return self._infer_shape_impl(True, *args, **kwargs)

    def _infer_shape_impl(self, partial, *args, **kwargs):
        known = {}
        if args:
            arg_list = self.list_arguments()
            for a_name, a_shape in zip(arg_list, args):
                if a_shape is not None:
                    known[a_name] = tuple(a_shape)
        for k, v in kwargs.items():
            if v is not None:
                known[k] = tuple(v)

        topo = self._topo()
        arg_nodes, aux_nodes = _classify_vars(topo)
        shapes, structs, faults = _shape_walk(topo, known)
        for _node, stage, err in faults:
            if stage == "hook":
                raise err
        missing = [n.name for n in arg_nodes + aux_nodes
                   if id(n) not in shapes]
        if missing and not partial:
            raise MXNetError(
                "infer_shape: cannot infer shapes for %s; provide them "
                "explicitly" % missing)
        arg_shapes = [shapes.get(id(n)) for n in arg_nodes]
        aux_shapes = [shapes.get(id(n)) for n in aux_nodes]
        if missing:
            return arg_shapes, None, aux_shapes
        for _node, stage, err in faults:
            if stage == "op":
                raise err
        out_shapes = [tuple(structs[id(n)][i].shape)
                      for (n, i) in self._entries]
        return arg_shapes, out_shapes, aux_shapes

    def infer_type(self, *args, **kwargs):
        import jax
        import jax.numpy as jnp
        import numpy as np

        known = {}
        if args:
            for a_name, a_type in zip(self.list_arguments(), args):
                if a_type is not None:
                    known[a_name] = np.dtype(a_type).name
        for k, v in kwargs.items():
            if v is not None:
                known[k] = np.dtype(v).name
        topo = self._topo()
        arg_nodes, aux_nodes = _classify_vars(topo)
        # reference InferType propagates the known dtype to the other
        # float arguments of each op (same-dtype rule): typing only the
        # data input types the whole net (test_utils.check_consistency
        # depends on this).  Conservative version: when every explicitly
        # known dtype agrees on one float type, unknown un-attributed
        # args default to it instead of float32.
        default = "float32"
        kt = {np.dtype(v).name for v in known.values()}
        if len(kt) == 1 and np.dtype(next(iter(kt))).kind == "f":
            default = next(iter(kt))
        arg_types = [np.dtype(known.get(
            n.name, n.raw_attr.get("__dtype__", default)))
            for n in arg_nodes]
        aux_types = [np.dtype(known.get(
            n.name, n.raw_attr.get("__dtype__", "float32")))
            for n in aux_nodes]
        # outputs via eval_shape with unit shapes is unreliable (shape-
        # dependent ops); reuse infer_shape machinery when shapes known is
        # overkill — outputs inherit the head dtype of a tiny trace.
        try:
            shape_kwargs = {}
            arg_shapes, out_shapes, _ = self.infer_shape_partial()
            if out_shapes is None:
                raise MXNetError("partial")
            # full shapes known: trace dtypes exactly
            var_vals = {}
            for n, t in zip(arg_nodes, arg_types):
                var_vals[id(n)] = jax.ShapeDtypeStruct(
                    tuple(arg_shapes[arg_nodes.index(n)]), jnp.dtype(t))
            for n, t in zip(aux_nodes, aux_types):
                var_vals[id(n)] = jax.ShapeDtypeStruct((1,), jnp.dtype(t))
            entries = self._entries

            def fn(vv):
                heads, _ = eval_graph(topo, entries, vv)
                return heads
            outs = jax.eval_shape(fn, var_vals)
            out_types = [np.dtype(o.dtype) for o in outs]
        except Exception:  # mxlint: allow-broad-except(dtype trace is best-effort over arbitrary fcomputes; fall back to float32)
            out_types = [np.dtype("float32")] * len(self._entries)
        return arg_types, out_types, aux_types

    # -------------------------------------------------------- verification
    def verify(self, shapes=None, types=None, tp_size=1,
               check_registry=False, mesh=None, parallel=None,
               memory=None, **shape_kwargs):
        """Statically verify the graph BEFORE any compile/device time.

        Runs the :mod:`mxnet_tpu.analysis` graph verifier: per-node
        shape/dtype consistency against the op registry, missing
        param-shape rules, dead inputs, duplicate names, cycles, and
        (``tp_size`` > 1) tensor-parallel sharding coverage.  Input
        shapes go in like ``infer_shape``'s kwargs::

            report = net.verify(data=(32, 3, 224, 224))
            if not report.ok:
                print(report)          # node-level diagnostics
            report.raise_if_errors()   # or fail hard

        ``mesh`` ({axis: size}) additionally runs the distributed-
        correctness pass (MXG011-016) for the composed parallel step
        described by ``parallel`` (an ``analysis.build_config`` dict)::

            net.verify(data=(32, 8, 64), mesh={"data": 2, "pipe": 2},
                       parallel=analysis.build_config(
                           pipeline_stages=2, data_shapes=...))

        ``memory`` (True or an ``analysis.memlive.check_memory``
        options dict) additionally runs the static memory-liveness
        pass (MXG017-021): predicted peak HBM vs the armed budget,
        remat/ZeRO/donation advice — all before any compile::

            net.verify(data=(32, 3, 224, 224),
                       memory={"is_train": True, "n_slots": 2,
                               "mesh": {"data": 8}})

        Returns an :class:`mxnet_tpu.analysis.Report`.
        """
        from .analysis import verify_symbol
        known = dict(shapes or {})
        known.update(shape_kwargs)
        return verify_symbol(self, shapes=known, types=types,
                             tp_size=tp_size,
                             check_registry=check_registry,
                             mesh=mesh, parallel=parallel,
                             memory=memory)

    # ------------------------------------------------------------- binding
    def bind(self, ctx=None, args=None, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None,
             strict=False):
        from .executor import Executor
        return Executor(self, ctx or current_context(), args, args_grad,
                        grad_req, aux_states, group2ctx=group2ctx,
                        shared_exec=shared_exec, strict=strict)

    def simple_bind(self, ctx=None, grad_req="write", type_dict=None,
                    group2ctx=None, shared_exec=None, strict=False,
                    **kwargs):
        """Infer shapes from kwargs, allocate arrays, bind.

        Reference: python/mxnet/symbol.py:1163 (python-side allocation then
        bind)."""
        from . import ndarray as nd
        ctx = ctx or current_context()
        arg_shapes, _, aux_shapes = self.infer_shape(**kwargs)
        arg_types, _, aux_types = self.infer_type(
            **{k: v for k, v in (type_dict or {}).items()})
        arg_names = self.list_arguments()
        args = [nd.zeros(s, ctx=ctx, dtype=t)
                for s, t in zip(arg_shapes, arg_types)]
        aux_states = [nd.zeros(s, ctx=ctx, dtype=t)
                      for s, t in zip(aux_shapes, aux_types)]
        if isinstance(grad_req, str):
            reqs = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            reqs = dict(zip(arg_names, grad_req))
        else:
            reqs = {n: grad_req.get(n, "null") for n in arg_names}
        args_grad = {}
        for n, s, t in zip(arg_names, arg_shapes, arg_types):
            if reqs.get(n, "null") != "null":
                args_grad[n] = nd.zeros(s, ctx=ctx, dtype=t)
        return self.bind(ctx, args, args_grad, reqs, aux_states,
                         group2ctx=group2ctx, shared_exec=shared_exec,
                         strict=strict)

    # -------------------------------------------------------------- ser/de
    def tojson(self):
        """Serialize in the reference's JSON graph layout
        (nnvm::Symbol::Save; heads/arg_nodes/nodes)."""
        topo = self._topo()
        node_ids = {id(n): i for i, n in enumerate(topo)}
        nodes = []
        arg_nodes = []
        for i, node in enumerate(topo):
            if node.is_variable:
                arg_nodes.append(i)
                entry = {"op": "null", "name": node.name, "inputs": []}
                if node.raw_attr:
                    entry["attrs"] = dict(node.raw_attr)
            else:
                attrs = {k: _attr_str(v) for k, v in node.attrs.items()}
                attrs.update(node.raw_attr)
                entry = {"op": node.op.name, "name": node.name,
                         "inputs": [[node_ids[id(s)], idx, 0]
                                    for (s, idx) in node.inputs]}
                if attrs:
                    entry["attrs"] = attrs
            nodes.append(entry)
        heads = [[node_ids[id(n)], idx, 0] for (n, idx) in self._entries]
        row_ptr = [0]
        for n in topo:
            row_ptr.append(row_ptr[-1] + n.num_outputs())
        return json.dumps({
            "nodes": nodes,
            "arg_nodes": arg_nodes,
            "node_row_ptr": row_ptr,
            "heads": heads,
            "attrs": {"mxnet_version": ["int", 1001]},
        }, indent=2)

    def save(self, fname):
        with open(fname, "w") as f:
            f.write(self.tojson())

    # ------------------------------------------------------------- helpers
    def _single_entry(self):
        if len(self._entries) != 1:
            raise MXNetError("operation requires a single-output symbol; "
                             "got %d outputs" % len(self._entries))
        return self._entries[0]

    # evaluation helper for tests / debugging
    def eval(self, ctx=None, **kwargs):
        ex = self.bind(ctx or current_context(),
                       {k: v for k, v in kwargs.items()})
        return ex.forward()


def _attr_str(v):
    if isinstance(v, bool):
        return "True" if v else "False"
    if v is None:
        return "None"
    if isinstance(v, (list, tuple)):
        return "(" + ", ".join(str(x) for x in v) + ")"
    return str(v)


# ---------------------------------------------------------------- creation
def Variable(name, attr=None, shape=None, lr_mult=None, wd_mult=None,
             dtype=None, init=None, **kwargs):
    """Create a variable symbol (reference symbol.py Variable)."""
    if not isinstance(name, str):
        raise TypeError("expect a string for variable name")
    raw = attribute.current().get(attr)
    if shape is not None:
        raw["__shape__"] = json.dumps(list(shape))
    if lr_mult is not None:
        raw["__lr_mult__"] = str(lr_mult)
    if wd_mult is not None:
        raw["__wd_mult__"] = str(wd_mult)
    if dtype is not None:
        import numpy as np
        raw["__dtype__"] = np.dtype(dtype).name
    if init is not None:
        if not isinstance(init, str):
            init = init.dumps()
        raw["__init__"] = init
    for k, v in kwargs.items():
        if k.startswith("__") and k.endswith("__"):
            raw[k] = str(v)
        else:
            raise ValueError("unknown variable option %r" % k)
    node = _Node(None, name, raw_attr=raw)
    return Symbol([(node, 0)])


var = Variable


def Group(symbols):
    """Group symbols into one multi-output symbol."""
    entries = []
    for s in symbols:
        if not isinstance(s, Symbol):
            raise TypeError("expect Symbols in Group")
        entries.extend(s._entries)
    return Symbol(entries)


def _create(op_name, name, attr, sym_args, attr_kwargs, sym_kwargs=None):
    """Compose a new op node (the generated mx.sym.<op> body)."""
    op = get_op(op_name)
    sym_kwargs = sym_kwargs or {}

    if op.key_var_num_args and op.key_var_num_args not in attr_kwargs:
        attr_kwargs = dict(attr_kwargs)
        attr_kwargs[op.key_var_num_args] = (
            len(sym_args) + len(sym_kwargs)) or 1
    attrs = op.parse_attrs(attr_kwargs)
    arg_names = op.get_arg_names(attrs)
    aux_names = op.get_aux_names(attrs)
    all_names = arg_names + aux_names

    hint = op.name.lower().lstrip("_")
    name = _name_mod.current().get(name, hint)
    raw = attribute.current().get(attr)

    slots = {}
    for i, s in enumerate(sym_args):
        if i >= len(all_names):
            raise MXNetError("%s: too many positional inputs" % op_name)
        slots[all_names[i]] = s
    for k, v in sym_kwargs.items():
        if k in slots:
            raise MXNetError("%s: duplicate input %r" % (op_name, k))
        slots[k] = v

    inputs = []
    for nm in all_names:
        s = slots.get(nm)
        if s is None:
            # auto-create the parameter/aux variable (reference: nnvm
            # Symbol composition fills missing inputs with variables)
            s = Variable("%s_%s" % (name, nm))
        if not isinstance(s, Symbol):
            raise TypeError("%s: input %r must be a Symbol" % (op_name, nm))
        inputs.append(s._single_entry())

    node = _Node(op, name, attrs=attrs, raw_attr=raw, inputs=inputs,
                 num_args=len(arg_names))
    return Symbol([(node, i) for i in range(node.num_outputs())])


def _make_sym_function(op):
    def fn(*args, name=None, attr=None, out=None, **kwargs):
        sym_args = []
        for a in args:
            if not isinstance(a, Symbol):
                raise TypeError("positional inputs must be Symbols")
            sym_args.append(a)
        sym_kwargs, attr_kwargs = {}, {}
        for k, v in kwargs.items():
            if isinstance(v, Symbol):
                sym_kwargs[k] = v
            else:
                attr_kwargs[k] = v
        return _create(op.name, name, attr, sym_args, attr_kwargs, sym_kwargs)
    fn.__name__ = op.name
    fn.__doc__ = op.doc
    return fn


def _register_sym_functions():
    g = globals()
    for opname in _registry.list_ops():
        op = get_op(opname)
        g[opname] = _make_sym_function(op)
    for alias, target in _registry._ALIASES.items():
        g[alias] = g[target]


_register_sym_functions()


# convenience creators mirroring mx.sym.zeros/ones/arange.  A 0 in shape is
# a deferred batch dim resolved at bind time (the RNN begin_state pattern);
# meta kwargs (e.g. __layout__) become node attrs.
def zeros(shape, dtype="float32", name=None, **kwargs):
    attr = {k: str(v) for k, v in kwargs.items()
            if k.startswith("__") and k.endswith("__")}
    return _create("_zeros", name, attr or None, [],
                   {"shape": tuple(shape), "dtype": dtype})


def ones(shape, dtype="float32", name=None, **kwargs):
    attr = {k: str(v) for k, v in kwargs.items()
            if k.startswith("__") and k.endswith("__")}
    return _create("_ones", name, attr or None, [],
                   {"shape": tuple(shape), "dtype": dtype})


def arange(start, stop=None, step=1.0, repeat=1, dtype="float32", name=None):
    return _create("_arange", name, None, [],
                   {"start": start, "stop": stop, "step": step,
                    "repeat": repeat, "dtype": dtype})


# ---------------------------------------------------------------- loading
def load_json(json_str):
    """Deserialize from the reference JSON layout."""
    data = json.loads(json_str)
    raw_nodes = data["nodes"]
    built = []
    for entry in raw_nodes:
        raw_attr = dict(entry.get("attrs", entry.get("attr", {}) or {}))
        if entry["op"] == "null":
            node = _Node(None, entry["name"], raw_attr=raw_attr)
        else:
            op = get_op(entry["op"])
            params = {k: v for k, v in raw_attr.items()
                      if not (k.startswith(_META_PREFIX))}
            meta = {k: v for k, v in raw_attr.items()
                    if k.startswith(_META_PREFIX)}
            attrs = op.parse_attrs(params)
            inputs = [(built[src], idx)
                      for (src, idx, *_rest) in entry["inputs"]]
            node = _Node(op, entry["name"], attrs=attrs, raw_attr=meta,
                         inputs=inputs,
                         num_args=len(op.get_arg_names(attrs)))
        built.append(node)
    entries = [(built[i], idx) for (i, idx, *_r) in data["heads"]]
    return Symbol(entries)


def load(fname):
    with open(fname) as f:
        return load_json(f.read())
