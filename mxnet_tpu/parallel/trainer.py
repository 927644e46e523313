"""ShardedTrainer: a Symbol fused into one pjit train step.

This is the TPU-native performant path.  The reference runs forward,
backward, and optimizer as separate engine pushes with kvstore reduce in
between (SURVEY §3.1); here the whole training step — forward, vjp,
gradient collectives, optimizer update, aux-state update — is ONE
jit-compiled XLA program over a device mesh:

* batch sharded over the ``data`` axis → XLA inserts the gradient psum over
  ICI (the role of kvstore 'device', `src/kvstore/comm.h:220-385`);
* nominated weights sharded over the ``model`` axis → GSPMD tensor
  parallelism (absent in the reference, SURVEY §2.4);
* parameters are donated, so updates are in-place in HBM.

Mixed precision follows TPU practice rather than the reference's fp16
path: master weights live permanently in float32, activations/grads run
in ``dtype`` (bfloat16 on the MXU), and the optimizer updates the f32
masters.  ``layout="NHWC"`` feeds channel-minor activations end-to-end —
the layout XLA:TPU wants for convs — while weights keep the reference
OIHW layout (see ops/nn.py `image_layout`).

(Design note: a flat-packed fused optimizer — all masters concatenated
into one vector per hyperparameter group — was tried and measured SLOWER
on ResNet-50/v5e than per-parameter updates: the gradient concat and
unpack relayouts cost more than the small-op overhead they remove.  XLA
already fuses per-parameter updates adequately.)

The optimizer is pluggable: any name registered in
``mxnet_tpu.optimizer`` whose update rule has a fused formulation below
(sgd/nag/ccsgd/adam/adagrad/rmsprop/adadelta), with the reference's
lr_mult/wd_mult semantics (`python/mxnet/optimizer.py` _get_lr/_get_wd;
wd_mult defaults to 0 for params not ending in _weight/_gamma).

Module/Executor remain the API-parity path; bench.py and the pod-scale
training scripts use this.
"""
from __future__ import annotations

import struct as _struct

import numpy as np

from ..base import MXNetError
from ..symbol import eval_graph, _classify_vars
from ..initializer import Xavier, InitDesc, rule_for, draw
from ..ops.nn import image_layout
from .. import optimizer as _opt_mod
from ..telemetry import plan as _plan
from ..telemetry.spans import span as _span

__all__ = ["ShardedTrainer"]

#: ``jax.named_scope`` names inside the step program: forward and loss,
#: the vjp, the optimizer update
SCOPE_FWD, SCOPE_BWD, SCOPE_OPT = "mxtpu.fwd", "mxtpu.bwd", "mxtpu.opt"

#: folded into ``PRNGKey(seed)`` for the initial draw, so that it shares
#: no stream with the step's key, which is ``PRNGKey(seed)`` split once
#: a dispatch; each parameter then folds in its index in ``_param_names``
_INIT_STREAM = 0x696e6974   # "init"


def _make_update_rule(opt):
    """(n_state_slots, rule) for a fused, functional optimizer update.

    ``rule(w, g, slots, lr, wd, t) -> (new_w, new_slots)`` over f32 master
    weights; mirrors the semantics of the corresponding
    ``mxnet_tpu.optimizer`` classes (themselves mirroring the reference's
    fused update kernels, src/operator/optimizer_op.cc:18-161).
    ``t`` is the 1-based update count (traced scalar, adam bias correction).
    """
    import jax.numpy as jnp

    clip = opt.clip_gradient

    def prep(g, w, wd):
        if clip is not None and clip > 0:
            g = jnp.clip(g, -clip, clip)
        return g + wd * w

    name = type(opt).__name__.lower()

    if name in ("sgd", "ccsgd"):
        momentum = opt.momentum
        if momentum == 0.0:
            return 0, lambda w, g, s, lr, wd, t: (w - lr * prep(g, w, wd), s)

        def sgd_rule(w, g, s, lr, wd, t):
            m = momentum * s[0] - lr * prep(g, w, wd)
            return w + m, [m]
        return 1, sgd_rule

    if name == "nag":
        momentum = opt.momentum

        def nag_rule(w, g, s, lr, wd, t):
            g = prep(g, w, wd)
            m = momentum * s[0] + g
            return w - lr * (g + momentum * m), [m]
        return 1, nag_rule

    if name == "adam":
        b1, b2, eps = opt.beta1, opt.beta2, opt.epsilon

        def adam_rule(w, g, s, lr, wd, t):
            g = prep(g, w, wd)
            m = b1 * s[0] + (1 - b1) * g
            v = b2 * s[1] + (1 - b2) * jnp.square(g)
            lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
            return w - lr_t * m / (jnp.sqrt(v) + eps), [m, v]
        return 2, adam_rule

    if name == "adagrad":
        eps = opt.float_stable_eps

        def adagrad_rule(w, g, s, lr, wd, t):
            if clip is not None and clip > 0:
                g = jnp.clip(g, -clip, clip)
            h = s[0] + jnp.square(g)
            return w - lr * (g / jnp.sqrt(h + eps) + wd * w), [h]
        return 1, adagrad_rule

    if name == "rmsprop" and not getattr(opt, "centered", False):
        g1, eps = opt.gamma1, opt.epsilon

        def rmsprop_rule(w, g, s, lr, wd, t):
            g = prep(g, w, wd)
            n = (1 - g1) * jnp.square(g) + g1 * s[0]
            return w - lr * g / jnp.sqrt(n + eps), [n]
        return 1, rmsprop_rule

    if name == "adadelta":
        rho, eps = opt.rho, opt.epsilon

        def adadelta_rule(w, g, s, lr, wd, t):
            if clip is not None and clip > 0:
                g = jnp.clip(g, -clip, clip)
            acc_g = rho * s[0] + (1 - rho) * jnp.square(g)
            delta = jnp.sqrt(s[1] + eps) / jnp.sqrt(acc_g + eps) * g
            acc_d = rho * s[1] + (1 - rho) * jnp.square(delta)
            return w - delta - wd * w, [acc_g, acc_d]
        return 2, adadelta_rule

    raise MXNetError(
        "optimizer %r has no fused ShardedTrainer formulation; supported: "
        "sgd, ccsgd, nag, adam, adagrad, rmsprop (non-centered), adadelta"
        % name)


class ShardedTrainer:
    @_span("trainer.build", category="trainer")
    def __init__(self, symbol, mesh, data_shapes, label_shapes=(),
                 optimizer="sgd", optimizer_params=None, learning_rate=0.05,
                 momentum=0.9, weight_decay=0.0, initializer=None,
                 dtype="float32", tp_rules=None, seed=0, layout=None,
                 auto_layouts=False, fuse_blocks=None,
                 stem_space_to_depth=None, elide_input_bn_grad=True,
                 pipeline_stages=1, pipeline_microbatches=None,
                 sequence_parallel=False, input_mean=None, input_std=None,
                 strict=None):
        """
        symbol: loss-headed Symbol (e.g. SoftmaxOutput net).
        mesh: jax.sharding.Mesh with ('data', 'model') axes.
        data_shapes/label_shapes: dict name -> GLOBAL shape (batch dim 0),
            in the reference NCHW convention regardless of ``layout``.
        optimizer: registry name (or an Optimizer instance) — see
            `_make_update_rule` for the fused set.  ``learning_rate`` /
            ``momentum`` / ``weight_decay`` are convenience defaults merged
            into ``optimizer_params``.
        initializer: what the float32 masters start from; default
            ``Xavier(rnd_type="gaussian", factor_type="in", magnitude=2)``.
            Which rule a name gets is the initializer's own dispatch
            (suffix rules, a ``Variable``'s ``init=``, ``Mixed``'s
            patterns).  Where that rule can be traced (the built-in
            ``Zero``/``One``/``Constant``/``Uniform``/``Normal``/
            ``Xavier``/``MSRAPrelu`` and the bias/gamma/beta/aux
            constants) the parameter is drawn ON THE DEVICE, in float32
            from ``jax.random``, by one jitted program under the
            parameters' shardings, keyed by ``seed``: the host rule's
            distribution (fans from the reference OIHW shape), not its
            numbers, and ``np.random.seed`` does not move it.  Any other
            rule (a subclass whose ``_init_weight`` is numpy code,
            ``Load``, ``Orthogonal``, ``Bilinear``, ``LSTMBias``,
            ``FusedRNN``) fills a host array from numpy's global
            generator as it always did, parameter by parameter.
        seed: two trainers of one graph and one ``seed`` start equal, on
            one device or sharded over a mesh.  The initial draw uses
            ``fold_in(PRNGKey(seed), "init")`` and then one ``fold_in``
            a parameter by its index; the step's own key (dropout) is
            ``PRNGKey(seed)`` split once a dispatch: no shared stream.
        dtype: compute dtype for activations/grads (master weights stay f32).
        tp_rules: {param_name: axis_index} — weight dims to shard over the
            'model' axis.  Default: classifier-style FullyConnected weights
            whose output dim divides the tp size.
        layout: None (reference NCHW) or "NHWC" (TPU-preferred channel-minor
            activations; host batches are transposed on ingest).  Weights
            keep reference layouts, so NHWC parameters are interchangeable
            with NCHW checkpoints whenever Flatten only ever sees 1x1
            spatial maps (global-pool-then-FC nets like ResNet/Inception);
            an MLP-style Flatten of a WxH map permutes the FC input order.
        auto_layouts: False, the one value left (the option went in
            PR 43); state lives in jit's default layouts, masters OIHW.
        strict: run the distributed-correctness pass
            (``analysis.spmd``, MXG011-016) over this (graph, mesh,
            parallel config) triple before any compile and raise a
            descriptive MXNetError on findings.  None -> the
            ``MXNET_TPU_STRICT_BIND`` env default.

        The constructor is a ``trainer.build`` span (telemetry.spans)
        whose children are its phases in order: ``.graph``,
        ``.init_params``, ``.place``, ``.plan``.  ``.init_params``'s
        record carries what was drawn where: ``device_params`` /
        ``device_bytes``, ``host_params`` / ``host_bytes`` and
        ``host_names`` (at most eight).
        """
        from . import multihost

        self.symbol = symbol
        self.mesh = mesh
        self.dtype = dtype
        self._stage_fns = {}      # lazy per-input device staging programs
        # process-spanning mesh (launch.py multi-host job): the SAME
        # jitted step runs on every process; host<->device staging goes
        # through parallel/multihost.py instead of device_put
        self._multiproc = multihost.spans_processes(mesh)
        # auto_layouts: kept only because every benchmark/configs/*.json
        # passes ``"auto_layouts": false`` straight into this constructor
        # and a PR may not edit those files; the option's code is gone
        if auto_layouts:
            raise MXNetError(
                "ShardedTrainer(auto_layouts=True): XLA-chosen state "
                "layouts were removed in PR 43 (+0.24% on ResNet-50 for "
                "about 100 s of uncached compile); the keyword accepts "
                "only False until the benchmark's configurations drop it")
        # input_mean/input_std: per-channel (or scalar) normalization
        # applied ON DEVICE to uint8 data inputs staged via put_batch —
        # the raw_uint8 ingest path (native reader ships bytes, the chip
        # does (x - mean)/std; the reference normalizes on the host,
        # src/io/iter_normalize.h)
        self._input_mean = input_mean
        self._input_std = input_std
        if layout not in (None, "NCHW", "NHWC"):
            raise MXNetError("unsupported layout %r" % (layout,))
        self._layout = layout or "NCHW"
        # fuse_blocks: block-granularity fusion pass (analysis.fusion) —
        # conv+BN+ReLU / FC+activation chains emitted as single
        # custom-vjp regions with a pinned layout per boundary, on both
        # the train step's forward AND its backward.  Works in either
        # layout; None -> the MXNET_FUSE_BLOCKS env default.
        if fuse_blocks is None:
            from ..ops import fused as _fused_mod
            fuse_blocks = _fused_mod.block_fusion_enabled()
        self._fuse_blocks = bool(fuse_blocks)
        # stem_space_to_depth: equivalent 4x4/s1 rewrite of the 7x7/s2
        # C=3 stem conv (ops/fused.py stem_s2d_conv)
        if stem_space_to_depth is None:
            from ..ops import fused as _fused_mod
            stem_space_to_depth = _fused_mod.stem_s2d_enabled()
        self._stem_s2d = bool(stem_space_to_depth) and \
            self._layout == "NHWC"
        # elide_input_bn_grad: skip backward-data of convs that only feed
        # an input-BN beta grad (ops/fused.py).  Always sound here: the
        # trainer's vjp differentiates params only, never batch inputs.
        self._elide_input_grads = bool(elide_input_bn_grad)
        # pipeline_stages > 1: GPipe over the mesh's 'pipe' axis — the
        # graph is cut into stages at single-live-tensor positions and
        # the step streams microbatches stage-to-stage over ICI
        # (parallel/pipeline.py heterogeneous schedule)
        self._pp = int(pipeline_stages)
        if self._pp > 1:
            if mesh.shape.get("pipe", 1) != self._pp:
                raise MXNetError(
                    "pipeline_stages=%d needs a mesh with a 'pipe' axis "
                    "of that size (build_mesh(pp=%d)); mesh has %r"
                    % (self._pp, self._pp, dict(mesh.shape)))
            if mesh.shape.get("model", 1) != 1:
                raise MXNetError("pipeline_stages cannot combine with "
                                 "tensor parallelism (packed stage "
                                 "params cannot also be tensor-sharded)")
        self._pp_microbatches = int(pipeline_microbatches or
                                    (2 * self._pp if self._pp > 1 else 1))
        # sequence_parallel: shard data inputs' dim 1 (the sequence) over
        # the 'model' axis and activate the ring-attention context, so
        # _contrib_RingAttention nodes run the ICI ring schedule
        # (parallel/sequence.py).  Weights stay replicated over 'model'
        # (tp_rules default {}): the axis carries sequence shards.
        self._seq_parallel = bool(sequence_parallel)
        if self._seq_parallel:
            sp_size = mesh.shape.get("model", 1)
            if sp_size <= 1:
                raise MXNetError(
                    "sequence_parallel=True needs a mesh 'model' axis of "
                    "size > 1 to shard the sequence over (build_mesh(tp="
                    "n) — the axis carries sequence shards here)")
            if self._pp > 1:
                raise MXNetError("sequence_parallel does not compose "
                                 "with pipeline_stages yet")
            for n, s in data_shapes.items():
                if len(s) >= 2 and s[1] % sp_size:
                    raise MXNetError(
                        "sequence_parallel: input %r sequence dim %d is "
                        "not divisible by the %d sequence shards"
                        % (n, s[1], sp_size))

        if strict is None:
            from .. import config as _config
            strict = _config.get_bool("MXNET_TPU_STRICT_BIND")
        with _span("trainer.build.graph", category="trainer"):
            self._analyse_graph(
                symbol, mesh, data_shapes, label_shapes, optimizer,
                optimizer_params, learning_rate, momentum, weight_decay,
                tp_rules, strict)
        with _span("trainer.build.init_params", category="trainer") as sp:
            host_params, sp.attrs = self._init_state(initializer, seed)
        with _span("trainer.build.place", category="trainer"):
            self._place_state(host_params, seed)
        with _span("trainer.build.plan", category="trainer"):
            self._plan_step(strict)

    def _analyse_graph(self, symbol, mesh, data_shapes, label_shapes,
                       optimizer, optimizer_params, learning_rate,
                       momentum, weight_decay, tp_rules, strict):
        """``trainer.build.graph``: everything the constructor works
        out from the symbol and the mesh before any array exists —
        variables and index inputs, shape inference, the optimizer's
        rule, tensor-parallel rules, the SPMD verification pass, the
        shardings."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        self._topo = symbol._topo()
        if self._layout == "NHWC":
            self._check_nhwc_safe()
        arg_nodes, aux_nodes = _classify_vars(self._topo)
        self._arg_nodes, self._aux_nodes = arg_nodes, aux_nodes
        arg_names = [n.name for n in arg_nodes]
        self._input_names = list(data_shapes) + list(label_shapes or ())
        self._data_names = list(data_shapes)
        self._label_shapes = dict(label_shapes or {})
        self._param_names = [n for n in arg_names
                             if n not in self._input_names]
        self._aux_names = [n.name for n in aux_nodes]
        # expert layers' load statistics, carried as aux state like a
        # batch norm's moving statistics: {aux name: layer name}
        self._moe_loads = {
            node.inputs[-1][0].name: node.name for node in self._topo
            if node.op is not None and node.op.name == "_contrib_TopKMoE"
            and node.inputs[-1][0].is_variable}

        # data inputs consumed as integer indices (Embedding/take/...):
        # these must NOT be cast to a narrow compute dtype — bf16 rounds
        # ids above 256, silently corrupting lookups (ADVICE r3).
        # Carrier tracking walks pass-through (shape-only) ops, so
        # Embedding(Reshape(data)) still registers the data input.
        _index_arg_of = {"Embedding": 0, "one_hot": 0, "take": 1,
                         "gather_nd": 1, "batch_take": 1}
        _pass_through = frozenset({
            "Reshape", "Flatten", "expand_dims", "transpose", "BlockGrad",
            "slice_axis", "slice", "identity", "stop_gradient",
            "SwapAxis", "squeeze"})
        carriers = {id(n): n.name for n in self._arg_nodes
                    if n.name in self._data_names}
        self._int_inputs = set()
        self._int_input_bounds = {}   # name -> max Embedding input_dim
        unbounded = set()             # consumed by a boundless index op
        for node in self._topo:
            if node.op is None:
                continue
            opname = node.op.name
            if opname in _pass_through and node.inputs:
                src = node.inputs[0][0]
                if id(src) in carriers:
                    carriers[id(node)] = carriers[id(src)]
            idx = _index_arg_of.get(opname)
            if idx is None or idx >= len(node.inputs):
                continue
            nm = carriers.get(id(node.inputs[idx][0]))
            if nm is None:
                continue
            self._int_inputs.add(nm)
            if opname == "Embedding" and nm not in unbounded:
                self._int_input_bounds[nm] = max(
                    self._int_input_bounds.get(nm, 0),
                    int(node.attrs.get("input_dim", 0)))
            elif opname != "Embedding":
                # take/one_hot/gather tables carry no declared id range
                unbounded.add(nm)
                self._int_input_bounds.pop(nm, None)

        # inputs whose activations move to channel-minor under NHWC
        self._nhwc_inputs = set()
        if self._layout == "NHWC":
            self._nhwc_inputs = {n for n, s in data_shapes.items()
                                 if len(s) == 4}

        def to_layout(name, shape):
            if name in self._nhwc_inputs:
                n, c, h, w = shape
                return (n, h, w, c)
            return tuple(shape)

        shapes = {n: to_layout(n, s) for n, s in data_shapes.items()}
        for n, s in (label_shapes or {}).items():
            shapes[n] = tuple(s)
        self._input_shapes = shapes
        # raw host-convention (NCHW) global shapes, for staging
        # per-process shards of untransposed host batches (multi-host)
        self._host_input_shapes = {n: tuple(s)
                                   for n, s in data_shapes.items()}
        for n, s in (label_shapes or {}).items():
            self._host_input_shapes[n] = tuple(s)
        with image_layout(self._layout):
            arg_shapes, _, aux_shapes = symbol.infer_shape(**shapes)
        self._arg_shapes = dict(zip(arg_names, arg_shapes))
        self._aux_shapes = dict(zip(self._aux_names, aux_shapes))
        global_batch = next(iter(data_shapes.values()))[0]

        # ---- optimizer: registry-created, reference mult semantics
        if isinstance(optimizer, str):
            kw = dict(optimizer_params or {})
            kw.setdefault("learning_rate", learning_rate)
            kw.setdefault("wd", weight_decay)
            if optimizer.lower() in ("sgd", "ccsgd", "nag", "dcasgd"):
                kw.setdefault("momentum", momentum)
            kw.setdefault("rescale_grad", 1.0 / global_batch)
            kw.setdefault("param_idx2name",
                          {n: n for n in self._param_names})
            optimizer = _opt_mod.create(optimizer, **kw)
        else:
            # instance path: mirror Module.init_optimizer (reference
            # module.py:461-463) — default rescale to gradient averaging
            # and give the wd_mult/lr_mult machinery the param names
            if optimizer.rescale_grad == 1.0:
                optimizer.rescale_grad = 1.0 / global_batch
            if not optimizer.idx2name:
                optimizer.idx2name = {n: n for n in self._param_names}
                optimizer.set_lr_mult({})
                optimizer.set_wd_mult({})
        self.optimizer = optimizer
        self._rescale = optimizer.rescale_grad
        self._n_slots, self._update_rule = _make_update_rule(optimizer)

        tp_size = mesh.shape.get("model", 1)
        if tp_rules is None:
            if self._seq_parallel:
                # the model axis carries sequence shards; weights replicate
                tp_rules = {}
            else:
                # graph-derived Megatron-style defaults: column/row-
                # parallel FC pairing (QKV/out-proj, ff1/ff2) + conv
                # output-channel sharding (parallel/tp_rules.py)
                from .tp_rules import derive_tp_rules
                tp_rules = derive_tp_rules(self._topo, self._arg_shapes,
                                           tp_size)
                if tp_size > 1 and tp_rules:
                    # surface the derived layout once: which weights got
                    # model-axis sharded (and on which dim) decides the
                    # communication pattern and per-chip memory
                    import logging
                    logging.info(
                        "ShardedTrainer derived tp_rules (Megatron "
                        "pairing, tp=%d): %s", tp_size,
                        {k: tp_rules[k] for k in sorted(tp_rules)})
        # reshard rule table (MXNET_TPU_RESHARD_RULES, parallel/reshard
        # grammar): regex rules overriding the derived tp_rules per
        # param — the operator's hand-written partition layout for the
        # CURRENT mesh, the match_partition_rules pattern.  Entries may
        # only name the 'model' axis (weights never shard over 'data');
        # an all-replicated spec ("name=") un-shards a derived rule.
        from . import reshard as _reshard
        rrules = _reshard.env_rules()
        if rrules:
            tp_rules = dict(tp_rules)
            for name in self._param_names:
                spec = _reshard.first_match(rrules, name)
                if spec is None:
                    continue
                dims = [d for d, ax in enumerate(spec) if ax is not None]
                for d in dims:
                    if str(spec[d]) != "model":
                        raise MXNetError(
                            "reshard rule for param %r names axis %r; "
                            "trainer params shard only over 'model' "
                            "(the 'data' axis carries batches)"
                            % (name, spec[d]))
                if len(dims) > 1:
                    raise MXNetError(
                        "reshard rule for param %r shards %d dims; the "
                        "trainer supports one sharded dim per weight"
                        % (name, len(dims)))
                if not dims or tp_size <= 1:
                    if dims:
                        # a model-sharding rule on a mesh with no
                        # model axis degenerates to replicated — loud
                        # enough to notice, soft enough that one fleet
                        # -wide rule file survives an elastic shrink
                        # to a single device
                        import logging
                        logging.warning(
                            "reshard rule for param %r requests "
                            "'model' sharding but the mesh has no "
                            "model axis (tp=1); the param stays "
                            "replicated", name)
                    tp_rules.pop(name, None)
                    continue
                d = dims[0]
                shp = self._arg_shapes[name]
                if d >= len(shp) or shp[d] % tp_size:
                    raise MXNetError(
                        "reshard rule for param %r cannot shard dim %d "
                        "of shape %s over the %d-way 'model' axis"
                        % (name, d, tuple(shp), tp_size))
                tp_rules[name] = d
        self.tp_rules = tp_rules

        # distributed-correctness pass (analysis.spmd, MXG011-016): the
        # composed (graph, mesh, parallel config) triple is verified
        # BEFORE any compile — mismatched collectives, infeasible
        # stage/axis partitions and conflicting sharding specs raise a
        # node-level diagnostic here instead of hanging a fleet
        if strict:
            from ..analysis import spmd as _spmd
            _spmd.verify_trainer_config(
                symbol, mesh,
                data_shapes=dict(data_shapes),
                label_shapes=dict(label_shapes or {}),
                pipeline_stages=self._pp,
                pipeline_microbatches=self._pp_microbatches,
                sequence_parallel=self._seq_parallel,
                tp_rules=tp_rules, dtype=self.dtype,
                arg_shapes=self._arg_shapes,
            ).raise_if_errors("ShardedTrainer strict bind")
            # static memory-liveness pass (analysis.memlive): predict
            # the step's peak HBM from liveness intervals — sharding-
            # and donation-aware (the step jits donate params/opt/aux)
            # — and record it so budget checks and OOM reports compare
            # the static peak against the XLA plan (MXG018 drift
            # gauge).  With a budget armed, an over-budget step is
            # rejected HERE (MXG017), before any compile.
            from ..analysis import memlive as _memlive
            from ..analysis.verifier import Report as _Report
            try:
                axes = {str(k): int(v)
                        for k, v in dict(mesh.shape).items()}
            except Exception:  # mxlint: allow-broad-except(mesh.shape drifted across jax versions; an unknown mesh just disables sharding-aware byte division)
                axes = {}
            mem_report = _Report()
            _memlive.check_memory(
                symbol,
                shapes={**dict(data_shapes), **dict(label_shapes or {})},
                report=mem_report, is_train=True, mesh=axes,
                tp_rules=dict(tp_rules), n_slots=self._n_slots,
                donate=True, advice=False, record=True,
                program="trainer.step")
            mem_report.raise_if_errors(
                "ShardedTrainer strict bind (memory)")

        def param_spec(name):
            shp = self._arg_shapes.get(name, self._aux_shapes.get(name))
            spec = [None] * len(shp)
            if name in tp_rules:
                spec[tp_rules[name]] = "model"
            return P(*spec)

        self._param_sharding = {
            n: NamedSharding(mesh, param_spec(n)) for n in self._param_names}
        self._aux_sharding = {
            n: NamedSharding(mesh, P(*([None] * len(self._aux_shapes[n]))))
            for n in self._aux_names}
        def batch_spec(n):
            dims = ["data"] + [None] * (len(shapes[n]) - 1)
            if self._seq_parallel and n in self._data_names \
                    and len(dims) >= 2:
                dims[1] = "model"       # the sequence dim
            return P(*dims)

        self._batch_sharding = {
            n: NamedSharding(mesh, batch_spec(n))
            for n in self._input_names}

    def _init_state(self, initializer, seed):
        """``trainer.build.init_params``: the f32 masters and the aux
        state.  Every parameter whose rule is ``traceable``
        (``initializer.rule_for``) and all of the aux state come out of
        ONE jitted program under their own shardings, so each device
        (each process of a multi-host job) materialises its shards and
        nothing whole exists on the host.  A rule that only fills a host
        array (a user's ``_init_weight`` over ``np.random``, ``Load``,
        an SVD) is run here on numpy, as it always was.  Returns those
        host-drawn values and the span's attributes.  Initializer
        errors propagate: a wrong-shape bug must not silently become a
        different init."""
        import jax
        import jax.numpy as jnp
        init = initializer or Xavier(rnd_type="gaussian", factor_type="in",
                                     magnitude=2)
        attrs = self.symbol.attr_dict()
        traced, host_params = {}, {}
        for index, name in enumerate(self._param_names):
            desc = InitDesc(name, attrs.get(name))
            rule = rule_for(init, desc)
            if getattr(rule, "traceable", False):
                traced[name] = (rule, desc, index)
                continue
            arr = _HostArray(np.zeros(self._arg_shapes[name], np.float32))
            rule(desc, arr)
            host_params[name] = arr.data

        # parameters of one rule and one shape share ONE traced and
        # lowered function, called once a parameter with its own key:
        # a model of many small layers costs a trace and a lowering per
        # distinct shape, not per parameter.  (Not one vmapped draw a
        # shape: XLA compiles eight stacked draws of 2048 x 2048 in 7 s
        # where one takes 0.3 s.)
        drawers = {}
        fold_in = jax.jit(jax.random.fold_in)

        def drawn(name, key):
            rule, desc, _ = traced[name]
            shape = self._arg_shapes[name]
            at = (rule, shape)
            if at not in drawers:
                drawers[at] = jax.jit(
                    lambda k: draw(rule, desc, shape, k))
            return drawers[at](key)

        def program(key):
            key = jax.random.fold_in(key, _INIT_STREAM)
            params = {name: drawn(name, fold_in(key, index))
                      for name, (_, _, index) in traced.items()}
            aux = {name: jnp.full(
                       self._aux_shapes[name],
                       1.0 if name.endswith("moving_var") else 0.0,
                       jnp.float32)
                   for name in self._aux_names}
            return params, aux

        # a numpy key: every process of a multi-host job hands the
        # program the same replicated value
        key = np.asarray(jax.random.PRNGKey(seed))
        shardings = ({n: self._param_sharding[n] for n in traced},
                     self._aux_sharding)
        with self.mesh:
            self.params, self.aux = jax.jit(
                program, out_shardings=shardings)(key)

        def nbytes(names):
            return sum(4 * int(np.prod(self._arg_shapes[n])) for n in names)
        return (host_params,
                {"device_params": len(traced),
                 "device_bytes": nbytes(traced),
                 "host_params": len(host_params),
                 "host_bytes": nbytes(host_params),
                 "host_names": sorted(host_params)[:8]})

    def _place_state(self, host_params, seed):
        """``trainer.build.place``: the host-drawn parameters and the
        zeroed optimizer slots onto the mesh, and the step key."""
        import jax
        # NB multi-host: every process runs this constructor with the
        # same seeds.  The device-drawn state (_init_state) is one
        # global program, each rank holding its shards only; a
        # HOST-drawn parameter is the identical full value on every
        # rank, of which _put_state slices out the addressable shards
        with self.mesh:
            for n, value in host_params.items():
                self.params[n] = self._put_state(
                    value, self._param_sharding[n])
            self.params = {n: self.params[n] for n in self._param_names}
            self.opt_state = self._device_zero_slots()
        self._key = jax.random.PRNGKey(seed)

    def _plan_step(self, strict):
        """``trainer.build.plan``: the fusion plan's decisions, the
        step function that will trace under them, and the dispatch
        bookkeeping."""
        # plan-search decisions (analysis.plansearch): an ambient
        # plan_decisions context wins; otherwise consult the committed
        # graph_plan tuning-cache entry ONCE at construction — keyed by
        # the graph's structural digest + trace layout + THIS mesh's
        # axis sizes + backend — and activate it around every step
        # trace, so a tuned plan is dispatched with zero search cost
        # (greedy on miss, like kernel configs).  Pipeline stages never
        # fuse (seeded partial topos), so the lookup is skipped there.
        from ..analysis import fusion as _fusion_mod
        self._plan_decisions = _fusion_mod.active_decisions()
        if self._plan_decisions is None and self._fuse_blocks \
                and self._pp <= 1:
            from ..analysis import plansearch as _plansearch
            self._plan_decisions = _plansearch.committed_decisions(
                self._topo, self.symbol._entries, self._layout,
                mesh=self._mesh_axis_sizes())
        self._step_fn = self._build_step()
        if strict:
            # MXG012 over the REAL step program: trace the un-jitted
            # step (no XLA compile) and scan its jaxpr for collectives
            # under axis_index-conditioned control flow.  Strict-only —
            # costs one extra trace of the step
            self._verify_step_rank_divergence()
        # the numerics variant (telemetry.numerics): the same step with
        # an in-graph stat tree as a fifth output, compiled lazily on
        # the first SAMPLED step (MXNET_TPU_NUMERICS_EVERY) so runs with
        # numerics off never pay the extra compile
        self._stats_step_fn = None
        self._scan_fns = {}
        # AOT executables dispatched in place of the jit wrappers, keyed
        # (program, id(fn)): the memory plan comes from the SAME compile
        # that runs the step (jax shares no cache between lower().
        # compile() and jit calls, so a separate analysis compile would
        # double every compile)
        self._aot_exes = {}
        # costdb dispatch scope: process-unique and rotated on rebuild,
        # so a rebuilt fn reusing a collected fn's id cannot alias its
        # dispatch counters (a compile dispatch mistaken for post-warm
        # would get its compile timed as dispatch wall)
        from ..telemetry import costdb as _costdb
        self._costdb_scope = _costdb.next_scope()
        self._fwd_fn = None
        self._step_count = 0
        # current step's straggler-attribution accumulator (reset by
        # step()/run_steps(); see telemetry.distview)
        self._seg = {"input_s": 0.0, "collective_s": 0.0, "skew": None}
        # epoch this trainer resumed from (load_checkpoint sets it):
        # _step_count restarts at 0 after a resume, so anything deriving
        # a global step/epoch must add this offset
        self._resume_epoch = 0
        self._hyper_snapshot = self._hyper_state()

    def _verify_step_rank_divergence(self):
        """MXG012 over the step this trainer will actually dispatch:
        trace the un-jitted step function with this trainer's own
        state/batch avals (``jax.make_jaxpr`` — no compile) and scan
        the jaxpr for collectives under rank-conditioned control flow
        (``analysis.spmd.verify_step_fn``).  Raises on findings."""
        import jax
        import jax.numpy as jnp
        from ..analysis import spmd as _spmd
        py_step = getattr(self, "_py_step", None)
        if py_step is None:
            return
        batch = {n: jax.ShapeDtypeStruct(
                     tuple(self._input_shapes[n]), jnp.float32)
                 for n in self._input_names}
        args = (self.params, self.opt_state, self.aux, batch,
                jax.ShapeDtypeStruct((2,), jnp.uint32),
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.float32))
        _spmd.verify_step_fn(py_step, args).raise_if_errors(
            "ShardedTrainer strict bind")

    def _device_zero_slots(self):
        """Fresh optimizer slots created ON DEVICE by one jitted program
        (host-side np.zeros + device_put would ship the whole optimizer
        state — e.g. 1.5 GB for adam on a 190M-param model — over the
        host link just to write zeros)."""
        import jax
        import jax.numpy as jnp

        if self._n_slots == 0:
            return {n: [] for n in self._param_names}

        def make():
            return {n: [jnp.zeros(self._arg_shapes[n], jnp.float32)
                        for _ in range(self._n_slots)]
                    for n in self._param_names}

        shardings = {n: [self._param_sharding[n]] * self._n_slots
                     for n in self._param_names}
        return jax.jit(make, out_shardings=shardings)()

    def _put_state(self, value, target):
        """Stage a full host value (identical on every process) as a
        device array under the NamedSharding ``target``."""
        import jax
        if self._multiproc:
            from . import multihost
            return multihost.stage_local(target, value)
        return jax.device_put(value, target)

    def _hyper_state(self):
        """Optimizer hyperparameters baked into the compiled step."""
        opt = self.optimizer
        rule_attrs = tuple(
            (a, getattr(opt, a)) for a in
            ("momentum", "beta1", "beta2", "epsilon", "gamma1", "gamma2",
             "rho", "float_stable_eps") if hasattr(opt, a))
        return (dict(opt.lr_mult), dict(opt.wd_mult), opt.wd,
                opt.rescale_grad, opt.clip_gradient, rule_attrs)

    # ------------------------------------------------------------ builders
    # ops adapted to NHWC activations (ops/nn.py) — their axis attrs are
    # remapped at trace time, so an explicit channel-ish axis is fine
    _NHWC_ADAPTED = frozenset({
        "Convolution", "Deconvolution", "Pooling", "BatchNorm", "Concat",
        "SliceChannel", "LRN", "InstanceNorm", "LeakyReLU", "UpSampling",
        "Crop", "Pad", "SoftmaxActivation", "Flatten", "FullyConnected",
        "Activation", "Dropout", "SoftmaxOutput",
    })

    def _check_nhwc_safe(self):
        """Refuse NHWC mode for graphs whose ops would silently index the
        wrong axis.  Two classes: known channel-axis ops with no NHWC
        adaptation, and generic tensor ops pinning an explicit axis that
        could be spatial/channel (axis semantics are written against the
        reference NCHW convention)."""
        from ..ops.nn import NHWC_UNAWARE_OPS
        bad = set()
        for node in self._topo:
            if node.op is None:
                continue
            name = node.op.name
            if name in NHWC_UNAWARE_OPS:
                bad.add(name)
                continue
            if name in self._NHWC_ADAPTED:
                continue
            if name == "transpose" and not node.attrs.get("axes"):
                bad.add("transpose()")  # default axes reverse all dims
                continue
            for key in ("axis", "dim", "axes", "begin", "end"):
                v = node.attrs.get(key)
                vals = v if isinstance(v, (tuple, list)) else (v,)
                if any(isinstance(x, int) and
                       (1 <= x <= 3 or -3 <= x <= -1) for x in vals):
                    bad.add("%s(%s=%r)" % (name, key, v))
                    break
        if bad:
            raise MXNetError(
                "layout='NHWC' is not supported for graphs containing "
                "%s — these index axes in the reference NCHW convention "
                "and have no NHWC adaptation; use the default NCHW "
                "layout" % ", ".join(sorted(bad)))

    def _node_value_map(self, params, batch, aux):
        vals = {}
        for node in self._arg_nodes:
            if node.name in params:
                vals[id(node)] = params[node.name]
            else:
                vals[id(node)] = batch[node.name]
        for node in self._aux_nodes:
            vals[id(node)] = aux[node.name]
        return vals

    def _per_param_hyper(self, name):
        """Static (lr_mult, effective_wd) for one param, ref semantics."""
        opt = self.optimizer
        lr_mult = opt.lr_mult.get(name, 1.0)
        wd_mult = opt.wd_mult.get(name, 1.0)
        return lr_mult, wd_mult * opt.wd

    def _abstract_node_shapes(self, micro_bsz):
        """{(id(node), out_idx): shape} for every op-node output, traced
        abstractly at microbatch size (no FLOPs; jax.eval_shape)."""
        import jax
        import jax.numpy as jnp
        from ..symbol import eval_graph

        shapes = {}
        name2ni = {}
        for node in self._topo:
            if node.is_variable or node.op is None:
                continue
            for i, on in enumerate(node.output_names()):
                name2ni[on] = (id(node), i)

        def mon(name, val):
            k = name2ni.get(name)
            if k is not None:
                shapes[k] = tuple(val.shape)

        gbatch = self._input_shapes[self._data_names[0]][0]

        def absfwd():
            vv = {}
            for node in self._arg_nodes:
                nm = node.name
                if nm in self._input_names:
                    # leading dims scale by micro_bsz/gbatch so per-token
                    # labels declared (batch*seq,) trace at (micro*seq,),
                    # mirroring the runtime side-array microbatch split
                    full = self._input_shapes[nm]
                    shp = (full[0] * micro_bsz // gbatch,) + tuple(full[1:])
                    dt = jnp.float32 if "label" in nm \
                        else jnp.dtype(self.dtype)
                else:
                    shp = self._arg_shapes[nm]
                    dt = jnp.dtype(self.dtype)
                vv[id(node)] = jnp.zeros(shp, dt)
            for node in self._aux_nodes:
                vv[id(node)] = jnp.zeros(self._aux_shapes[node.name],
                                         jnp.float32)
            with image_layout(self._layout):
                eval_graph(self._topo, self.symbol._entries, vv,
                           is_train=False, key=None, monitor=mon,
                           batch_size=micro_bsz)
            return 0

        jax.eval_shape(absfwd)
        return shapes

    def _build_pipeline_step(self, collect_stats=False):
        """GPipe step: the graph cut into ``pipeline_stages`` segments,
        each stage's packed params resident on its 'pipe'-axis device,
        microbatches streamed stage-to-stage over ICI (ppermute), all
        inside ONE jit.  See parallel/pipeline.py for the schedule and
        the packing encoding.  Composes with data parallelism over the
        mesh's 'data' axis (shard_map transposition inserts the grad
        psum).  Successor of the reference's per-device layer placement
        (example/model-parallel-lstm/lstm.py:142-205)."""
        import functools
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .mesh import shard_map_nocheck
        from ..symbol import eval_graph
        from .pipeline import plan_pipeline_stages, hetero_pipeline_loss

        n_pp, m_micro = self._pp, self._pp_microbatches
        mesh = self.mesh
        dp = mesh.shape.get("data", 1)
        topo, entries = self._topo, self.symbol._entries

        if len(entries) != 1 or entries[0][0].op is None \
                or entries[0][0].op.name != "SoftmaxOutput":
            raise MXNetError(
                "the pipeline-parallel trainer currently supports a "
                "single SoftmaxOutput loss head (its custom vjp is "
                "cotangent-independent, so pipelined gradients are "
                "reference-exact); got %r"
                % [e[0].op.name if e[0].op else "var" for e in entries])
        hattrs = entries[0][0].attrs
        if float(hattrs.get("grad_scale", 1.0)) != 1.0 or \
                hattrs.get("normalization", "null") != "null" or \
                hattrs.get("use_ignore") or hattrs.get("multi_output"):
            raise MXNetError("pipeline path supports SoftmaxOutput with "
                             "default grad_scale/normalization/"
                             "multi_output only")
        head_label_var = entries[0][0].inputs[1][0]
        if not head_label_var.is_variable:
            raise MXNetError("pipeline path needs the loss label to be "
                             "a batch variable (got a computed input)")
        label_name = head_label_var.name
        if len(self._data_names) != 1:
            raise MXNetError("pipeline path supports one data input")
        dname = self._data_names[0]
        compute_dtype = jnp.dtype(self.dtype)
        if compute_dtype.kind == "f" and dname in self._int_inputs:
            # the pipeline ring buffer carries stage inputs in the
            # compute dtype; token ids above the dtype's exact-integer
            # range would be rounded in transit
            exact = 1 << (jnp.finfo(compute_dtype).nmant + 1)
            bound = self._int_input_bounds.get(dname)
            # ids run 0..input_dim-1, and integers up to `exact` are
            # representable, so input_dim == exact+1 is still safe
            if bound is None or bound > exact + 1:
                # unknown bound (take/gather consumer) is treated as
                # over-range: silent id rounding is worse than refusing
                raise MXNetError(
                    "pipeline_stages with dtype=%s cannot carry %r as "
                    "integer ids through the compute-dtype ring buffer: "
                    "id range %s exceeds (or cannot be proven within) "
                    "the dtype's exact-integer range %d; use "
                    "dtype='float32' or a first-stage cut after the "
                    "lookup" % (self.dtype, dname,
                                bound if bound is not None else "unknown",
                                exact))
        gbatch = self._input_shapes[dname][0]
        if gbatch % (dp * m_micro):
            raise MXNetError(
                "global batch %d not divisible by data-parallel size %d "
                "x %d microbatches" % (gbatch, dp, m_micro))
        bu = gbatch // (dp * m_micro)

        shapes = self._abstract_node_shapes(bu)

        def nelem(shp):
            n = 1
            for d in shp:
                n *= int(d)
            return n

        def cost_of(node):
            c = float(nelem(shapes.get((id(node), 0), (1,))))
            for (src, _i) in node.inputs:
                if src.is_variable and src.name in self._arg_shapes \
                        and src.name not in self._input_names:
                    c += float(nelem(self._arg_shapes[src.name]))
            return c

        def legal_cut(bound):
            # the ring buffer is (microbatch_rows, W): a boundary whose
            # leading dim is not the microbatch row count (e.g. after a
            # batch-folding Reshape) cannot ride it
            shp = shapes.get((id(bound[0]), bound[1]))
            return shp is not None and len(shp) >= 1 and shp[0] == bu

        stages = plan_pipeline_stages(topo, entries,
                                      set(self._input_names), n_pp,
                                      cost_of=cost_of,
                                      legal_cut=legal_cut)

        # boundary widths -> the common ring buffer width W
        widths = [nelem(self._input_shapes[dname][1:])]
        for s in stages[1:]:
            bnode, bidx = s["boundary_in"]
            widths.append(nelem(shapes[(id(bnode), bidx)][1:]))
        buf_w = max(widths)

        # packed per-stage parameter layouts
        layouts, lens = [], []
        for s in stages:
            off, lay = 0, []
            for nm in s["param_names"]:
                shp = self._arg_shapes[nm]
                lay.append((nm, tuple(shp), off, nelem(shp)))
                off += nelem(shp)
            layouts.append(lay)
            lens.append(off)
        pack_l = max(lens + [1])

        side_names = []
        for si, s in enumerate(stages):
            for nm in s["batch_names"]:
                if si == 0 and nm == dname:
                    continue
                if nm not in side_names:
                    side_names.append(nm)

        compute_dtype = jnp.dtype(self.dtype)
        layout = self._layout
        name2arg = {n.name: n for n in self._arg_nodes}

        head_node = entries[0][0]

        def make_branch(si):
            meta = stages[si]
            lay = layouts[si]
            is_last = si == n_pp - 1
            if si == 0:
                in_feat = tuple(self._input_shapes[dname][1:])
            else:
                bnode, bidx = meta["boundary_in"]
                in_feat = tuple(shapes[(id(bnode), bidx)][1:])
            insize = nelem(in_feat)
            # Last stage stops BEFORE the SoftmaxOutput head and computes
            # softmax + summed CE manually: the gradient is identically
            # (p - onehot) (the head's reference convention at
            # grad_scale=1/normalization null), but it flows through
            # standard autodiff — the head's cotangent-IGNORING
            # custom_vjp would inject gradients from the schedule's
            # inactive fill/drain ticks that the active-mask cannot zero.
            seg_nodes = meta["nodes"] if not is_last else \
                [n for n in meta["nodes"] if n is not head_node]
            seg_entries = [head_node.inputs[0]] if is_last \
                else [stages[si + 1]["boundary_in"]]
            # eval_graph binds variables by iterating them in topo order
            seg_vars, seen = [], set()
            for n in seg_nodes:
                for (src, _i) in n.inputs:
                    if src.is_variable and id(src) not in seen:
                        seen.add(id(src))
                        seg_vars.append(src)
            seg_topo = seg_vars + seg_nodes

            def branch(row, x_flat, mb, side):
                p = {nm: row[off:off + sz].reshape(shp)
                     for (nm, shp, off, sz) in lay}
                nb = x_flat.shape[0]
                x = x_flat[:, :insize].reshape((nb,) + in_feat)
                var_values = {id(name2arg[nm]): v for nm, v in p.items()}
                seed = {}
                if si == 0:
                    var_values[id(name2arg[dname])] = x
                else:
                    bnode, bidx = meta["boundary_in"]
                    seed[id(bnode)] = tuple(
                        x if j == bidx else None
                        for j in range(bnode.num_outputs()))
                label = None
                for nm in meta["batch_names"]:
                    if si == 0 and nm == dname:
                        continue
                    sv = side[side_names.index(nm)]
                    v = lax.dynamic_index_in_dim(sv, mb, 0,
                                                 keepdims=False)
                    var_values[id(name2arg[nm])] = v
                    if nm == label_name:
                        label = v
                with image_layout(layout):
                    heads, _aux = eval_graph(
                        seg_topo, seg_entries, var_values,
                        is_train=True, key=None, batch_size=nb,
                        seed_vals=seed)
                # the per-branch loss is shape (1,), never rank 0: a
                # scalar on the differentiated path becomes a rank-0
                # shard_map residual, which jax 0.4.x's partial-eval
                # fails to promote on the remat/transpose path
                if is_last:
                    logits = heads[0].astype(jnp.float32)
                    logp = jax.nn.log_softmax(logits, axis=-1)
                    idx = label.astype(jnp.int32).reshape((-1, 1))
                    psel = jnp.take_along_axis(logp, idx, axis=1,
                                               mode="clip")[:, 0]
                    loss = -jnp.sum(psel).reshape((1,))
                    y_flat = jnp.zeros((nb, buf_w), compute_dtype)
                else:
                    y = heads[0]
                    y2 = y.reshape(nb, -1).astype(compute_dtype)
                    y_flat = jnp.pad(y2,
                                     ((0, 0), (0, buf_w - y2.shape[1])))
                    loss = jnp.zeros((1,), jnp.float32)
                return y_flat, loss

            return branch

        branches = [make_branch(si) for si in range(n_pp)]
        rescale = self._rescale
        rule = self._update_rule
        hyper = {k: self._per_param_hyper(k) for k in self._param_names}
        # metric divisor: the summed CE covers every head row (per-token
        # labels have gbatch*k rows); match the plain path's mean
        label_rows = self._input_shapes.get(label_name, (gbatch,))[0]

        x_side_specs = tuple(
            P(*([None, "data"] +
                [None] * (len(self._input_shapes[nm]) - 1)))
            for nm in side_names)

        def step(params, opt_state, aux, batch, key, lr, t):
            def loss_fn(p32):
                p = {k: v.astype(compute_dtype) for k, v in p32.items()}
                rows = []
                for si in range(n_pp):
                    parts = [p[nm].reshape(-1)
                             for (nm, _s, _o, _z) in layouts[si]]
                    row = jnp.concatenate(parts) if parts else \
                        jnp.zeros((0,), compute_dtype)
                    rows.append(jnp.pad(row, (0, pack_l - row.shape[0])))
                # the packed stage rows enter the shard_map REPLICATED
                # and each device selects its row by stage id inside the
                # body: resharding this in-jit concatenate onto the pipe
                # axis trips a GSPMD partitioner bug under dp x pp (the
                # partial-update all-reduce double-counts the data
                # replicas, scaling every packed param by dp)
                stacked = lax.with_sharding_constraint(
                    jnp.stack(rows), NamedSharding(mesh, P(None, None)))
                x = batch[dname].astype(compute_dtype)
                xs = x.reshape((m_micro, gbatch // m_micro, -1))
                xs = jnp.pad(xs, ((0, 0), (0, 0),
                                  (0, buf_w - xs.shape[2])))
                # side arrays microbatch on dim 0; a leading dim of
                # gbatch*k (e.g. per-token labels (batch*seq,)) splits
                # row-major into (M, local*k) consistently with the data
                side = tuple(
                    batch[nm].reshape((m_micro, -1)
                                      + tuple(batch[nm].shape[1:]))
                    for nm in side_names)

                def smbody(ps, xs_, sd):
                    br = [(lambda f: (lambda row, xx, mb:
                                      f(row, xx, mb, sd)))(f)
                          for f in branches]
                    # (1,)-shaped loss through the body (see
                    # hetero_pipeline_loss: jax 0.4.x mishandles
                    # rank-0 shard_map residuals under grad)
                    local = hetero_pipeline_loss(br, xs_, ps, m_micro)
                    return lax.psum(lax.psum(local, "pipe"), "data")

                return shard_map_nocheck(
                    smbody, mesh,
                    (P(None, None), P(None, "data", None),
                     x_side_specs), P(None))(stacked, xs, side)[0]

            with _plan.recording():
                loss_sum, grads = jax.value_and_grad(loss_fn)(params)
            new_params, new_state = {}, {}
            for k, w in params.items():
                lr_mult, wd_eff = hyper[k]
                g = grads[k].astype(jnp.float32) * rescale
                new_params[k], new_state[k] = rule(
                    w, g, opt_state[k], lr * lr_mult, wd_eff, t)
            new_aux = {n.name: aux[n.name] for n in self._aux_nodes}
            loss = loss_sum / label_rows
            if collect_stats:
                # param/grad numerics on the pipelined step (fused-block
                # stats don't apply: seeded partial graphs never fuse)
                from ..telemetry import numerics as _numerics
                stats = _numerics.step_stats(params, grads, loss=loss)
                return new_params, new_state, new_aux, loss, stats
            return new_params, new_state, new_aux, loss

        step.__name__ = step.__qualname__ = "mxtpu_train_step"
        if collect_stats:
            self._py_step_stats = step
        else:
            self._py_step = step
        return self._jit_over_state(step, 2 if collect_stats else 1)

    def _jit_over_state(self, fn, extra_outputs=1):
        """The one way a step program is jitted: ``fn(params, opt_state,
        aux, batch, key, lr, t)`` under the state's and the batch's
        shardings, the state donated; it returns the new state and
        ``extra_outputs`` more values (the loss; the stats)."""
        import jax
        state = (self._param_sharding,
                 {n: [self._param_sharding[n]] * self._n_slots
                  for n in self._param_names},
                 self._aux_sharding)
        return jax.jit(
            fn, in_shardings=state + (self._batch_sharding, None, None, None),
            out_shardings=state + (None,) * extra_outputs,
            donate_argnums=(0, 1, 2))

    def _build_step(self, collect_stats=False):
        """Build the jitted train step.  ``collect_stats=True`` builds
        the NUMERICS VARIANT (telemetry.numerics): the same step with a
        fifth output — the in-graph tensor-stat tree over params, grads,
        and (when block fusion is active) fused-block outputs.  It is a
        SEPARATE compile dispatched only on sampled steps, so unsampled
        steps run the unmodified program (the jaxpr equation count is
        bit-for-bit the no-numerics one)."""
        import jax
        import jax.numpy as jnp
        if self._pp > 1:
            return self._build_pipeline_step(collect_stats=collect_stats)

        topo, entries = self._topo, self.symbol._entries
        head_is_loss = [bool(n.op is not None and n.op.is_loss)
                        for (n, _i) in entries]
        head_is_own_loss = head_is_loss[0] \
            and entries[0][0].op.name == "MakeLoss"
        rescale = self._rescale
        compute_dtype = jnp.dtype(self.dtype)
        layout, rule = self._layout, self._update_rule
        hyper = {k: self._per_param_hyper(k) for k in self._param_names}

        def step(params, opt_state, aux, batch, key, lr, t):
            from ..telemetry import numerics as _numerics
            bsz = next(iter(batch.values())).shape[0]

            def fwd(p32):
                # compute-precision copies of the f32 masters (the astype
                # vjp returns f32 grads automatically)
                from ..ops.fused import (stem_s2d, elide_input_grads,
                                         block_fusion)
                from ..analysis.fusion import plan_decisions
                from .sequence import sequence_parallel as seq_ctx
                from .mesh import kernel_mesh
                p = {k: v.astype(compute_dtype) for k, v in p32.items()}
                with image_layout(layout), kernel_mesh(self.mesh), \
                        block_fusion(self._fuse_blocks), \
                        plan_decisions(self._plan_decisions), \
                        stem_s2d(self._stem_s2d), \
                        seq_ctx(self.mesh if self._seq_parallel
                                else None), \
                        elide_input_grads(
                            self._input_names
                            if self._elide_input_grads else ()):
                    var_values = self._node_value_map(p, batch, aux)
                    # fused-block output stats ride the stats variant
                    # only: the collection window is open while
                    # analysis.fusion.apply_block evaluates each block,
                    # and the stat scalars leave the vjp trace as part
                    # of fwd's auxiliary output (capturing the raw
                    # block tracers in a side dict would leak them)
                    with _numerics.block_stats(collect_stats) as sink:
                        heads, aux_upd = eval_graph(
                            topo, entries, var_values, is_train=True,
                            key=key, batch_size=bsz)
                return heads, (aux_upd, dict(sink) if sink else {})

            # stable names on the device: every op of the step carries
            # one of these scopes in its ``op_name``, whatever the compile.
            # The recording spans the forward trace and the pull: the
            # layers note their plans from either (telemetry.plan)
            from ..ops.nn import maybe_mirror
            with _plan.recording():
                with jax.named_scope(SCOPE_FWD):
                    heads, vjp, (aux_upd, blk_stats) = jax.vjp(
                        maybe_mirror(fwd), params, has_aux=True)
                with jax.named_scope(SCOPE_BWD):
                    cot = [jnp.ones_like(h) if il else jnp.zeros_like(h)
                           for h, il in zip(heads, head_is_loss)]
                    (grads,) = vjp(list(cot))

            new_params, new_state = {}, {}
            with jax.named_scope(SCOPE_OPT):
                for k, w in params.items():
                    lr_mult, wd_eff = hyper[k]
                    g = grads[k].astype(jnp.float32) * rescale
                    new_params[k], new_state[k] = rule(
                        w, g, opt_state[k], lr * lr_mult, wd_eff, t)

            new_aux = {}
            for n in self._aux_nodes:
                upd = aux_upd.get(id(n), aux[n.name])
                new_aux[n.name] = upd.astype(jnp.float32)

            # monitoring loss: mean -log p(label) from the softmax head;
            # a head that is its own loss (``MakeLoss``: the labels and
            # the weights were made in the graph) is monitored by its
            # value, the mean over its elements, in float32
            loss = jnp.float32(0)
            label = None
            for nm in self._input_names:
                if "label" in nm:
                    label = batch[nm]
            if head_is_own_loss:
                loss = jnp.mean(heads[0].astype(jnp.float32))
            elif label is not None and head_is_loss[0]:
                probs = heads[0]
                if probs.ndim == 2 and label.ndim >= 2 and \
                        label.size == probs.shape[0]:
                    # per-token labels fed as (batch, seq): the head
                    # flattened rows row-major, labels follow
                    label = label.reshape((-1,))
                if probs.ndim == 2 and label.ndim == 1:
                    idx = label.astype(jnp.int32).reshape((-1, 1))
                    # mode="clip": jit's default fill mode turns an
                    # out-of-range label into NaN and poisons the metric
                    p = jnp.take_along_axis(
                        probs.astype(jnp.float32), idx, axis=1,
                        mode="clip")[:, 0]
                    loss = -jnp.mean(jnp.log(jnp.maximum(p, 1e-10)))
            if collect_stats:
                stats = _numerics.step_stats(params, grads,
                                             blocks=blk_stats,
                                             loss=loss)
                return new_params, new_state, new_aux, loss, stats
            return new_params, new_state, new_aux, loss

        # the module on a trace's "XLA Modules" line: jit_mxtpu_train_step
        step.__name__ = step.__qualname__ = "mxtpu_train_step"
        if collect_stats:
            self._py_step_stats = step
        else:
            # the scan chain (_build_multi_step) composes the PLAIN step
            self._py_step = step
        return self._jit_over_state(step, 2 if collect_stats else 1)

    def _build_multi_step(self, k):
        """k steps chained inside ONE compiled program via lax.scan.

        Chaining steps in-program removes the per-step host dispatch
        and lets XLA keep params/state resident between iterations.  lr and t are
        (k,) arrays (the host-side lr_scheduler is evaluated per step up
        front), so schedules behave exactly as in :meth:`step`.
        """
        import jax
        from jax import lax

        step = self._py_step

        def multi(params, opt_state, aux, batch, key, lrs, ts):
            def body(carry, xs):
                p, s, a, ky = carry
                lr, t = xs
                ky, sub = jax.random.split(ky)
                p, s, a, loss = step(p, s, a, batch, sub, lr, t)
                return (p, s, a, ky), loss

            (params, opt_state, aux, _), losses = lax.scan(
                body, (params, opt_state, aux, key), (lrs, ts), length=k)
            return params, opt_state, aux, losses

        multi.__name__ = multi.__qualname__ = "mxtpu_train_chain"
        return self._jit_over_state(multi)

    # ------------------------------------------------------------------ api
    def _maybe_rebuild(self):
        """Recompile when optimizer hyperparameters changed.

        The reference Optimizer reads lr_mult/wd_mult/rescale on every
        update; they are baked into the compiled step here, so post-build
        set_lr_mult()/set_wd_mult()/rescale changes are honored by
        recompiling (and reallocating slots if the rule changed)."""
        import jax
        opt = self.optimizer
        if self._hyper_state() == self._hyper_snapshot:
            return
        self._rescale = opt.rescale_grad
        old_slots = self._n_slots
        self._n_slots, self._update_rule = _make_update_rule(opt)
        if self._n_slots != old_slots:
            with self.mesh:
                self.opt_state = self._device_zero_slots()
        self._step_fn = self._build_step()
        self._stats_step_fn = None
        self._scan_fns = {}
        self._aot_exes = {}
        # retire the old costdb dispatch scope (see __init__): the new
        # fns must warm up as compiles, and the old counters are pruned
        from ..telemetry import costdb as _costdb
        _costdb.drop_scope(self._costdb_scope)
        self._costdb_scope = _costdb.next_scope()
        self._hyper_snapshot = self._hyper_state()

    def _cast_batch(self, batch):
        """Data inputs follow the compute dtype (bf16 training); labels
        keep their own dtype.  No layout work happens on the host — the
        NCHW->NHWC transpose runs on device in :meth:`put_batch` (a host
        transpose of a full image batch costs hundreds of ms on small
        hosts and doubles peak host memory)."""
        out = {}
        for k, v in batch.items():
            v = np.asarray(v)
            if "label" not in k and v.dtype.kind == "f" \
                    and k not in self._int_inputs:
                # integer-semantic inputs (token ids feeding Embedding/
                # take) stay float32: exact for ids < 2^24, while bf16
                # rounds ids above 256
                v = v.astype(self.dtype)
            out[k] = v
        return out

    def put_batch(self, batch):
        """Stage a host batch (reference NCHW convention) onto the mesh
        as sharded device arrays in the trainer's active layout.  Use
        with :meth:`step` to overlap host IO with compute, or to reuse a
        batch without re-transfer.  Under layout='NHWC' the image
        transpose happens ON DEVICE after the (layout-untouched) host
        bytes land — XLA transposes in microseconds what numpy pays
        hundreds of ms for.

        On a process-spanning mesh each process passes its OWN
        contiguous shard of the global batch (dim 0 split across the
        processes of the 'data' axis, reference num_parts/part_index
        slicing); the staged result is one global array.

        Telemetry: a ``trainer.put_batch`` span with the attributes
        ``host_bytes`` (what leaves the host, after the cast) and
        ``inputs``."""
        with _span("trainer.put_batch", category="trainer") as sp:
            host = self._cast_batch(batch)
            sp.attrs = {"host_bytes": sum(int(v.nbytes)
                                          for v in host.values()),
                        "inputs": len(host)}
            return self._put_cast_batch(host)

    def _put_cast_batch(self, host):
        import numpy as _np
        out = {}
        normalize = (self._input_mean is not None
                     or self._input_std is not None)
        for k, v in host.items():
            # batch dim may differ (partial tail batches): compare the
            # feature dims only to detect a host-NCHW image batch.  A
            # batch whose dims also match the NCHW reading (C==H==W) is
            # ambiguous and follows the documented host-NCHW convention
            feat = tuple(v.shape[1:])
            needs_transpose = (
                k in self._nhwc_inputs and v.ndim == 4
                and (feat != tuple(self._input_shapes[k][1:])
                     or feat == tuple(self._host_input_shapes[k][1:])))
            # uint8 inputs are normalized on device ONLY when the
            # trainer was configured for it; otherwise they reach the
            # graph unchanged (integer data, in-graph normalization)
            is_u8 = (v.dtype == _np.uint8 and k in self._data_names
                     and normalize)
            if needs_transpose or is_u8:
                fn, sharding = self._get_stage_fn(k, needs_transpose,
                                                  is_u8, v.ndim)
                out[k] = fn(self._stage_batch_value(v, sharding))
            else:
                out[k] = self._stage_batch_value(v,
                                                 self._batch_sharding[k])
        return out

    def _stage_batch_value(self, v, sharding):
        """One batch input onto the mesh: device_put single-process,
        per-process-shard assembly on a process-spanning mesh.  The
        global shape follows the LOCAL shard's dims (scaled by the
        process count along sharded axes), so partial tail batches work
        multi-host too — every process must pass the same-sized shard."""
        import jax
        if not self._multiproc:
            return jax.device_put(v, sharding)
        from . import multihost
        return multihost.stage_local(
            sharding, v, multihost.scale_local_shape(sharding, v.shape))

    def _get_stage_fn(self, name, needs_transpose, is_u8, ndim):
        """Jitted on-device staging program for one input: NCHW->NHWC
        transpose and/or uint8 -> (x - mean)/std -> compute dtype."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        key = (name, needs_transpose, is_u8, ndim)
        hit = self._stage_fns.get(key)
        if hit is not None:
            return hit
        # the RAW host layout lands batch-sharded; the staged result
        # takes the input's full batch sharding (seq-parallel inputs
        # keep their dim-1 'model' shard)
        in_sharding = NamedSharding(self.mesh, P("data"))
        out_sharding = self._batch_sharding[name]
        compute_dtype = jnp.dtype(self.dtype)
        mean, std = self._input_mean, self._input_std
        ch_axis = -1 if (self._layout == "NHWC" or needs_transpose) else 1

        def reshape_stat(s, x_ndim):
            a = jnp.asarray(s, jnp.float32)
            if a.ndim == 0:
                return a
            shape = [1] * x_ndim
            shape[ch_axis] = a.shape[0]
            return a.reshape(shape)

        def stage(a):
            if needs_transpose:
                a = jnp.transpose(a, (0, 2, 3, 1))
            if is_u8:
                x = a.astype(jnp.float32)
                if mean is not None:
                    x = x - reshape_stat(mean, x.ndim)
                if std is not None:
                    x = x / reshape_stat(std, x.ndim)
                return x.astype(compute_dtype)
            return a

        fn = jax.jit(stage, out_shardings=out_sharding)
        self._stage_fns[key] = (fn, in_sharding)
        return fn, in_sharding

    def _stage_accounted(self, host_batch):
        """Stage one host batch, charging the wall to the ioview
        ``device_stage`` pipeline stage (the H2D half of the data
        plane).  Unlike :meth:`_stage_timed` this runs OUTSIDE a step,
        so nothing lands in the step's ``input_wait`` segment — that
        is the point of prefetched staging."""
        import time as _time
        from ..telemetry import ioview as _iov
        t0 = _time.perf_counter()
        dev = self.put_batch(host_batch)
        _iov.account("device_stage", _time.perf_counter() - t0,
                     items=1,
                     nbytes=sum(getattr(v, "nbytes", 0)
                                for v in host_batch.values()))
        return dev

    def staged_batches(self, batches):
        """Double-buffered host->device staging over an iterable of
        HOST batches: yields staged device batches (feedable straight
        to :meth:`step`), dispatching batch N+1's transfer right after
        the caller resumes from batch N — i.e. while batch N's step is
        still in flight on an async backend, so the H2D transfer
        overlaps the current step's compute instead of serializing
        into its ``input_wait`` segment.

        The thread-free sibling of :class:`~mxnet_tpu.io.
        DevicePrefetchIter` (which adds a worker thread and a depth-N
        queue on top of the same staging seam; the ioview
        ``device_stage`` metric times both).  Use when the host batches
        are already cheap to produce (synthetic/benchmark loops)::

            for dev_batch in trainer.staged_batches(host_batches):
                loss = trainer.step(dev_batch)
        """
        it = iter(batches)
        try:
            nxt = self._stage_accounted(next(it))
        except StopIteration:
            return
        for host in it:
            cur, nxt = nxt, None
            yield cur
            # the caller just dispatched its step on `cur`; this
            # transfer rides under that still-running step
            nxt = self._stage_accounted(host)
        yield nxt

    def step(self, batch):
        """One fused training step.  ``batch``: dict name -> host array
        with GLOBAL batch dim (or a dict from :meth:`put_batch`).
        Returns the (device) loss scalar.

        Telemetry: each call is a ``trainer.step`` span over the whole
        method, whose children say where a dispatch's host time goes —
        ``trainer.step.prepare`` (everything before the executable is
        called), ``<program>.launch`` (the executable call alone, up to
        its return), ``trainer.step.account`` (everything after, with
        ``<program>.sync`` inside it when the cost database blocks on
        the output and ``telemetry.step_end``) — and one ``step_end``
        record (step time is host-side dispatch+staging — on an async
        backend the device may still be computing).  The
        step is split into compute / input-wait / collective-wait
        segments (``mxtpu_step_segment_seconds``, telemetry.distview):
        input-wait is the host->device staging time, and on a
        process-spanning mesh a pre-collective timestamp barrier
        measures how long this rank waited for its slowest peer
        (``mxtpu_collective_wait_seconds`` / skew gauge) — the
        straggler-attribution signal tools/run_top.py aggregates.  The
        first call registers the compiled step's memory plan
        (``mxtpu_memory_plan_bytes{program="trainer.step"}``) and
        budget-checks it before dispatch; a backend RESOURCE_EXHAUSTED
        is re-raised with the plan + live-bytes forensics attached, and
        any MXNetError dumps the flight recorder's black box
        (MXNET_TPU_FLIGHT_DIR)."""
        import time as _time
        from .. import telemetry
        from ..telemetry import flight as _flight, memory as _tmem
        from ..telemetry import tracing as _tracing
        step_no = self._step_count + 1
        # one distributed trace per step: the existing distview
        # segments become its child spans, and flight events recorded
        # inside (step_begin, any error) carry the trace id
        tr = _tracing.start_trace("trainer.step", attrs={"step": step_no})
        with tr, _span("trainer.step", category="trainer", step=step_no), \
                _flight.crash_guard("trainer.step"), \
                _tmem.annotate_oom("trainer.step"):
            step_ctx = _tracing.current()
            with _span("trainer.step.prepare", category="trainer"):
                t0 = _time.perf_counter()
                _flight.record("step_begin", program="trainer.step",
                               step=step_no)
                self._seg = {"input_s": 0.0, "collective_s": 0.0,
                             "skew": None}
                program, fn, args = self._prepare_step(batch)
                obs = self._begin_dispatch(program, fn)
            out = self._launch(program, fn, args, obs)
            with _span("trainer.step.account", category="trainer"):
                self._end_dispatch(obs, out, args)
                loss = self._finish_step(program, out, args)
                self._publish_moe_loads(obs)
                # the donated state's handles (some hundreds of arrays)
                # die here, inside the span, not as the method returns
                del args, out
                total = _time.perf_counter() - t0
                if step_ctx is not None:
                    self._record_segment_spans(
                        step_ctx, _tracing.epoch_of(t0), total)
                telemetry.step_end(samples=self._batch_samples(batch),
                                   step_time=total,
                                   extra=self._segments_extra(total))
        return loss

    def _record_segment_spans(self, ctx, ts0, total_s):
        """The step's segment split as trace spans under the
        ``trainer.step`` span (``ctx`` is that span's own context, so
        these land as its children): input_wait, compute (the
        remainder, distview's definition), collective_wait — laid out
        sequentially from ``ts0`` so the waterfall reads like the
        step."""
        from ..telemetry import tracing as _tracing
        seg = self._seg
        inp = max(0.0, float(seg["input_s"]))
        coll = max(0.0, float(seg["collective_s"]))
        comp = max(0.0, float(total_s) - inp - coll)
        _tracing.record_span(ctx, "step.input_wait", ts0, inp)
        _tracing.record_span(ctx, "step.compute", ts0 + inp, comp)
        attrs = None
        sk = seg.get("skew")
        if sk is not None:
            attrs = {"skew_s": round(sk["skew_s"], 6),
                     "slowest_rank": sk["slowest_rank"]}
        _tracing.record_span(ctx, "step.collective_wait",
                             ts0 + inp + comp, coll, attrs=attrs)

    def _segments_extra(self, total_s, count=1):
        """The straggler-attribution fields for this step's JSONL
        record: the segment split (recorded into
        ``mxtpu_step_segment_seconds`` as a side effect) plus the
        measured skew when the pre-collective barrier ran."""
        from ..telemetry import distview as _dv
        seg = self._seg
        extra = {"segments": _dv.record_step_segments(
            total_s, input_s=seg["input_s"],
            collective_s=seg["collective_s"], count=count)}
        sk = seg["skew"]
        if sk is not None:
            extra["skew_s"] = round(sk["skew_s"], 6)
            extra["slowest_rank"] = sk["slowest_rank"]
        num = seg.get("numerics")
        if num is not None:
            import math as _math
            from ..telemetry import numerics as _numerics
            # the compact numerics pair rides the step's JSONL record so
            # the run aggregator can surface cross-rank grad-norm skew
            # and digest drift next to the time skew (tools/run_top.py);
            # a non-finite grad norm stays out (the nonfinite rule
            # already carries it, and the step-log must stay strict JSON)
            gn = num.get("grad_norm")
            if isinstance(gn, float) and _math.isfinite(gn):
                extra["grad_norm"] = gn
            if num.get("digest") is not None:
                extra["digest"] = num["digest"]
            if _numerics.ledger_path() is None:
                # no dedicated ledger file: the step-log itself is the
                # ledger — the full record rides under "numerics", so
                # tools/numdiff.py accepts MXNET_TPU_TELEMETRY_JSONL
                # directly (read_ledger's inline carrier form)
                extra["numerics"] = _numerics.json_safe(
                    {k: v for k, v in num.items() if k != "anomalies"})
        return extra

    def _batch_samples(self, batch):
        try:
            first = next(iter(batch.values()))
            return int(first.shape[0])
        except (StopIteration, AttributeError, IndexError, TypeError):
            return 0

    def _begin_dispatch(self, program, fn):
        """Open the cost database's observation of one dispatch
        (telemetry.costdb): the fused blocks this program's compile
        traced bind to it, and sampled dispatches record synchronized
        wall time + flops/bytes + mesh shape as persistent MFU/roofline
        records (:meth:`cost_summary`).  None on a process-spanning
        mesh, whose dispatches are never timed — a sampled
        ``block_until_ready`` on one rank would skew the fleet."""
        if self._multiproc:
            return None
        from ..telemetry import costdb as _costdb
        return _costdb.begin_dispatch(
            program, key=(self._costdb_scope, id(fn)))

    def _launch(self, program, fn, args, obs):
        """Call the program: through its AOT executable, with the
        memory plan registered + budget-checked on first use
        (telemetry.memory.dispatch_planned, which holds the
        ``<program>.launch`` span round the executable call and the
        ``program.lower``/``program.compile`` spans of the first use).
        Process-spanning meshes keep the plain jit dispatch (AOT
        example staging is a per-process choice)."""
        from ..telemetry import costdb as _costdb, memory as _tmem
        if obs is None:
            # bind-only: the compile's traced block signatures must not
            # dangle (they would attach to the next single-proc program
            # dispatched in this process)
            try:
                with _span(program + ".launch", category="trainer"):
                    return fn(*args)
            finally:
                _costdb.bind_pending(
                    program, key=(self._costdb_scope, id(fn)))
        first = bool(self._moe_loads) and \
            (program, id(fn)) not in self._aot_exes
        try:
            out = _tmem.dispatch_planned(self._aot_exes, program, fn,
                                         args)
            if first:
                # what the expert layers' products became in the
                # program just compiled, read from its text
                from . import moe as _moe
                with _span("program.plan", program=program):
                    _moe.note_compiled(
                        self._aot_exes.get((program, id(fn))))
            return out
        except BaseException:  # mxlint: allow-broad-except(re-raised unchanged — the handler only closes the costdb observation bind-only, so the compile's traced signatures cannot dangle and attach to the next program dispatched)
            _costdb.end_dispatch(obs, failed=True)
            raise

    def _end_dispatch(self, obs, out, args, steps=1):
        """Close the observation :meth:`_begin_dispatch` opened.
        ``steps``: inner train steps the one dispatch executed
        (``run_steps`` passes its chain length so the per-step wall
        meets the signatures' per-step flops)."""
        if obs is not None:
            from ..telemetry import costdb as _costdb
            _costdb.end_dispatch(obs, out=out, args=args,
                                 mesh=self._mesh_axis_sizes(),
                                 steps=steps)

    def _publish_moe_loads(self, obs):
        """The expert layers' loads of the last step as gauges
        (``parallel.moe.publish_load``), on the dispatches the cost
        database has already blocked on and on no other: reading the
        few floats then waits for nothing, and no dispatch gains a host
        sync."""
        from ..telemetry import costdb as _costdb
        if not self._moe_loads or not _costdb.sampled(obs):
            return
        from . import moe as _moe
        _moe.publish_load({layer: np.asarray(self.aux[aux])
                           for aux, layer in self._moe_loads.items()})

    def _mesh_axis_sizes(self):
        """{axis name: size} of the trainer's mesh — part of every
        costdb record key (the same block costs differently on a
        different mesh)."""
        try:
            return {str(k): int(v)
                    for k, v in dict(self.mesh.shape).items()}
        except (AttributeError, TypeError, ValueError):
            return None

    def _stage_timed(self, batch):
        """Stage a host batch, charging the wall time to the step's
        ``input_wait`` segment (already-staged device batches cost 0)
        and to the ioview ``device_stage`` pipeline stage (the H2D half
        of the data plane; a DevicePrefetchIter staging on its worker
        thread accounts there instead — the two paths are disjoint)."""
        import time as _time
        import jax
        from ..telemetry import ioview as _iov
        first = next(iter(batch.values()))
        if isinstance(first, jax.Array):
            return batch
        t0 = _time.perf_counter()
        dev_batch = self.put_batch(batch)
        dt = _time.perf_counter() - t0
        self._seg["input_s"] += dt
        _iov.account("device_stage", dt, items=1,
                     nbytes=sum(getattr(v, "nbytes", 0)
                                for v in batch.values()))
        return dev_batch

    def _measure_collective_entry(self, site):
        """On a process-spanning mesh, run the distview timestamp
        barrier just before dispatching the collective-bearing program:
        the measured wait/skew land in this step's segments."""
        if not self._multiproc:
            return
        from ..telemetry import distview as _dv
        info = _dv.pre_collective_barrier(site)
        if info is not None:
            self._seg["collective_s"] += info["wait_s"]
            self._seg["skew"] = info

    def _prepare_step(self, batch):
        """Everything of one :meth:`step` before the executable is
        called; returns ``(program, fn, args)``."""
        import jax
        import jax.numpy as jnp
        from .. import resilience
        resilience.fault_point("trainer.step")
        self._key, sub = jax.random.split(self._key)
        dev_batch = self._stage_timed(batch)
        opt = self.optimizer
        self._maybe_rebuild()
        self._step_count += 1
        # num_update honors begin_num_update so lr schedule AND adam bias
        # correction continue consistently across resume
        opt.num_update = max(opt.num_update, opt.begin_num_update
                             + self._step_count)
        lr = (opt.lr_scheduler(opt.num_update)
              if opt.lr_scheduler is not None else opt.lr)
        program, fn = "trainer.step", self._step_fn
        if self._numerics_sampled():
            # the numerics.nonfinite seam is evaluated ONLY on sampled
            # steps: an injected NaN must land where detection runs —
            # poisoning an unsampled step would corrupt the run with
            # zero anomaly signal
            dev_batch = self._maybe_poison_batch(dev_batch)
            if self._stats_step_fn is None:
                self._stats_step_fn = self._build_step(collect_stats=True)
            program, fn = "trainer.step_stats", self._stats_step_fn
        args = (self.params, self.opt_state, self.aux, dev_batch, sub,
                jnp.float32(lr), jnp.float32(opt.num_update))
        self._measure_collective_entry("trainer.step")
        return program, fn, args

    def _finish_step(self, program, out, args):
        """Take one :meth:`step`'s outputs; returns the loss."""
        if program != "trainer.step_stats":
            self.params, self.opt_state, self.aux, loss = out
            return loss
        from ..telemetry import numerics as _numerics
        self.params, self.opt_state, self.aux, loss, stats = out
        dev_batch, sub = args[3], args[4]
        # the stats fetch is the ONLY host sync numerics adds, and
        # only on sampled steps; every rank samples the same step
        # numbers, so a multi-process fleet syncs symmetrically
        payload = _numerics.process_step(
            stats, step=self._resume_epoch + self._step_count,
            program="trainer.step",
            provenance_fn=lambda: self._numerics_provenance(
                dev_batch, sub),
            # instance-unique EWMA scope (rotated on rebuild): two
            # trainers in one process must not share a grad_spike
            # baseline — model A's small norms would false-trip B
            scope=("trainer.step", self._costdb_scope))
        if payload is not None:
            self._seg["numerics"] = payload
        return loss

    def _numerics_sampled(self):
        """Whether THIS step dispatches the numerics stats variant.
        The cadence is phased on the GLOBAL step (resume epoch + local
        count — the number the ledger records carry), so a resumed run
        samples the same step numbers as a from-scratch one and the
        pre- vs post-resume ledgers stay numdiff-comparable."""
        from ..telemetry import numerics as _numerics
        return _numerics.sampled(self._resume_epoch + self._step_count)

    def _maybe_poison_batch(self, dev_batch):
        """The ``numerics.nonfinite`` chaos seam: when armed
        (MXNET_TPU_FAULTS), the injected hazard is a NUMERIC one — the
        first float data input is poisoned with NaNs instead of raising,
        so the detection/provenance path is what gets exercised
        (tools/ci_check.py stage 11).  Called only on SAMPLED steps
        (see ``_prepare_step``), so the injection is always detectable."""
        from .. import resilience
        try:
            resilience.fault_point("numerics.nonfinite")
            return dev_batch
        except resilience.FaultInjected:
            import jax.numpy as jnp
            import numpy as _np
            out = dict(dev_batch)
            for name in self._data_names:
                v = out[name]
                if _np.dtype(v.dtype).kind == "f" \
                        and name not in self._int_inputs:
                    out[name] = v * jnp.asarray(float("nan"), v.dtype)
                    return out
            # no float data input to poison: fall back to a param (the
            # provenance then names its first consumer)
            name = self._param_names[0]
            self.params = dict(self.params)
            self.params[name] = self.params[name] * jnp.float32(
                float("nan"))
            return dev_batch

    def _numerics_provenance(self, dev_batch, key):
        """NaN/Inf provenance: replay the step's forward EAGERLY (no
        jit) through ``eval_graph``'s per-node monitor hook — the
        executor's ``_forward_monitored`` path — and name the FIRST
        node producing a non-finite output.  Host-syncs per node, which
        is fine: it runs once, on a step already known to be anomalous.

        The replay binds the CURRENT (post-update) params — the step's
        input params were donated — so when the corruption entered
        through the update itself, the named node is the first to
        CONSUME a non-finite param rather than the backward op that
        produced it; either way it localizes the blast radius.  Batch-
        borne NaNs (the seeded-injection case) replay exactly."""
        import jax
        import jax.numpy as jnp

        found = {}
        order = [0]

        def mon(name, val):
            order[0] += 1
            if found:
                return
            try:
                bad = int(jax.device_get(jnp.sum(
                    ~jnp.isfinite(jnp.asarray(val).astype(jnp.float32)))))
            except (TypeError, ValueError):
                return
            if bad:
                found.update(node=str(name), nonfinite=bad,
                             position=order[0])

        compute_dtype = jnp.dtype(self.dtype)
        p = {k: v.astype(compute_dtype) for k, v in self.params.items()}
        bsz = next(iter(dev_batch.values())).shape[0]
        with image_layout(self._layout):
            var_values = self._node_value_map(p, dev_batch, self.aux)
            eval_graph(self._topo, self.symbol._entries, var_values,
                       is_train=True, key=key, monitor=mon,
                       batch_size=bsz)
        return dict(found) if found else None

    def run_steps(self, batch, num_steps):
        """``num_steps`` fused training steps in ONE device program.

        The scan-chained equivalent of calling :meth:`step` in a loop on
        the same batch: per-step host dispatch disappears and XLA
        keeps the donated state resident
        between iterations.  lr schedules advance per inner step exactly
        as in :meth:`step` (the scheduler is evaluated on host into a
        (num_steps,) lr array).  Returns the per-step loss array.

        Use for throughput-critical loops where the batch is staged once
        (benchmarks, synthetic-data soak runs); for distinct batches per
        step, stage the next batch with :meth:`put_batch` while the chip
        runs (double buffering) and call :meth:`step` per batch.

        Telemetry: a ``trainer.run_steps`` span over the whole method
        with the children of :meth:`step`'s (``.prepare``, ``.launch``,
        ``.account`` with ``.sync`` inside it when the cost database
        blocks on the output).
        """
        import time as _time
        from .. import telemetry
        from ..telemetry import flight as _flight, memory as _tmem
        program = "trainer.run_steps"
        with _span(program, category="trainer", steps=num_steps), \
                _flight.crash_guard(program), _tmem.annotate_oom(program):
            with _span("trainer.run_steps.prepare", category="trainer"):
                t0 = _time.perf_counter()
                _flight.record("step_begin", program=program,
                               step=self._step_count + 1, count=num_steps)
                self._seg = {"input_s": 0.0, "collective_s": 0.0,
                             "skew": None}
                fn, args = self._prepare_run_steps(batch, num_steps)
                obs = self._begin_dispatch(program, fn)
            out = self._launch(program, fn, args, obs)
            with _span("trainer.run_steps.account", category="trainer"):
                self._end_dispatch(obs, out, args, steps=num_steps)
                self.params, self.opt_state, self.aux, losses = out
                self._publish_moe_loads(obs)
                # the donated state's handles (some hundreds of arrays)
                # die here, inside the span, not as the method returns
                del args, out
                # the scan chain IS num_steps full optimizer updates
                # observed once from the host: counters/percentiles
                # advance per inner step, but the JSONL gets ONE record
                # (count=num_steps) — per-record snapshots of an opaque
                # chain would be byte-identical
                total = _time.perf_counter() - t0
                telemetry.step_end(
                    samples=self._batch_samples(batch),
                    step_time=total / max(1, num_steps),
                    count=num_steps,
                    extra=self._segments_extra(total, count=num_steps))
        return losses

    def health(self):
        """This rank's SLO health verdict (``mxtpu-health/1`` dict —
        see ``telemetry.slo``).  The training-run rules (step-time
        regression vs the rolling baseline, collective-wait share,
        starved-input share, the step heartbeat, numerics/io
        passthrough) are evaluated on the ``step_end`` cadence every
        :meth:`step`/:meth:`run_steps` already drives, so this is a
        read, not an evaluation."""
        from ..telemetry import slo
        return slo.health()

    def _prepare_run_steps(self, batch, num_steps):
        """Everything of one :meth:`run_steps` before the executable is
        called; returns ``(fn, args)``."""
        import jax
        import jax.numpy as jnp
        import numpy as _np
        from ..telemetry import numerics as _numerics

        if _numerics.enabled() and \
                not getattr(self, "_numerics_scan_warned", False):
            # the scan chain is one opaque program; numerics samples the
            # step() path only — say so ONCE instead of silently leaving
            # the ledger empty while the knob claims every Nth step
            self._numerics_scan_warned = True
            import logging
            logging.warning(
                "MXNET_TPU_NUMERICS_EVERY is set but run_steps chains "
                "are not sampled (the lax.scan chain is one opaque "
                "program); use step() where numerics coverage matters")
        dev_batch = self._stage_timed(batch)
        self._maybe_rebuild()
        fn = self._scan_fns.get(num_steps)
        if fn is None:
            fn = self._build_multi_step(num_steps)
            self._scan_fns[num_steps] = fn
        opt = self.optimizer
        ts, lrs = [], []
        for _ in range(num_steps):
            self._step_count += 1
            opt.num_update = max(opt.num_update, opt.begin_num_update
                                 + self._step_count)
            ts.append(opt.num_update)
            lrs.append(opt.lr_scheduler(opt.num_update)
                       if opt.lr_scheduler is not None else opt.lr)
        self._key, sub = jax.random.split(self._key)
        args = (self.params, self.opt_state, self.aux, dev_batch, sub,
                jnp.asarray(_np.asarray(lrs, _np.float32)),
                jnp.asarray(_np.asarray(ts, _np.float32)))
        self._measure_collective_entry("trainer.run_steps")
        return fn, args

    def forward(self, batch, is_train=False):
        """Jitted inference forward returning head arrays."""
        import jax
        if self._fwd_fn is None:
            topo, entries = self._topo, self.symbol._entries
            layout = self._layout
            import jax.numpy as jnp
            compute_dtype = jnp.dtype(self.dtype)

            def fwd(params, aux, batch):
                from ..ops.fused import block_fusion
                from ..analysis.fusion import plan_decisions
                from .sequence import sequence_parallel as seq_ctx
                from .mesh import kernel_mesh
                p = {k: v.astype(compute_dtype)
                     for k, v in params.items()}
                bsz = next(iter(batch.values())).shape[0]
                # loss heads still take label inputs at inference; their
                # forward ignores the values, so zeros stand in
                full = dict(batch)
                for n, s in self._label_shapes.items():
                    if n not in full:
                        full[n] = jnp.zeros((bsz,) + tuple(s[1:]),
                                            jnp.float32)
                # the fused blocks keep eval-mode BN semantics inside
                # the region, so inference lowers through the same plan
                with image_layout(layout), kernel_mesh(self.mesh), \
                        block_fusion(self._fuse_blocks), \
                        plan_decisions(self._plan_decisions), \
                        seq_ctx(self.mesh if self._seq_parallel
                                else None):
                    var_values = self._node_value_map(p, full, aux)
                    heads, _ = eval_graph(topo, entries, var_values,
                                          is_train=False, key=None,
                                          batch_size=bsz)
                return heads
            self._fwd_fn = jax.jit(fwd, in_shardings=(
                self._param_sharding, self._aux_sharding,
                {k: self._batch_sharding[k] for k in self._data_names}))
        first = next(iter(batch.values()))
        # inference takes data inputs only — drop labels if supplied
        batch = {k: v for k, v in batch.items() if k in self._data_names}
        if isinstance(first, jax.Array):
            dev_batch = batch  # already staged via put_batch
        else:
            dev_batch = self.put_batch(batch)
        return self._fwd_fn(self.params, self.aux, dev_batch)


    def fusion_summary(self):
        """Summary of the most recent block-fusion plan traced in this
        process (blocks fused by kind, relayouts eliminated, fallback
        reasons) — None before the first fused compile or when
        ``fuse_blocks`` is off.  See docs/api/fusion.md."""
        from ..analysis import fusion as _fusion
        return _fusion.last_plan_summary() if self._fuse_blocks else None

    def cost_summary(self, top=5):
        """Roll-up of the process cost database
        (:mod:`mxnet_tpu.telemetry.costdb`): record counts, measured
        per-program wall/MFU, and the ``top`` worst-MFU fused blocks
        with their roofline bound — the autotuner targeting signal.
        Sampled collection runs through this trainer's dispatches
        (``MXNET_TPU_COSTDB_SAMPLE``); ``MXNET_TPU_COSTDB`` persists
        the records across runs.  See docs/api/telemetry.md."""
        from ..telemetry import costdb as _costdb
        return _costdb.summary(top=top)

    # ------------------------------------------------------- checkpoints
    def mesh_descriptor(self):
        """JSON-able descriptor of this trainer's mesh + per-param
        partition layout (``parallel/reshard.py``): axis sizes, the
        saving world size, and each param's spec in the reference
        (OIHW) dim convention.  Recorded in the checkpoint manifest's
        ``meta["mesh"]`` (schema v2) so a later load can detect a mesh
        reshape; see :meth:`load_checkpoint`."""
        from . import multihost, reshard as _reshard
        specs = _reshard.specs_from_tp_rules(
            self.tp_rules,
            {n: self._arg_shapes[n] for n in self._param_names})
        return _reshard.mesh_descriptor(self.mesh, specs=specs,
                                        world=multihost.world_size())

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """Write reference-format checkpoint files from the sharded
        state: ``prefix-symbol.json`` + ``prefix-%04d.params`` (arg:/aux:
        name prefixes — Module/FeedForward can load these) and optionally
        ``prefix-%04d.states`` holding the fused optimizer slots + the
        update counter.  NOTE: the .states layout is the fused-path's own
        (name-keyed slot arrays); Module's .states files are pickled
        per-index Updater dicts and the two are NOT interchangeable —
        params/aux files are.

        Multi-host: call on EVERY process (process-sharded state is
        all-gathered collectively); rank 0 writes the files and a
        barrier orders the write before any rank's subsequent load —
        the reference's rank-0 checkpointing in dist training
        (example/image-classification/train_model.py saves on
        kv.rank==0 only).  ``prefix`` must live on storage every host
        can read (NFS/GCS): load_checkpoint has all ranks read the
        files rank 0 wrote.
        """
        import jax
        import numpy as _np
        from .. import ndarray as _nd
        from . import multihost

        from .. import resilience
        # gather-on-save streams ONE array at a time off the mesh (the
        # host dict accumulates numpy copies; device memory never holds
        # a second full param).  The reshard.gather seam fires per
        # array — the chaos window of an elastic gather.
        host = {}
        for k, v in self.params.items():
            resilience.fault_point("reshard.gather")
            host["arg:%s" % k] = multihost.gather_to_host(v)
        for k, v in self.aux.items():
            resilience.fault_point("reshard.gather")
            host["aux:%s" % k] = multihost.gather_to_host(v)
        st = None
        if save_optimizer_states:
            st = {"meta:num_update": _np.array(
                [self.optimizer.begin_num_update + self._step_count],
                _np.int64)}
            for k, slots in self.opt_state.items():
                for i, sl in enumerate(slots):
                    resilience.fault_point("reshard.gather")
                    st["slot%d:%s" % (i, k)] = \
                        multihost.gather_to_host(sl)
        if not self._multiproc or jax.process_index() == 0:
            resilience.atomic_write("%s-symbol.json" % prefix,
                                    self.symbol.save)
            param_name = "%s-%04d.params" % (prefix, epoch)
            resilience.atomic_write(
                param_name,
                lambda tmp: _nd.save(
                    tmp, {k: _nd.array(v) for k, v in host.items()}),
                fault_site="checkpoint.save")
            files = [param_name]
            arrays = dict(host)
            if st is not None:
                states_name = "%s-%04d.states" % (prefix, epoch)
                resilience.atomic_write(
                    states_name,
                    lambda tmp: _nd.save(
                        tmp, {k: _nd.array(v) for k, v in st.items()}))
                files.append(states_name)
                arrays.update(st)
            # the manifest commits the checkpoint: written LAST (itself
            # atomically), so a crash anywhere above leaves no epoch a
            # verified loader would pick up.  meta["mesh"] (schema v2)
            # records the saving mesh so a later load on a different
            # shape reshards instead of guessing (docs/api/reshard.md);
            # meta["data_position"] is the ADVISORY iterator position of
            # the run's tracked data iterator (telemetry.ioview) — the
            # recorded half of mid-epoch resume (restore comes later)
            meta = {"mesh": self.mesh_descriptor()}
            from ..telemetry import ioview as _iov
            from .. import io_resume as _ior
            pos = _iov.current_position()
            if pos is not None:
                meta["data_position"] = pos
            # data_state (mxnet_tpu.io_resume) is the RESTORED half:
            # the tracked iterator's durable state, consumed by
            # load_checkpoint -> restore_data_iter/fit.  Rank 0's
            # iterator describes the fleet under the lockstep SPMD
            # contract (ledger states remap per rank on load)
            entry = _ior.data_state_entry()
            if entry is not None:
                meta["data_state"] = entry
            resilience.write_manifest(prefix, epoch, files, arrays=arrays,
                                      meta=meta)
        if self._multiproc:
            multihost.process_barrier("sharded_trainer_ckpt_save")

    def load_checkpoint(self, prefix, epoch, load_optimizer_states=False):
        """Restore params/aux (and fused optimizer slots) saved by
        :meth:`save_checkpoint`.  Params/aux files are Module-format, so
        Module-trained checkpoints resume on the fused path; optimizer
        .states files are fused-path-specific (see save_checkpoint).
        Multi-host: every rank reads the files (``prefix`` must be on
        shared storage) and stages its own shards.
        Raises on any name mismatch — a silent partial load would look
        like a resume while actually restarting from random init.

        Elastic (docs/api/reshard.md): when the manifest's mesh
        descriptor (schema v2) names a different device grid than this
        trainer's mesh, the load RESHARDS instead of raising — every
        array is validated against the target layout up front
        (``reshard.plan_reshard``), then shard-on-load stages ONE array
        at a time onto the new mesh (the ``reshard.scatter`` seam fires
        per array) into a staged copy that only replaces the live state
        once every array landed, so a mid-reshard failure degrades to a
        descriptive MXNetError with the old-mesh state untouched.  A
        world-size change additionally fires the ``elastic.rejoin``
        seam and records ``rank_join``/``rank_leave`` events.  v1
        manifests (no descriptor) keep the legacy behavior."""
        import time as _time
        import jax
        import numpy as _np
        from .. import ndarray as _nd
        from .. import resilience
        from . import reshard as _reshard

        resilience.fault_point("checkpoint.load")
        param_name = "%s-%04d.params" % (prefix, epoch)
        # manifest CRC verification first: a truncated/corrupt file must
        # surface as a named MXNetError, not an unpickle traceback
        manifest = resilience.verify_manifest(prefix, epoch)
        try:
            loaded = _nd.load(param_name)
        except FileNotFoundError as e:
            raise MXNetError(
                "checkpoint params file %r is missing for epoch %d"
                % (param_name, epoch)) from e
        except (ValueError, EOFError, _struct.error) as e:
            raise MXNetError("checkpoint params file %r is corrupt: %s"
                             % (param_name, e)) from e
        file_args = {k.split(":", 1)[1]: v for k, v in loaded.items()
                     if k.startswith("arg:")}
        file_aux = {k.split(":", 1)[1]: v for k, v in loaded.items()
                    if k.startswith("aux:")}
        missing = (set(self.params) - set(file_args)) |             (set(self.aux) - set(file_aux))
        unexpected = (set(file_args) - set(self.params)) |             (set(file_aux) - set(self.aux))
        if missing or unexpected:
            raise MXNetError(
                "checkpoint/model mismatch: missing %s, unexpected %s"
                % (sorted(missing), sorted(unexpected)))
        # ---- elastic detection: the manifest's mesh descriptor vs the
        # mesh this trainer was built on.  The plan validates EVERY
        # array against the target layout before any state moves.
        saved_desc = _reshard.manifest_mesh(manifest)
        cur_desc = self.mesh_descriptor()
        reshaping = saved_desc is not None and \
            not _reshard.same_mesh(saved_desc, cur_desc)
        plan = None
        if reshaping:
            shapes = {n: self._arg_shapes[n] for n in file_args}
            shapes.update({n: self._aux_shapes[n] for n in file_aux})
            plan = _reshard.plan_reshard(saved_desc, cur_desc, shapes)
        from . import multihost as _mh
        saved_world = (saved_desc or {}).get("world")
        world_changed = saved_world is not None and \
            int(saved_world) != _mh.world_size()
        if world_changed:
            # the rank join/leave seam fires BEFORE any state moves: an
            # injected rejoin fault leaves the old-mesh state intact
            resilience.fault_point("elastic.rejoin")

        t0 = _time.perf_counter()
        # reshard loads stage into a copy and commit only once every
        # array landed (transiently ~2x state, like any resume over
        # random init); same-mesh loads keep the in-place replacement
        target_params = {} if reshaping else self.params
        target_aux = {} if reshaping else self.aux
        target_slots = None
        new_num_update = None
        try:
            with self.mesh:
                for name, v in file_args.items():
                    if reshaping:
                        resilience.fault_point("reshard.scatter")
                    target_params[name] = self._put_state(
                        _np.asarray(v.asnumpy(), _np.float32),
                        self._param_sharding[name])
                for name, v in file_aux.items():
                    if reshaping:
                        resilience.fault_point("reshard.scatter")
                    target_aux[name] = self._put_state(
                        _np.asarray(v.asnumpy(), _np.float32),
                        self._aux_sharding[name])
                if load_optimizer_states:
                    states_name = "%s-%04d.states" % (prefix, epoch)
                    try:
                        st = _nd.load(states_name)
                    except FileNotFoundError as e:
                        raise MXNetError(
                            "checkpoint states file %r is missing for "
                            "epoch %d" % (states_name, epoch)) from e
                    except (ValueError, EOFError, _struct.error) as e:
                        raise MXNetError(
                            "checkpoint states file %r is corrupt: %s"
                            % (states_name, e)) from e
                    slots_in_file = {}
                    for k in st:
                        if k.startswith("slot"):
                            slot, name = k.split(":", 1)
                            i = int(slot[len("slot"):])
                            slots_in_file[name] = max(
                                slots_in_file.get(name, 0), i + 1)
                    for name, n in slots_in_file.items():
                        if name not in self.opt_state or                                 n != len(self.opt_state[name]):
                            raise MXNetError(
                                "optimizer state mismatch for %r: file "
                                "has %d slots, trainer (%s) expects %d "
                                "— resume with the optimizer the "
                                "checkpoint was saved with"
                                % (name, n,
                                   type(self.optimizer).__name__,
                                   self._n_slots))
                    target_slots = {n: list(s)
                                    for n, s in self.opt_state.items()} \
                        if reshaping else self.opt_state
                    for k, v in st.items():
                        if k == "meta:num_update":
                            new_num_update = int(
                                v.asnumpy().astype(_np.int64)[0])
                            continue
                        slot, name = k.split(":", 1)
                        i = int(slot[len("slot"):])
                        if reshaping:
                            resilience.fault_point("reshard.scatter")
                        target_slots[name][i] = self._put_state(
                            _np.asarray(v.asnumpy(), _np.float32),
                            self._param_sharding[name])
        except (MXNetError, ValueError, RuntimeError, TypeError) as e:
            if reshaping:
                # degrade to the old-mesh error path: the live state
                # was never touched (staged copies are dropped)
                raise MXNetError(
                    "resharding checkpoint %r epoch %d from mesh %s "
                    "onto mesh %s failed: %s — trainer state left "
                    "unchanged on the current mesh"
                    % (prefix, epoch, plan["src"], plan["dst"], e)) \
                    from e
            raise
        if reshaping:
            self.params = target_params
            self.aux = target_aux
            if target_slots is not None:
                self.opt_state = target_slots
            _reshard.note_reshape("load", plan,
                                  seconds=_time.perf_counter() - t0,
                                  epoch=epoch)
        if world_changed:
            _reshard.note_world_change(saved_world, _mh.world_size(),
                                       kind="load")
        if new_num_update is not None:
            self.optimizer.begin_num_update = new_num_update
        # the restored state IS the new baseline: steps counted before
        # this load no longer describe it (with optimizer states the
        # meta handling above also restored begin_num_update)
        self._resume_epoch = int(epoch)
        self._step_count = 0
        # stash the durable data-iterator state for restore_data_iter /
        # fit to consume (mxnet_tpu.io_resume): model state and data
        # cursor resume from the SAME checkpoint, so a SIGKILL mid-epoch
        # replays no sample and drops none — across a world-size change
        # the ledger state re-cuts per rank (io.remap)
        if manifest is not None:
            from .. import io_resume as _ior
            _ior.note_loaded_state(
                _reshard.manifest_data_state(manifest),
                source="%s epoch %d" % (prefix, epoch))

    def load_latest_checkpoint(self, prefix, load_optimizer_states=False):
        """Restore from the NEWEST complete checkpoint under ``prefix``,
        falling back past corrupt/incomplete epochs (a save interrupted
        between tmp-write and rename is invisible; a CRC-failing file is
        skipped with a warning).  Returns the restored epoch, or None
        when no checkpoint exists yet (caller starts fresh) — the
        preemption-restart resume path."""
        import logging
        from ..base import MXNetError as _Err
        from ..model import find_checkpoints

        for ep in reversed(find_checkpoints(
                prefix, require_states=load_optimizer_states)):
            try:
                self.load_checkpoint(
                    prefix, ep, load_optimizer_states=load_optimizer_states)
                return ep
            except _Err as e:
                logging.warning("falling back past checkpoint epoch %d "
                                "of %r: %s", ep, prefix, e)
        return None

    def restore_data_iter(self, it):
        """Restore ``it`` from the ``data_state`` entry the last
        :meth:`load_checkpoint` found (``mxnet_tpu.io_resume``), and
        register it as the run's tracked iterator so subsequent
        checkpoints carry ITS state.  Returns the consumed manifest
        entry, or None when the checkpoint carried no durable state.
        A restore fault (the ``io.resume`` seam) propagates with the
        entry still pending — retry with the same iterator after
        clearing the fault."""
        from .. import io_resume as _ior
        from ..telemetry import ioview as _iov
        _iov.track(it)
        return _ior.apply_pending(it)

    def install_preemption_handler(self, prefix, save_optimizer_states=True,
                                   signals=None, exit_process=True):
        """Checkpoint-and-exit cleanly on SIGTERM (host preemption).

        Cloud TPU hosts get a SIGTERM grace window before shutdown; the
        handler writes an atomic checkpoint at epoch = resumed epoch +
        completed step count and exits 0, so the supervisor (tools/launch.py watchdog
        or an external scheduler) can restart the job and
        :meth:`load_latest_checkpoint` resumes it.  Runs in the MAIN
        thread between Python bytecodes — an in-flight jitted step
        finishes first, so the saved state is step-consistent.

        Multi-host caveat: save_checkpoint is collective (the gather);
        the handler assumes every rank receives the signal (true for
        whole-slice preemption and for launch.py's group teardown).

        Returns the handler (its ``.triggered`` attribute flips to True
        after it fires — useful when ``exit_process=False`` and the
        training loop wants to drain and stop itself)."""
        import signal as _signal
        import sys as _sys
        import logging

        if signals is None:
            signals = (_signal.SIGTERM,)

        def handler(signum, frame):
            if handler._saving:         # repeated TERM during the save
                return
            handler._saving = True
            try:
                # _step_count restarts at 0 after a resume: offset by
                # the resumed epoch so a SECOND preemption never writes
                # a lower epoch than the first (load_latest would
                # resume the older checkpoint and re-train the same
                # window forever)
                epoch = self._resume_epoch + self._step_count
                logging.warning(
                    "preemption signal %d: checkpointing to %r epoch "
                    "%d and exiting", signum, prefix, epoch)
                # black box first: if the grace window expires mid-save
                # the flight dump still tells the postmortem what the
                # run was doing when the preemption landed
                from ..telemetry import flight as _flight
                _flight.record("preemption", signum=int(signum),
                               epoch=epoch)
                _flight.dump("sigterm")
                self.save_checkpoint(
                    prefix, epoch,
                    save_optimizer_states=save_optimizer_states)
                handler.triggered = True
                if exit_process:
                    _sys.exit(0)
            finally:
                # in drain mode (exit_process=False) a LATER preemption
                # must checkpoint again, not be swallowed by a latch
                handler._saving = False

        handler._saving = False
        handler.triggered = False
        for sig in signals:
            _signal.signal(sig, handler)
        return handler



class _HostArray:
    """Minimal NDArray-like shim so Initializers can write numpy in-place."""

    def __init__(self, data):
        self.data = data

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    def __setitem__(self, key, value):
        self.data[key] = np.asarray(value)

    def __getitem__(self, key):
        return self.data[key]

