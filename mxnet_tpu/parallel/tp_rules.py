"""Automatic tensor-parallel sharding rules for Symbol graphs.

The reference has no tensor parallelism (SURVEY §2.4); the TPU-native
design is GSPMD sharding annotations: ``tp_rules`` maps parameter names
to the weight axis sharded over the mesh 'model' axis, and XLA inserts
the all-gathers/reduce-scatters.  ANY rule set is numerically correct —
GSPMD reshards as needed — so the job of this module is to derive the
COMMUNICATION-EFFICIENT rules a user would hand-write:

* Megatron-style pairing (arXiv:1909.08053): a FullyConnected whose
  output feeds (through elementwise/attention-shaped ops) another
  FullyConnected is column-parallel (weight axis 0, the output dim) and
  its partner row-parallel (weight axis 1, the input dim) — one psum
  per block instead of per-layer all-gathers.  Covers transformer
  QKV -> attention -> out-proj and ff1 -> act -> ff2 chains.
* Convolutions shard output channels (OIHW axis 0) when divisible —
  activations stay channel-sharded through elementwise/BN chains.
* Classifier-style standalone FC weights stay column-parallel (the
  round-2 default rule).

Bias/beta-style vectors follow their column-parallel owner (axis 0);
row-parallel owners keep replicated biases (they add after the psum).
"""
from __future__ import annotations

__all__ = ["derive_tp_rules"]

# ops a sharded activation flows through without changing which FC pair
# should be row-parallel: elementwise-ish, attention-shaped, dropout
_PASS_OPS = frozenset({
    "Activation", "LeakyReLU", "Dropout", "identity", "_copy",
    "softmax", "log_softmax", "SoftmaxActivation", "slice_axis",
    "batch_dot", "elemwise_mul", "_mul", "_mul_scalar", "_div_scalar",
    "_plus_scalar", "_minus_scalar", "broadcast_mul", "negative",
    "clip", "expand_dims", "squeeze", "SwapAxis", "transpose",
    # per channel / per head: a column-sharded activation stays sharded
    "_contrib_CausalConv1D", "_contrib_RotaryEmbedding",
    "_contrib_GatedRMSNorm", "_contrib_GatedDeltaRule",
    "_contrib_SSDScan",
})


def _weight_of(node):
    """(weight_name, bias_name | None) for FullyConnected/Convolution."""
    names = [src.name for (src, _i) in node.inputs if src.is_variable]
    w = next((n for n in names if n.endswith("_weight")), None)
    b = next((n for n in names if n.endswith("_bias")), None)
    return w, b


def derive_tp_rules(topo, arg_shapes, tp_size, min_dim=8):
    """{param_name: shard_axis} over the 'model' axis for a graph.

    topo: Symbol topo order; arg_shapes: {name: shape}; tp_size: the
    mesh 'model' axis size.  Only dims divisible by tp_size and at
    least ``min_dim * tp_size`` wide are sharded.
    """
    if tp_size <= 1:
        return {}
    rules = {}
    ok = lambda d: d % tp_size == 0 and d >= min_dim * tp_size

    fc_nodes = []
    col_ids = set()    # FC nodes currently column-parallel
    for node in topo:
        if node.is_variable or node.op is None:
            continue
        opname = node.op.name
        if opname in ("FullyConnected", "Convolution"):
            w, b = _weight_of(node)
            if w is None or w not in arg_shapes:
                continue
            shp = arg_shapes[w]
            if opname == "Convolution":
                if len(shp) >= 3 and ok(shp[0]) and \
                        int(node.attrs.get("num_group", 1)) == 1:
                    rules[w] = 0
                    if b is not None and b in arg_shapes:
                        rules[b] = 0
                continue
            # FullyConnected: column-parallel by default
            if ok(shp[0]):
                rules[w] = 0
                col_ids.add(id(node))
                if b is not None and b in arg_shapes:
                    rules[b] = 0
            fc_nodes.append(node)

    # second pass: an FC whose data flows (through pass-ops) out of a
    # column-parallel FC becomes row-parallel — sharding its INPUT dim
    # consumes the column-sharded activation directly and emits one
    # psum, whether or not its own output dim was shardable
    memo = {}

    def reaches_col(node):
        """Does data flowing into ``node`` come from a column-parallel
        FC through pass-ops only?  Memoized: pass-op diamonds (gating)
        would otherwise branch exponentially."""
        r = memo.get(id(node))
        if r is not None:
            return r
        memo[id(node)] = False       # cycle/diamond guard
        out = False
        for (src, _i) in node.inputs:
            if src.is_variable or src.op is None:
                continue
            if id(src) in col_ids:
                out = True
                break
            if src.op.name in _PASS_OPS and reaches_col(src):
                out = True
                break
        memo[id(node)] = out
        return out

    for node in fc_nodes:
        w, b = _weight_of(node)
        shp = arg_shapes[w]
        if len(shp) != 2 or not ok(shp[1]) or rules.get(w) == 1:
            continue
        if reaches_col(node):
            rules[w] = 1              # row-parallel: shard input dim
            if b is not None:
                rules.pop(b, None)    # bias adds after the psum
            col_ids.discard(id(node))
            memo.clear()              # col_ids changed; recompute
    return rules
