"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh.

The reference's model parallelism assigns whole layers to devices and
runs them sequentially per batch (`example/model-parallel-lstm/
lstm.py:142-205` — each LSTM layer on its own GPU, overlap only from
async engine dispatch).  This module is the compiled TPU-native
successor: the layer stack is sharded over a ``pipe`` mesh axis, the
batch is split into microbatches, and ONE jitted SPMD program streams
activations stage-to-stage over the ICI ring (`lax.ppermute` inside
`shard_map`), so all stages compute concurrently after the fill phase.
Gradients come from `jax.grad` straight through the schedule — the
backward pass replays it in reverse (GPipe semantics; per-microbatch
`jax.checkpoint` keeps activation memory at O(microbatch)).

Scope: uniform stages — every stage maps (microbatch, ...) -> the same
shape (layer stacks: RNN/transformer layers, repeated blocks).  The
stage parameters are stacked on a leading axis sharded over ``pipe``.
"""
from __future__ import annotations

import functools

__all__ = ["pipeline_apply", "pipeline_grad", "make_pipeline_mesh"]


def make_pipeline_mesh(n_stages, devices=None):
    """1-D mesh with a ``pipe`` axis of n_stages devices."""
    from .mesh import make_1d_mesh
    return make_1d_mesh("pipe", n_stages, devices)


def _stage_loop(stage_fn, params_stack, x_stack, axis_name, remat,
                n_stages):
    """Per-device body under shard_map.

    params_stack: (1, ...) this device's stage params (leading stage axis
    sharded to size 1).  x_stack: (M, B_u, ...) all microbatches,
    replicated.  Returns (M, B_u, ...) outputs of the LAST stage
    (garbage on other devices; caller slices stage S-1's shard).
    ``n_stages`` is threaded in statically (the scan length and the
    ppermute ring need python ints; jax 0.4.x has no lax.axis_size).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = n_stages
    sid = lax.axis_index(axis_name)
    m = x_stack.shape[0]
    params = jax.tree.map(lambda p: p[0], params_stack)
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    shift = [(i, (i + 1) % n) for i in range(n)]  # stage s -> s+1

    def tick(carry, t):
        # carry: (inbuf, outputs)
        #   inbuf: (B_u, ...) the activation this stage consumes this tick
        #   outputs: (M, B_u, ...) last-stage results by microbatch
        inbuf, outputs = carry
        # stage 0 reads microbatch t from the input stream; others read
        # what the previous stage sent last tick
        x_t = jnp.where(sid == 0,
                        x_stack[jnp.clip(t, 0, m - 1)], inbuf)
        # active when microbatch (t - sid) is in range
        mb = t - sid
        active = (mb >= 0) & (mb < m)
        y = fn(params, x_t)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # the last stage stores its result; everyone else forwards it
        outputs = jnp.where(
            (sid == n - 1) & active,
            outputs.at[jnp.clip(mb, 0, m - 1)].set(y), outputs)
        nxt = lax.ppermute(y, axis_name, shift)
        return (nxt, outputs), None

    inbuf0 = jnp.zeros_like(x_stack[0])
    outputs0 = jnp.zeros_like(x_stack)
    (_, outputs), _ = lax.scan(tick, (inbuf0, outputs0),
                               jnp.arange(m + n - 1))
    return outputs


def pipeline_apply(stage_fn, params_stack, x, mesh, microbatches,
                   remat=True):
    """Run ``x`` through ``n_stages`` pipelined applications of
    ``stage_fn`` (one stage per device on the mesh's ``pipe`` axis).

    stage_fn(params, x_micro) -> y_micro with y.shape == x.shape (uniform
    stages).  params_stack: pytree whose leaves have a leading stage axis
    of size n_stages.  x: (batch, ...), split into ``microbatches`` equal
    chunks.  Returns (batch, ...) outputs of the final stage, replicated.
    """
    from .. import telemetry
    if _being_traced(params_stack, x):
        # caller is tracing (jit(pipeline_apply) is a supported
        # pattern): a span here would record one trace-time interval
        # and then nothing per execution — worse than no data
        return _pipeline_apply(stage_fn, params_stack, x, mesh,
                               microbatches, remat)
    with telemetry.span("pipeline.apply", category="trainer"):
        return _pipeline_apply(stage_fn, params_stack, x, mesh,
                               microbatches, remat)


def _being_traced(*trees):
    """Whether any array leaf of ``trees`` is a tracer, i.e. the caller
    runs under jit/grad rather than on concrete arrays."""
    import jax
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(trees))


def _pipeline_apply(stage_fn, params_stack, x, mesh, microbatches, remat):
    import jax
    from jax.sharding import PartitionSpec as P

    n = mesh.devices.size
    b = x.shape[0]
    if b % microbatches:
        raise ValueError("batch %d not divisible into %d microbatches"
                         % (b, microbatches))
    x_stack = x.reshape((microbatches, b // microbatches) + x.shape[1:])

    body = functools.partial(_stage_loop, stage_fn, axis_name="pipe",
                             remat=remat, n_stages=int(n))
    out = jax.shard_map(
        lambda p, xs: jax.lax.psum(body(p, xs), "pipe"),
        mesh=mesh,
        in_specs=(P("pipe"), P()),
        out_specs=P(),
        check_vma=False,
    )(params_stack, x_stack)
    # only the last stage contributed nonzeros; psum replicates its result
    return out.reshape((b,) + out.shape[2:])


def pipeline_grad(loss_fn, stage_fn, params_stack, x, labels, mesh,
                  microbatches, remat=True):
    """(loss, grads) of ``loss_fn(pipeline(x), labels)`` w.r.t. the
    stacked stage params — jax.grad runs the schedule in reverse
    (ppermute transposes to the opposite ring direction)."""
    import jax
    from .. import telemetry

    def full(p):
        y = _pipeline_apply(stage_fn, p, x, mesh, microbatches,
                            remat=remat)
        return loss_fn(y, labels)

    if _being_traced(params_stack, x, labels):
        # under an outer trace a span records nothing per execution
        return jax.value_and_grad(full)(params_stack)
    with telemetry.span("pipeline.grad", category="trainer"):
        return jax.value_and_grad(full)(params_stack)


# ===================================================================
# Heterogeneous stages: arbitrary per-stage functions/params/shapes.
#
# The uniform path above stacks identical stage params; real models
# (ResNet stages, embed->blocks->head transformers) have per-stage
# pytrees of different shapes and different boundary activations.  The
# SPMD-compatible encoding:
#
# * each stage's (compute-dtype) params are flattened and concatenated
#   into one vector, padded to the max stage length, stacked (N, L) and
#   sharded over ``pipe`` — every device holds ONLY its stage's packed
#   params (no replication);
# * boundary activations are flattened per sample and padded to the max
#   boundary width W, so the ring carries one (B_u, W) buffer;
# * the per-device stage body is ``lax.switch(stage_id, branches)`` —
#   each branch statically unpacks ITS stage's params/input shape, runs
#   the stage, and re-packs.  Only the resident branch executes on each
#   device, so compute and memory stay per-stage.
#
# The GPipe schedule (fill, steady state, drain over M + N - 1 ticks)
# and its reverse-mode transpose are the same as the uniform path.
# ===================================================================

def plan_pipeline_stages(topo, entries, batch_names, n_stages,
                         cost_of=None, legal_cut=None):
    """Partition a Symbol graph into ``n_stages`` contiguous segments.

    Cuts are only legal where exactly ONE tensor crosses the boundary
    (single-live-tensor positions — between residual blocks, transformer
    layers, stacked stages); ``legal_cut((node, out_idx)) -> bool`` can
    veto candidates further (the trainer rejects boundaries whose
    leading dim is not the microbatch row count).  Segments are balanced
    by ``cost_of`` (node -> float; default: 1 per node — callers with
    shape information pass a params+activations proxy).

    Returns a list of per-stage dicts:
      nodes         — the segment's non-variable nodes, topo order
      boundary_in   — (node, out_idx) produced by the previous segment
                      (None for stage 0)
      param_names   — names of weight variables consumed by the segment
      batch_names   — batch variables consumed by the segment (stage 0
                      gets the data; later stages e.g. the loss labels)
    Raises MXNetError when the graph has no n_stages-1 legal cuts or
    when a segment node carries auxiliary state (BatchNorm moving stats
    — GPipe microbatching would change their semantics).
    """
    from ..base import MXNetError

    nodes = [n for n in topo if not n.is_variable]
    if len(nodes) < n_stages:
        raise MXNetError("graph has %d op nodes < %d pipeline stages"
                         % (len(nodes), n_stages))
    pos = {id(n): i for i, n in enumerate(nodes)}
    end = len(nodes)

    # last consumer position of every (producer, out_idx)
    last_use = {}
    for i, n in enumerate(nodes):
        for (src, idx) in n.inputs:
            if not src.is_variable:
                last_use[(id(src), idx)] = i
    for (n, idx) in entries:
        last_use[(id(n), idx)] = end

    # legal cut positions: after node i, exactly one value crosses
    id2node = {id(n): n for n in nodes}
    crossings = {}
    for i in range(len(nodes) - 1):
        live = [(pid, idx) for (pid, idx), lu in last_use.items()
                if pos[pid] <= i < lu]
        if len(live) == 1:
            pid, idx = live[0]
            if legal_cut is None or legal_cut((id2node[pid], idx)):
                crossings[i] = live[0]

    if cost_of is None:
        def cost_of(node):
            return 1.0
    prefix = []
    acc = 0.0
    for n in nodes:
        acc += float(cost_of(n))
        prefix.append(acc)
    total = acc

    cuts = []
    prev = -1
    cands = sorted(crossings)
    for s in range(1, n_stages):
        target = total * s / n_stages
        best = None
        for c in cands:
            if c <= prev or (cuts and c <= cuts[-1]):
                continue
            # keep enough remaining cut positions for later stages
            remaining = sum(1 for cc in cands if cc > c)
            if remaining < n_stages - 1 - s:
                continue
            if best is None or abs(prefix[c] - target) < \
                    abs(prefix[best] - target):
                best = c
        if best is None:
            raise MXNetError(
                "cannot cut the graph into %d pipeline stages: only %d "
                "single-live-tensor positions available" %
                (n_stages, len(cands)))
        cuts.append(best)
        prev = best

    stages = []
    bounds = [-1] + cuts + [len(nodes) - 1]
    for s in range(n_stages):
        seg = nodes[bounds[s] + 1: bounds[s + 1] + 1]
        pnames, bnames = [], []
        for n in seg:
            if len(n.inputs) > n.num_args:
                raise MXNetError(
                    "pipeline stage %d contains %r which carries "
                    "auxiliary state; GPipe microbatching would change "
                    "its semantics (BatchNorm moving stats are per-"
                    "microbatch) — use LayerNorm-style models or fewer "
                    "stages" % (s, n.name))
            stoch = n.op.stochastic
            if callable(stoch):
                stoch = stoch(n.attrs)
            if stoch:
                raise MXNetError(
                    "pipeline stage %d contains stochastic op %r; the "
                    "pipelined trace does not thread PRNG keys — set "
                    "dropout to 0 for pipeline training" % (s, n.name))
            for (src, _i) in n.inputs:
                if src.is_variable:
                    if src.name in batch_names:
                        if src.name not in bnames:
                            bnames.append(src.name)
                    elif src.name not in pnames:
                        pnames.append(src.name)
        boundary_in = None
        if s > 0:
            pid, idx = crossings[cuts[s - 1]]
            boundary_in = (id2node[pid], idx)
        stages.append({"nodes": seg, "boundary_in": boundary_in,
                       "param_names": pnames, "batch_names": bnames})
    return stages


def hetero_pipeline_loss(branches, x_stack, params_stack, microbatches,
                         axis_name="pipe", remat=True):
    """GPipe schedule over heterogeneous stage branches (per-device body
    — call under shard_map).

    branches: list of N fns ``(packed_params_row, x_flat, mb) ->
    (y_flat, loss)`` — branch s unpacks its own stage statically; all
    return the common padded buffer width and a shape-(1,) loss (nonzero
    only from the last stage).  x_stack: (M, B_u, W) microbatched input
    (consumed by stage 0).  params_stack: either (1, L) — this device's
    packed stage params, pre-sharded over ``axis_name`` — or (N, L)
    REPLICATED, in which case each device dynamically selects its
    stage's row.  Callers composing pipe with a data axis must pass the
    replicated form: GSPMD (jax 0.4.x) mispartitions the reshard of an
    in-jit concatenate onto a minor mesh axis — the partial
    dynamic-update-slices it combines with an add double-count the data
    replicas, silently scaling the packed params by the data-axis size.
    Returns the shape-(1,) summed loss over microbatches (nonzero on
    the last stage; psum over ``axis_name`` to broadcast).

    The loss stays rank-1 end to end INSIDE the shard_map body: jax
    0.4.x's shard_map partial-eval promotes rank-0 residuals
    inconsistently across the remat/transpose path, and a scalar
    residual with dim-0 axis names fails its out-spec check under
    jax.grad — callers index ``[0]`` outside the shard_map instead.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    # one branch per pipeline stage, one stage per device on the axis:
    # the branch count IS the axis size, and it is static (the scan
    # length below needs a python int; jax 0.4.x has no lax.axis_size)
    n = len(branches)
    sid = lax.axis_index(axis_name)
    m = x_stack.shape[0]
    if params_stack.shape[0] == 1:
        row = params_stack[0]            # pre-sharded: this stage's row
    else:
        row = lax.dynamic_index_in_dim(  # replicated: select by stage id
            params_stack, sid, 0, keepdims=False)
    shift = [(i, (i + 1) % n) for i in range(n)]

    def run_stage(x_t, mb):
        fns = [jax.checkpoint(f) if remat else f for f in branches]
        return lax.switch(sid, fns, row, x_t, mb)

    def tick(carry, t):
        inbuf, loss_acc = carry
        mb = t - sid
        active = (mb >= 0) & (mb < m)
        x_t = jnp.where(sid == 0, x_stack[jnp.clip(t, 0, m - 1)], inbuf)
        y, loss_c = run_stage(x_t, jnp.clip(mb, 0, m - 1))
        y = jnp.where(active, y, jnp.zeros_like(y))
        loss_acc = loss_acc + jnp.where(active, loss_c,
                                        jnp.zeros_like(loss_c))
        nxt = lax.ppermute(y, axis_name, shift)
        return (nxt, loss_acc), None

    inbuf0 = jnp.zeros_like(x_stack[0])
    (_, loss), _ = lax.scan(tick,
                            (inbuf0, jnp.zeros((1,), jnp.float32)),
                            jnp.arange(m + n - 1))
    return loss
