"""Expert parallelism: mixture-of-experts feed-forward layers.

Two layers live here.  :func:`switch_moe` is top-1 Switch routing through
dense one-hot dispatch einsums with a capacity (overflow tokens dropped).
:func:`topk_moe` is the token-choice top-k layer of current language
models (sigmoid scores, a selection-only bias, gated experts): it is
told which experts it holds, routes every token over all of them, and
computes the held experts' part of the result by sorting the
assignments and multiplying group by group; it builds no tensor of
tokens x experts x capacity, and its sorted buffer follows the share of
the experts it holds (:func:`buffer_rows`): nothing can be dropped
where a quarter or more is held.  A layer that holds under half of them
has a second, smaller buffer where that saves enough rows
(:func:`small_buffer_rows`), and each step runs over the one that what it
holds fits.  Its docstring has the rest.

Switch routing, as it always was:

Beyond-reference capability (the 0.10.1 reference predates MoE), built
the TPU way: top-1 routing is expressed as dense one-hot dispatch
einsums (static shapes, no data-dependent control flow, MXU-friendly),
and expert parallelism is GSPMD — expert-major tensors carry a
``with_sharding_constraint`` over the ``expert`` mesh axis, so XLA
inserts the all-to-alls that a hand-written dispatch would need.

Routing follows the Switch Transformer recipe: per-token top-1 expert,
capacity ``ceil(T/E * capacity_factor)``, overflow tokens dropped (the
residual path carries them), gradient to the router through the gate
probability, and the standard load-balancing auxiliary loss.
"""
from __future__ import annotations

import functools
import math
import re

import numpy as np

from ..telemetry import plan as _plan


def switch_moe(x, router_w, w1, b1, w2, b2, capacity_factor=1.25,
               mesh=None, expert_axis="expert"):
    """Switch-MoE FFN.

    x: (tokens, d); router_w: (d, E); w1: (E, d, ff); b1: (E, ff);
    w2: (E, ff, d); b2: (E, d).
    Returns (y (tokens, d), aux_loss scalar).  With ``mesh``, expert-major
    intermediates are sharded over ``expert_axis`` (expert parallelism).
    """
    import jax
    import jax.numpy as jnp

    t, d = x.shape
    e = router_w.shape[1]
    c = int(math.ceil(t / e * capacity_factor))

    def shard(v, spec):
        if mesh is None:
            return v
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.lax.with_sharding_constraint(
            v, NamedSharding(mesh, P(*spec)))

    # shard the expert weights too — expert parallelism's memory win is
    # each device holding only its E/n experts, not just sharded
    # activations (replicated committed params would otherwise win)
    w1 = shard(w1, (expert_axis, None, None))
    b1 = shard(b1, (expert_axis, None))
    w2 = shard(w2, (expert_axis, None, None))
    b2 = shard(b2, (expert_axis, None))

    logits = x @ router_w.astype(x.dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                 # (T,)
    gate = jnp.take_along_axis(probs, expert_idx[:, None],
                               axis=1)[:, 0]                # (T,)
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)   # (T,E)

    # position of each token within its expert's capacity buffer
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0         # (T,E)
    keep = (pos >= 0) & (pos < c)
    posc = jnp.clip(pos, 0, c - 1).astype(jnp.int32)
    disp = (onehot[:, :, None] *
            jax.nn.one_hot(posc, c, dtype=jnp.float32) *
            keep[:, :, None].astype(jnp.float32))           # (T,E,C)
    disp = disp.astype(x.dtype)

    xe = jnp.einsum("tec,td->ecd", disp, x)                 # (E,C,d)
    xe = shard(xe, (expert_axis, None, None))
    h = jnp.einsum("ecd,edf->ecf", xe, w1.astype(x.dtype))
    h = jax.nn.relu(h + b1[:, None, :].astype(x.dtype))
    h = shard(h, (expert_axis, None, None))
    ye = jnp.einsum("ecf,efd->ecd", h, w2.astype(x.dtype))
    ye = ye + b2[:, None, :].astype(x.dtype)
    ye = shard(ye, (expert_axis, None, None))

    y = jnp.einsum("tec,ecd->td", disp, ye)
    y = y * gate[:, None].astype(x.dtype)

    # Switch load-balance loss: E * sum_e f_e * P_e
    frac = jnp.mean(onehot, axis=0)                         # tokens/expert
    mean_p = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * mean_p)
    return y, aux


#: ``jax.named_scope`` of the expert layer's ops on the device
SCOPE_MOE = "mxtpu.block.moe"

#: what XLA:TPU names the Mosaic grouped-matmul custom calls it makes of
#: ``jax.lax.ragged_dot`` (``%ragged-dot-none.3 = ... custom-call(``;
#: their tile maps are ``%ragged-dot-metadata.*``): they visit only the
#: row tiles the groups fill.  Where a backend has none it multiplies
#: densely and masks, and no such instruction is in the program.
_GROUPED_PRODUCT = re.compile(
    r"^\s*%?ragged-dot-(?!metadata)[\w-]*(?:\.\d+)? = .*\bcustom-call\(",
    re.M)
#: grouped products of one trained layer of gated experts: three forward,
#: and each one's two backward products (ungated experts, two matrices an
#: expert, have six: a layer's plan says its own, ``products_trained``)
PRODUCTS_PER_TRAINED_LAYER = 9


def _sorted_dispatch(k):
    """``(dispatch, combine)`` for ``k`` assignments a token: the two
    row permutations between token order and expert-sorted order, each
    with a hand-written transpose so that both directions are gathers
    (autodiff would scatter-add ``tokens * k`` rows).  That holds for a
    buffer of every assignment there can be, where the sorted order is a
    permutation and ``inv`` its inverse; a buffer of fewer rows sums them
    into token order another way (:func:`_bounded_products`): gathering
    ``tokens * k`` rows through ``inv`` for the ``n_rows`` that are there
    costs more than it saves below half of them (PERF.md section 5's
    table)."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def dispatch(x, order, inv, held):
        # row r of the sorted buffer is token order[r] // k
        return x[order // k]

    def dispatch_fwd(x, order, inv, held):
        return dispatch(x, order, inv, held), (inv, held, x.shape[0])

    def dispatch_bwd(res, g):
        inv, held, t = res
        # rows past the held assignments belong to no group: whatever a
        # grouped product left there is not a gradient
        gk = jnp.where(held.reshape(-1, 1), g[inv], 0)
        return (gk.reshape(t, k, -1).sum(axis=1).astype(g.dtype),
                None, None, None)

    dispatch.defvjp(dispatch_fwd, dispatch_bwd)

    @jax.custom_vjp
    def combine(rows, order, inv):
        return rows[inv]

    def combine_fwd(rows, order, inv):
        return rows[inv], order

    def combine_bwd(order, g):
        return g[order], None, None

    combine.defvjp(combine_fwd, combine_bwd)
    return dispatch, combine


def _keeps_products(prim, *_avals, **_params):
    """``jax.checkpoint`` policy of the two branches of a layer with two
    sizes: a branch keeps its grouped products' results for the backward
    and makes the rest again from its inputs there (the gather into sorted
    order, masks, the gate arithmetic: no product).  What autodiff keeps
    of a ``lax.cond`` both branches allocate, and each writes zeros for
    the other's; left to itself it keeps every elementwise value between
    the products, and XLA cannot fuse them away across the ``cond``: one
    layer at LFM2's size with its backward planned 2.66 GB of scratch so,
    0.99 with this policy, 0.58 with one size (compiled for a described
    v5e; PERF.md section 6, PR 39)."""
    return prim.name == "ragged_dot_general"


def _experts(xs, w1, w3, w2, sizes):
    """The experts' products over rows sorted by expert, ``sizes`` rows
    an expert: ``w2(silu(xs w1) * (xs w3))``, or with ``w3`` None the
    ungated ``w2(relu(xs w1^T)^2)``, whose square is made again from the
    up-projection's output in the backward.  The ungated ``w1`` comes
    ``(experts, ff, d)``, both of an expert's matrices with ``d`` as
    their last axis: at a width that is no whole number of 128 lanes
    (Nemotron-H's 1856) XLA carries a float32 ``(experts, d, ff)`` master
    and its optimizer state through a chain of steps transposed and
    copies them in and out, 2.9 GB of the 8192-token step's plan
    (PERF.md section 6, PR 38)."""
    import jax
    import jax.numpy as jnp

    dtype = xs.dtype
    if w3 is not None:
        h = jax.nn.silu(jax.lax.ragged_dot(xs, w1.astype(dtype), sizes)) \
            * jax.lax.ragged_dot(xs, w3.astype(dtype), sizes)
        return jax.lax.ragged_dot(h, w2.astype(dtype), sizes)
    # an ungated width that is no whole number of 256-wide tiles is
    # zero-padded to one for the products (hidden units that are 0 on every
    # row): XLA:TPU's grouped matmul ran the 1856-wide experts' six products
    # of a layer in 10.5 ms, a width of 1920 in 11.1 and this padding's 2048
    # in 5.3 (PERF.md section 6, PR 38: three widths at one shape; whether
    # seven tiles are as fast as eight was not measured); a width under one
    # tile is left as it is
    ff = w2.shape[1]
    pad = -ff % GROUPED_WIDTH_TILE if ff > GROUPED_WIDTH_TILE else 0
    up, down = jnp.swapaxes(w1, 1, 2).astype(dtype), w2.astype(dtype)
    if pad:
        up = jnp.pad(up, ((0, 0), (0, 0), (0, pad)))
        down = jnp.pad(down, ((0, 0), (0, pad), (0, 0)))
    u = jax.lax.ragged_dot(xs, up, sizes)
    h = jax.checkpoint(lambda u: jnp.square(jax.nn.relu(u)))(u)
    return jax.lax.ragged_dot(h, down, sizes)


def _token_sum_form(t, n_rows, d, dtype):
    """``(form, tokens a block)`` of the two sums of a sorted buffer's rows
    into token order (:func:`_bounded_products`), a rule in the shapes and
    the platform as ``_flash_blocks`` is one: ``"kernel"``,
    ``mxtpu_moe_token_sum`` (``ops/moe_token_sum.py``), over rows of whole
    lane tiles, whole row chunks and whole token blocks, at the largest
    block of :data:`TOKEN_SUM_BLOCKS` that fits the default of VMEM at this
    width, where :func:`_token_sum_lowering` has the kernel; anywhere else
    ``"scatter_add"``, the ``jax.numpy`` form."""
    from ..ops import moe_token_sum as _kernel
    if d % 128 == 0 and n_rows % _kernel.CHUNK == 0:
        for block, widest in TOKEN_SUM_BLOCKS:
            if t % block == 0 and d <= widest:
                return _token_sum_lowering(dtype), block
    return "scatter_add", None


def _token_sum_lowering(dtype):
    """``"kernel"`` on a TPU over bf16 rows (a 0/1 matrix times bf16 rows
    is exact on the MXU; float32 rows would take six passes), else
    ``"scatter_add"``: the ``jax.numpy`` form, every other backend's path
    and the tests' oracle, as the delta rule's and the flash kernels'
    ``jax.numpy`` forms are.  Under a mesh of more than one device it stays
    too: GSPMD cannot partition the kernel.  (``"interpret"``, the kernel
    under Pallas's interpreter at any dtype, is the tests' to ask for.)"""
    import jax.numpy as jnp
    from .. import context as _context
    from . import mesh as _mesh
    if _context.on_tpu() and _mesh.active_kernel_mesh() is None \
            and dtype == jnp.bfloat16:
        return "kernel"
    return "scatter_add"


def _kernel_sums(k, n_rows, block, interpret):
    """``(dispatch, combine)`` of :func:`_bounded_products` on the kernel:
    the gather into the buffer's order and the weighted sum back, each with
    a hand-written transpose, as :func:`_sorted_dispatch`'s pair has.  The
    gather's transpose is the kernel with unit weights; the sum's is a
    gather (``g[token] * weight``), and the gates' cotangent comes from the
    kept product rows through ``slot``, another gather."""
    import jax
    import jax.numpy as jnp
    from ..ops.moe_token_sum import token_sum

    @jax.custom_vjp
    def dispatch(x, head, filled, pos):
        return jnp.where(filled[:, None], x[head // k], 0)

    def dispatch_fwd(x, head, filled, pos):
        return dispatch(x, head, filled, pos), pos

    def dispatch_bwd(pos, g):
        return (token_sum(g, pos, block=block, interpret=interpret),
                None, None, None)

    dispatch.defvjp(dispatch_fwd, dispatch_bwd)

    @jax.custom_vjp
    def combine(rows, gates, head, filled, slot, hit, pos):
        weight = jnp.sum(jnp.where(hit, gates[:, :, None], 0.0), axis=1)
        return token_sum(rows, pos, weight, block=block, interpret=interpret)

    def combine_fwd(rows, gates, *where):
        # the product rows are kept only for the gates' cotangent: a layer
        # whose router is not trained (``gates`` unperturbed) keeps none,
        # as autodiff kept none through the scatter-add
        y = combine(rows.value, gates.value, *(w.value for w in where))
        return y, (rows.value if gates.perturbed else None, gates.value,
                   *(w.value for w in where[:3]))

    def combine_bwd(res, g):
        rows, gates, head, filled, slot = res
        if isinstance(g, jax.custom_derivatives.SymbolicZero):
            g = jnp.zeros(g.shape, g.dtype)
        weight = jnp.where(filled, gates.reshape(-1)[head], 0.0)
        by_row = g[head // k].astype(jnp.float32)           # (n_rows, d)
        if rows is None:
            d_gates = jnp.zeros_like(gates)
        else:
            kept = jnp.where(filled[:, None], rows.astype(jnp.float32), 0.0)
            # an assignment has a row where it sorts before the groups' end
            d_gates = jnp.where(
                slot < jnp.sum(filled),
                jnp.sum(by_row * kept, axis=1)[jnp.minimum(slot, n_rows - 1)],
                0.0)
        # y has the rows' dtype, and so has its cotangent
        return ((by_row * weight[:, None]).astype(g.dtype), d_gates,
                None, None, None, None, None)

    combine.defvjp(combine_fwd, combine_bwd, symbolic_zeros=True)
    return dispatch, combine


def _bounded_products(x, w1, w3, w2, gates, order, inv, counts, n_rows):
    """The held experts' part of the result over a sorted buffer of
    ``n_rows < tokens * top_k`` rows (:func:`buffer_rows`): the buffer is
    the head of the sorted order, the groups end where it does, and the
    gather into it, the experts' products and the sum back into token order
    all run over its rows alone.  An assignment past the buffer has no row
    and adds nothing.

    The sum back and the gather's transpose both add the buffer's rows into
    token order.  ``order`` is a *stable* ``argsort`` by expert, so inside
    an expert's group the tokens ascend strictly and none repeats: the rows
    of a group that a block of tokens reads are one contiguous range, and
    where :func:`_token_sum_form` says ``"kernel"`` both are
    ``mxtpu_moe_token_sum`` calls over those ranges, per token the
    experts added in ascending order in float32, and their transposes are
    gathers (:func:`_kernel_sums`).  Elsewhere they are the ``jax.numpy``
    scatter-add and autodiff's transposes (a gather of ``n_rows`` rows for
    the scatter-add and the reverse), which must assume that any two rows
    may hit one token."""
    import jax
    import jax.numpy as jnp

    t, k = gates.shape
    order = order[:n_rows]
    token = order // k
    sizes = jnp.diff(jnp.minimum(jnp.cumsum(counts), n_rows), prepend=0)
    # rows past the held assignments belong to no group: what a grouped
    # product leaves there is neither a result nor a gradient
    filled = jnp.arange(n_rows) < jnp.sum(sizes)
    form, block = _token_sum_form(t, n_rows, x.shape[1], x.dtype)
    if form != "scatter_add":
        # pos[t, e]: the buffer row of token t's assignment to held expert
        # e, -1 where it has none there (or none within the buffer)
        ends = jnp.cumsum(sizes)
        slot = inv.reshape(t, k)
        hit = (slot[:, :, None] >= (ends - sizes)[None, None, :]) \
            & (slot[:, :, None] < ends[None, None, :])      # (t, k, held)
        pos = jnp.sum(jnp.where(hit, slot[:, :, None], 0), axis=1) \
            - (~jnp.any(hit, axis=1))
        dispatch, combine = _kernel_sums(k, n_rows, block,
                                         form == "interpret")
        xs = dispatch(x, order, filled, pos)
        rows = _experts(xs, w1, w3, w2, sizes)
        return combine(rows, gates, order, filled, slot, hit, pos)
    weight = jnp.where(filled, gates.reshape(-1)[order], 0.0)
    xs = jnp.where(filled[:, None], x[token], 0)            # (n_rows, d)
    rows = _experts(xs, w1, w3, w2, sizes)
    rows = jnp.where(filled[:, None], rows.astype(jnp.float32), 0.0) \
        * weight[:, None]
    y = jnp.zeros((t, x.shape[1]), jnp.float32).at[token].add(rows)
    return y.astype(x.dtype)


def _at_the_bound(x, w1, w3, w2, gates, order, inv, here, counts, n_rows):
    """The held experts' part of the result over a buffer of
    :func:`buffer_rows` rows: with every assignment there can be in the
    buffer the sorted order is a permutation and both directions are
    gathers (:func:`_sorted_dispatch`); with fewer rows,
    :func:`_bounded_products`."""
    import jax.numpy as jnp

    t, k = gates.shape
    if n_rows < t * k:
        return _bounded_products(x, w1, w3, w2, gates, order, inv, counts,
                                 n_rows)
    dispatch, combine = _sorted_dispatch(k)
    xs = dispatch(x, order, inv, here)                      # (t*k, d)
    rows = _experts(xs, w1, w3, w2, counts)
    back = combine(rows, order, inv).reshape(t, k, x.shape[1])
    weight = jnp.where(here, gates, 0.0)[:, :, None]
    return jnp.sum(jnp.where(here[:, :, None],
                             back.astype(jnp.float32), 0.0) * weight,
                   axis=1).astype(x.dtype)


@functools.lru_cache(maxsize=None)
def _two_sizes(n_rows, small_rows):
    """The same over whichever of two buffers the step's held assignments
    fit: a ``lax.cond`` on ``sum(counts) <= small_rows`` between
    :func:`_bounded_products` over ``small_rows`` rows, where every held
    assignment then has a row, and :func:`_at_the_bound`.  One jitted
    function a pair of sizes, so that a model's expert layers, its step
    and its chain, their derivatives and the graph's shape inference
    trace the two branches once between them and not once each (the
    four layers of the 8192-token LFM2 step lowered in 9.3 s instead of
    5.4 while every call traced its own; PERF.md section 6, PR 39)."""
    import jax
    import jax.numpy as jnp

    def two_sizes(x, w1, w3, w2, gates, order, inv, here, counts):
        def small(x, w1, w3, w2, gates):
            return _bounded_products(x, w1, w3, w2, gates, order, inv, counts,
                                     small_rows)

        def bound(x, w1, w3, w2, gates):
            return _at_the_bound(x, w1, w3, w2, gates, order, inv, here,
                                 counts, n_rows)

        return jax.lax.cond(
            jnp.sum(counts) <= small_rows,
            jax.checkpoint(small, policy=_keeps_products),
            jax.checkpoint(bound, policy=_keeps_products),
            x, w1, w3, w2, gates)
    return jax.jit(two_sizes)


#: an ungated expert's width is padded to a whole number of these for the
#: grouped products (:func:`_experts`)
GROUPED_WIDTH_TILE = 256
#: rows the sorted buffer is rounded up to (a sublane tile)
ROW_TILE = 8
#: how many times the even load of the held experts the buffer takes
BUFFER_OVER_EVEN = 4
#: and how many times the second, smaller buffer, which a step runs over
#: when what it holds fits
SMALL_OVER_EVEN = 2
#: tokens a block of ``mxtpu_moe_token_sum``, the largest first, each with
#: the widest rows at which its accumulator, two windows and output fit the
#: default of VMEM (compiled for a described v5e: 512 x 2304 fits and 512 x
#: 2432 does not, 256 x 4096 fits and 128 x 8192 does not).  The largest
#: block is the fastest at every shape of PERF.md section 5's table (LFM2's
#: sum: 0.485 ms at 128, 0.375 at 256, 0.330 at 512; my chip run, PR 46)
TOKEN_SUM_BLOCKS = ((512, 2304), (256, 4096), (128, 4096))
#: rows a token that the smaller buffer must save for a layer to have it:
#: the ``cond`` between the two sizes costs at its edge whatever it saves
#: (its branches share no buffer and XLA fuses nothing across it), and at
#: half a row a token, 8 of 256 experts held and 8 a token, the 8192-token
#: Kimi Linear step lost 3.5% (PERF.md section 6, PR 39)
SMALL_SAVES_ROWS_A_TOKEN = 0.5


def _rows_over_even(over, tokens, top_k, held, num_experts):
    every = int(tokens) * int(top_k)
    share = -(-over * every * int(held) // int(num_experts))
    return min(every, -(-share // ROW_TILE) * ROW_TILE)


def buffer_rows(tokens, top_k, held, num_experts):
    """Rows of :func:`topk_moe`'s sorted buffer, the bound on what a step
    may hold: ``min(tokens * top_k, BUFFER_OVER_EVEN * tokens * top_k *
    held / num_experts)``, the second rounded up to :data:`ROW_TILE`.
    With a quarter or more of the experts held that is every assignment
    there can be; with 8 of 256 it is ``tokens * top_k / 8``, four times
    what even routing sends here.  A step that holds no more than
    :func:`small_buffer_rows` runs over that many rows instead."""
    return _rows_over_even(BUFFER_OVER_EVEN, tokens, top_k, held,
                           num_experts)


def small_buffer_rows(tokens, top_k, held, num_experts):
    """Rows of the second, smaller sorted buffer: :data:`SMALL_OVER_EVEN`
    times the even load of the held experts, rounded up to
    :data:`ROW_TILE`; None, and the layer has one size, where that saves
    no more than :data:`SMALL_SAVES_ROWS_A_TOKEN` rows a token on
    :func:`buffer_rows`: half or more of the experts held (nothing
    saved), or so few that the bound itself is small (``2 top_k held /
    num_experts`` rows a token are saved below a quarter held)."""
    small = _rows_over_even(SMALL_OVER_EVEN, tokens, top_k, held,
                            num_experts)
    saved = buffer_rows(tokens, top_k, held, num_experts) - small
    return small if saved > SMALL_SAVES_ROWS_A_TOKEN * int(tokens) else None


# mxlint: allow-dtype-widening(the router, its sigmoid or softmax and the gate normalisation run in float32 by the model's definition)
def topk_moe(x, router_w, expert_bias, w1, w3, w2, top_k,
             expert_offset=0, norm_topk_prob=True,
             routed_scaling_factor=1.0, router_trained=True,
             score_func="sigmoid"):
    """Token-choice top-k expert layer over the experts held here.

    x: (tokens, d).  router_w: (E, d), the router at its published
    width.  expert_bias: (E,) or None, added to the scores for the
    selection only.  w1, w3: (held, d, ff); w2: (held, ff, d): the
    gated experts ``w2(silu(x w1) * (x w3))`` of the ``held`` experts
    ``expert_offset .. expert_offset + held - 1``; with ``w3`` None they
    are the ungated ``w2(relu(x w1^T)^2)`` with ``w1`` ``(held, ff, d)``
    (:func:`_experts` has why), two matrices an expert and two grouped
    products forward.

    Every token is routed over all ``E`` experts: ``s = sigmoid(x
    router_w^T)`` in float32 (``score_func`` ``"softmax"``: the softmax
    over the router's whole width, Qwen3-MoE's and SDAR's scores), its
    ``top_k`` experts are the largest of
    ``s + expert_bias``, its gates ``s`` at those, divided by their sum
    (+1e-6) over all ``top_k`` if ``norm_topk_prob``, times
    ``routed_scaling_factor``.  The result is ``sum_e gate_e *
    Expert_e(x)`` over the chosen experts **held here**; a token none of
    whose experts is here gets zero (the residual carries it).  Summed
    over the shares that together hold all ``E``, the results are the
    whole layer's.  There is no exchange and nothing stands in for the
    absent experts.

    The layer is exact about its share, gradients included: what it
    returns for ``x`` and ``router_w`` is the held experts' part of
    those gradients, and summed over the shares the parts are the whole
    layer's (the exchange that sums the results sums them).  A graph
    that is one share and runs without the others can say
    ``router_trained=False``: the scores are then constants to the
    gradient (none for ``router_w``, none through the gates), because
    the held experts' part alone teaches the router, and through the
    gates the layers below it, to prefer the experts held here.  When
    to say so is the model builder's decision (``models.lfm2_moe``).

    The held assignments are sorted by expert (one ``argsort`` of
    ``tokens * top_k`` keys, the others last), the tokens gathered into
    that order, and the three products done group by group
    (``jax.lax.ragged_dot``; the backward's two products are grouped
    too).  The buffer has at most :func:`buffer_rows` rows, a rule from
    the shapes alone.  Where ``held / E >= 1/4`` that is ``tokens *
    top_k``, the most that can be held: no assignment is dropped
    whatever the imbalance.  Below a quarter it is four times the even
    load of the held experts (a layer that holds 8 of 256 would else
    gather and multiply over 32 times its expected rows): the first
    ``buffer_rows`` held assignments in expert order are computed and
    the rest contribute nothing, which happens only when the held
    experts together draw more than four times their even share;
    ``load`` still counts them, so a reader sees ``sum(load[:-1]) -
    buffer_rows`` assignments left out.  Gathers, products and the
    combine run over the buffer's rows, filled or not; only the
    products skip the rows past the held assignments, which belong to
    no group.

    Two of those steps add the buffer's rows into token order: the
    combine, and the transpose of the gather into the buffer.  Because the
    sort is stable, inside an expert's group the tokens ascend and none
    repeats, so on a TPU (bf16 rows of whole lane tiles, whole blocks of
    tokens: :func:`_token_sum_form`) both are the Pallas kernel
    ``mxtpu_moe_token_sum``, which reads each block of tokens' rows of a
    group as one contiguous range and adds a token's experts in ascending
    order in float32; their own transposes are gathers.  Anywhere else
    they are the ``jax.numpy`` scatter-add, which must walk the rows one
    by one.  A buffer that holds every assignment is a permutation, and
    gathers both ways (:func:`_sorted_dispatch`).  A layer's plan says
    which (``token_sum``).

    So the rows a step runs over follow what it holds.  Where twice the
    even load (:func:`small_buffer_rows`) saves more than half a row a
    token on the bound (under half of the experts held, and not so few
    that the bound is small already), the layer is a
    ``lax.cond`` on the step's own count: ``sum(counts) <=
    small_rows`` runs :func:`_bounded_products` over ``small_rows``
    rows, where every held assignment has a row, so it is exact; any
    other step runs over ``buffer_rows`` rows as a layer with one size
    does, the same drop past the bound included.  Both branches are
    differentiated through the ``cond``; each keeps its grouped
    products' results for the backward and makes its gathers and masks
    again there (:func:`_keeps_products`).  Any other layer has one
    size and no ``cond``.

    Returns ``(y (tokens, d) in x's dtype, load)`` where ``load`` is
    float32 ``(held + 1,)``: the held experts' assignment counts as the
    routing made them (the group sizes of the products, before the
    buffer's bound) and the tokens with no held expert.
    """
    import jax
    import jax.numpy as jnp

    t, d = x.shape
    held = w1.shape[0]
    k = int(top_k)
    with jax.named_scope(SCOPE_MOE):
        logits = jnp.dot(x, router_w.T.astype(x.dtype),
                         preferred_element_type=jnp.float32)
        if score_func not in ("sigmoid", "softmax"):
            raise ValueError("topk_moe: score_func %r is neither sigmoid "
                             "nor softmax" % (score_func,))
        s = jax.nn.sigmoid(logits) if score_func == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)            # (t, E) f32
        if not router_trained:
            s = jax.lax.stop_gradient(s)
        sel = s if expert_bias is None else \
            s + jax.lax.stop_gradient(expert_bias.astype(jnp.float32))
        _, idx = jax.lax.top_k(sel, k)                      # (t, k)
        gates = jnp.take_along_axis(s, idx, axis=1)
        if norm_topk_prob:
            gates = gates / (jnp.sum(gates, axis=1, keepdims=True) + 1e-6)
        gates = gates * float(routed_scaling_factor)

        here = (idx >= expert_offset) & (idx < expert_offset + held)
        local = jnp.where(here, idx - expert_offset, held).reshape(-1)
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        counts = jnp.sum(
            local[:, None] == jnp.arange(held, dtype=local.dtype)[None, :],
            axis=0, dtype=jnp.int32)                        # (held,)

        n_rows = buffer_rows(t, k, held, router_w.shape[0])
        small_rows = small_buffer_rows(t, k, held, router_w.shape[0])
        if small_rows is None:
            y = _at_the_bound(x, w1, w3, w2, gates, order, inv, here,
                              counts, n_rows)
        else:
            y = _two_sizes(n_rows, small_rows)(
                x, w1, w3, w2, gates, order, inv, here, counts)

        load = jnp.concatenate([
            counts.astype(jnp.float32),
            jnp.sum(~jnp.any(here, axis=1), dtype=jnp.float32)[None]])
    # what sums the rows of the buffer a fitting step runs over into token
    # order: the full buffer's permutation is gathers both ways already
    summed = n_rows if small_rows is None else small_rows
    note_layer(num_experts=router_w.shape[0], experts_held=held,
               expert_offset=int(expert_offset), num_experts_per_tok=k,
               hidden_size=w2.shape[1], buffer_rows=n_rows,
               small_rows=small_rows, even_rows=t * k * held / router_w.shape[0],
               token_sum="gathers" if summed == t * k else
               _token_sum_form(t, summed, d, x.dtype)[0],
               products_trained=6 if w3 is None
               else PRODUCTS_PER_TRAINED_LAYER, score_func=score_func)
    return y, load


# ---- what the last traced step's expert layers are, and what they carried
plan_recording = _plan.recording
#: returned by :func:`last_plan_summary` in place of the recorded plan.
#: Nothing in the program sets it; ``benchmark/tests/test_moe_small_buffer.py``
#: does, by this name, and only a ``benchmark`` PR may edit that file
_LAST_SUMMARY = None
_LOAD_SAMPLES = []
#: sampled dispatches whose loads are kept (the oldest go first)
LOAD_SAMPLES_KEPT = 256


def note_layer(**info):
    """One expert layer's plan, from :func:`topk_moe` (no-op outside a
    ``telemetry.plan.recording``)."""
    _plan.note(SCOPE_MOE, **info)


def note_compiled(executable):
    """Read from the compiled program of the step traced last what its
    expert layers' products became: ``grouped_products`` counts the
    grouped-matmul custom calls in its text, ``grouped_layers`` the
    expert layers they cover, each at the products it compiled: its own
    ``products_trained`` (:data:`PRODUCTS_PER_TRAINED_LAYER` for gated
    experts, 6 for ungated ones), twice where the layer has two sizes
    (``small_rows``), since the text holds both branches' calls and a
    step runs one's (a backend that multiplies densely and masks reads
    0).  Both stay None where the executable gives no text."""
    layers = _plan.last(SCOPE_MOE)
    if layers is None or not hasattr(executable, "as_text"):
        return
    text = executable.as_text()
    if text:
        products = n = len(_GROUPED_PRODUCT.findall(text))
        covered = 0
        for layer in layers:
            n -= layer.get("products_trained", PRODUCTS_PER_TRAINED_LAYER) \
                * (1 if layer.get("small_rows") is None else 2)
            if n < 0:
                break
            covered += 1
        _plan.annotate(SCOPE_MOE, grouped_products=products,
                       grouped_layers=covered)


def last_plan_summary():
    """Summary of the expert layers of the step traced last in this
    process (None before any): ``expert_layers``; ``token_sum_layers``, the
    layers whose ``token_sum`` is the kernel; per layer the router
    width, experts held and offset, experts a token, the experts' width,
    ``score_func`` (``"sigmoid"`` or ``"softmax"``),
    ``buffer_rows`` (the most rows of the sorted buffer its products run
    over, :func:`buffer_rows`: the bound on what a step may hold),
    ``small_rows`` (the rows a step runs over instead when what it holds
    fits them, :func:`small_buffer_rows`; None for a layer with one
    size), ``even_rows`` (the assignments even routing sends to the held
    experts), ``token_sum`` (what sums the rows of the buffer a fitting step
    runs over into token order, forward and in the dispatch gather's
    transpose: ``"kernel"``, ``mxtpu_moe_token_sum``; ``"scatter_add"``, the
    ``jax.numpy`` form; ``"gathers"``, the permutation of a buffer that
    holds every assignment) and ``products_trained`` (the grouped products
    a trained step runs for it: 9 for gated experts, 6 for ungated ones; a
    layer with two sizes compiles twice that); and, once that step's program is
    compiled,
    ``grouped_products`` and ``grouped_layers`` as
    :func:`note_compiled` reads them from it.  As
    ``analysis.fusion.last_plan_summary()``."""
    if _LAST_SUMMARY is not None:
        return _LAST_SUMMARY
    layers = _plan.last(SCOPE_MOE)
    if layers is None:
        return None
    return dict({"expert_layers": len(layers), "layers": layers,
                 "token_sum_layers": sum(
                     layer.get("token_sum") == "kernel" for layer in layers),
                 "grouped_products": None, "grouped_layers": None},
                **_plan.annotations(SCOPE_MOE))


def publish_load(loads):
    """Publish the expert layers' loads of a step the host has already
    waited for.  ``loads``: ``{layer: host array (held + 1,)}`` as
    :func:`topk_moe` returns them.  Sets the ``mxtpu_moe_*`` gauges and
    keeps the sample for :func:`load_samples`.  For a layer whose plan
    (:func:`last_plan_summary`) has ``small_rows``, the sample and the
    gauge ``mxtpu_moe_small_buffer`` say whether the step's held
    assignments fit them (1.0) or it ran at the bound (0.0): the layer's
    own comparison, made again here from the same counts."""
    import time
    from ..telemetry.registry import gauge
    sample = {}
    # the trainer's layers and the plan's are both in the graph's order
    plans = (last_plan_summary() or {}).get("layers", ())
    if len(plans) != len(loads):
        plans = [{}] * len(loads)
    for (layer, load), plan in zip(loads.items(), plans):
        load = np.asarray(load, np.float64)
        counts = [float(c) for c in load[:-1]]
        for i, c in enumerate(counts):
            gauge("mxtpu_moe_expert_assignments").labels(
                layer=layer, expert=str(i)).set(c)
        gauge("mxtpu_moe_tokens_unrouted").labels(layer=layer).set(
            float(load[-1]))
        sample[layer] = {"assignments": counts,
                         "tokens_unrouted": float(load[-1])}
        if plan.get("small_rows") is not None:
            fits = float(sum(counts) <= plan["small_rows"])
            gauge("mxtpu_moe_small_buffer").labels(layer=layer).set(fits)
            sample[layer]["small_buffer"] = fits
    _LOAD_SAMPLES.append((time.perf_counter(), sample))
    del _LOAD_SAMPLES[:-LOAD_SAMPLES_KEPT]


def load_samples():
    """``[(perf_counter time, {layer: {"assignments": [...],
    "tokens_unrouted", and for a layer with two sizes
    "small_buffer"}})]`` of the dispatches whose loads were published,
    oldest first."""
    return list(_LOAD_SAMPLES)


def init_moe_params(rng, d, ff, num_experts, scale=0.1):
    """Convenience init for tests/examples."""
    return {
        "router_w": (rng.randn(d, num_experts) * scale).astype("float32"),
        "w1": (rng.randn(num_experts, d, ff) * scale).astype("float32"),
        "b1": np.zeros((num_experts, ff), "float32"),
        "w2": (rng.randn(num_experts, ff, d) * scale).astype("float32"),
        "b2": np.zeros((num_experts, d), "float32"),
    }


def make_expert_mesh(n_devices, devices=None):
    """1-d ('expert',) mesh for expert parallelism."""
    from .mesh import make_1d_mesh
    return make_1d_mesh("expert", n_devices, devices)
