"""The sum of an expert-sorted buffer's rows into token order, as a Pallas
kernel (``mxtpu_moe_token_sum``).

:func:`mxnet_tpu.parallel.moe.topk_moe` sorts its assignments by expert
with a *stable* ``argsort``, so inside one expert's group the tokens
ascend strictly and none repeats.  The rows of group ``e`` that belong to
a block of ``block`` consecutive tokens are therefore ONE contiguous range
of at most ``block`` rows, and ``y[t] = sum_e weight[t, e] * rows[pos[t,
e]]`` needs no general scatter-add, which XLA:TPU walks row by row because
it has to assume that any two rows may hit one token.

The kernel's grid is (token blocks) x (held experts), the experts the
reduction.  For block ``b`` and expert ``e`` it copies the rows
``[lo, hi)`` of the buffer (``CHUNK`` rows a copy, as many copies as the
range needs, the next step's started before this step computes), builds
the 0/1 matrix ``pos[t, e] == lo + i``, multiplies it with the rows on the
MXU into float32 (a 0/1 matrix times bf16 rows is exact), scales by the
float32 column of weights of ``(t, e)`` and adds into a float32
accumulator of the output block, written once after the last expert.  Per
token the experts are added in ascending order, as a scatter-add over the
sorted rows adds them.

It asks for no VMEM beyond the default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: the custom call's name in the compiled step's text and in a trace
MOE_TOKEN_SUM = "mxtpu_moe_token_sum"
#: rows a copy from the buffer moves (two bf16 sublane tiles)
CHUNK = 32
#: rows of the window one product runs over (the MXU's side)
SUB = 128

_F32 = jnp.float32


def window_rows(block):
    """Rows of a step's window: a range of at most ``block`` rows that
    starts anywhere in a :data:`CHUNK`, in whole :data:`SUB`-row parts."""
    return -(-(block + CHUNK) // SUB) * SUB


def _kernel(lo_ref, hi_ref, start_ref, copies_ref, rows_hbm, pos_ref, *rest,
            block, held, weighted):
    # the ranges' arithmetic is done outside (:func:`token_sum`) and the body
    # keeps to ``lax``: every ``jax.numpy`` call here is a jitted helper that
    # is traced and lowered again in each kernel of each program of a step
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    if weighted:
        w_ref, out_ref, win, acc, sem = rest
    else:
        w_ref = None
        out_ref, win, acc, sem = rest
    b, e = pl.program_id(0), pl.program_id(1)
    step = b * held + e
    slot = lax.rem(step, 2)

    def copies(s, slot, act):
        """``act`` on each copy of step ``s``'s range into ``win[slot]``."""
        start = pl.multiple_of(start_ref[s], CHUNK)

        def copy(c, carry):
            at = pl.multiple_of(c * CHUNK, CHUNK)
            act(pltpu.make_async_copy(
                rows_hbm.at[pl.ds(start + at, CHUNK), :],
                win.at[slot, pl.ds(at, CHUNK), :], sem.at[slot]))
            return carry

        lax.fori_loop(0, copies_ref[s], copy, 0)

    @pl.when(step == 0)
    def _():
        copies(step, slot, lambda dma: dma.start())

    @pl.when(step + 1 < pl.num_programs(0) * held)
    def _():
        copies(step + 1, 1 - slot, lambda dma: dma.start())

    copies(step, slot, lambda dma: dma.wait())

    @pl.when(e == 0)
    def _():
        acc[...] = lax.full(acc.shape, 0.0, acc.dtype)

    lo, hi, start = lo_ref[step], hi_ref[step], start_ref[step]
    here = lax.broadcasted_iota(jnp.int32, pos_ref.shape, 1) == e
    # where in the window token t's row of expert e lies (negative: none)
    rel = lax.reduce_sum(lax.select(here, pos_ref[...], lax.full_like(
        pos_ref[...], 0)), (1,)).reshape(block, 1) - start
    if weighted:
        w = lax.reduce_sum(lax.select(here, w_ref[...], lax.full_like(
            w_ref[...], 0)), (1,)).reshape(block, 1)

    def part(p, carry):
        at = pl.multiple_of(p * SUB, SUB)
        rows = win[slot, pl.ds(at, SUB), :]
        # rows outside the range are another group's, an earlier step's or
        # past the held assignments: 0 x whatever lies there is not 0
        i = lax.broadcasted_iota(jnp.int32, rows.shape, 0) + (at + start)
        rows = lax.select((i >= lo) & (i < hi), rows, lax.full_like(rows, 0))
        col = lax.broadcasted_iota(jnp.int32, (block, SUB), 1) + at
        hit = (rel == col).astype(rows.dtype)                  # (block, SUB)
        got = lax.dot(hit, rows, preferred_element_type=_F32)
        acc[...] += got * w if weighted else got
        return carry

    lax.fori_loop(0, lax.div(copies_ref[step] * CHUNK + (SUB - 1), SUB),
                  part, 0)

    @pl.when(e == held - 1)
    def _():
        out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def token_sum(rows, pos, weight=None, *, block, interpret=False):
    """``y[t] = sum_e weight[t, e] * rows[pos[t, e]]`` over the ``e`` with
    ``pos[t, e] >= 0``, in float32, experts ascending, cast once to
    ``rows``' dtype.

    rows: ``(n_rows, d)``, the buffer sorted by expert (``n_rows`` a whole
    number of :data:`CHUNK`, ``d`` of 128 lanes).  pos: int32 ``(tokens,
    held)``, the buffer row of token ``t``'s assignment to held expert
    ``e``, negative where it has none; within a column it ascends with
    ``t`` over the rows of expert ``e``'s group (what a stable sort by
    expert gives), so a block of ``block`` tokens reads at most ``block``
    consecutive rows of a group.  weight: float32 ``(tokens, held)`` or
    None for unit weights.  ``tokens`` is a whole number of ``block``.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n_rows, d = rows.shape
    tokens, held = pos.shape
    if tokens % block or n_rows % CHUNK or block % 8:
        raise ValueError("token_sum: %d tokens in blocks of %d over %d rows"
                         % (tokens, block, n_rows))
    blocks = tokens // block
    grouped = pos.reshape(blocks, block, held)
    # the rows of expert e that block b reads: [lo, hi), copied from the
    # chunk that holds lo on, ``copies`` chunks of them
    lo = jnp.min(jnp.where(grouped >= 0, grouped, n_rows), axis=1).reshape(-1)
    hi = jnp.max(grouped, axis=1).reshape(-1) + 1
    start = lo // CHUNK * CHUNK
    copies = jnp.where(hi > lo, -(-(hi - start) // CHUNK), 0)
    column = lambda b, e, *ranges: (b, 0)
    operands = [rows, pos] + ([] if weight is None else [weight.astype(_F32)])
    kernel = functools.partial(_kernel, block=block, held=held,
                               weighted=weight is not None)
    how = {"interpret": True} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"))}
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(blocks, held),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)]
            + [pl.BlockSpec((block, held), column)] * (len(operands) - 1),
            out_specs=pl.BlockSpec((block, d), column),
            scratch_shapes=[pltpu.VMEM((2, window_rows(block), d), rows.dtype),
                            pltpu.VMEM((block, d), _F32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((tokens, d), rows.dtype),
        name=MOE_TOKEN_SUM, **how,
    )(*(r.astype(jnp.int32) for r in (lo, hi, start, copies)), *operands)
