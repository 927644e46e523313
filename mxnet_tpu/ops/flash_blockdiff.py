"""Flash attention under the block-diffusion training mask.

Block-diffusion training (BD3-LM, arXiv:2503.09573; SDAR,
arXiv:2510.06303) runs a decoder once over ``2L`` rows: a clean copy of
a document of ``L`` tokens (rows ``0 .. L-1``) and a noised copy (rows
``L .. 2L-1``), both at positions ``0 .. L-1``, in blocks of ``B``
positions.  Which keys a row sees, with ``i``, ``j`` positions:

* clean row ``i`` sees clean key ``j`` iff ``j // B <= i // B`` (causal
  over blocks, both ways inside a block);
* noised row ``i`` sees noised key ``j`` iff ``j // B == i // B`` (its
  own block's noised rows) and clean key ``j`` iff ``j // B < i // B``
  (the clean rows of earlier blocks only);
* no clean row sees a noised key.

One softmax runs over everything a row sees.  The mask needs ``L^2 +
L B`` of the ``4 L^2`` pairs, 25% of the score square; laid out clean
copy first it lies under the diagonal but for ``B - 1`` columns, and
what a Q block sees is a prefix of the clean half's K/V tiles plus, for
a noised block, its own ``block_q`` columns of the noised half.

The kernels ``mxtpu_flash_fwd_blockdiff`` / ``mxtpu_flash_bwd_blockdiff``
are the streamed kernels of :mod:`mxnet_tpu.ops.pallas_kernels` with the
windowed kernels' way of skipping: the streamed axis is as long as the
most tiles a Q block runs (the clean half's tiles and one more), step
``j`` of a Q block is its ``j``-th live tile, and the K/V index map
names that tile, held at the block's last one for the steps past it, so
that a tile no row of the block sees is neither multiplied (``pl.when``
on the static grid) nor fetched.  A clean tile that every row of the
block sees whole carries no mask; the clean tile a block's own position
crosses runs over the prefix of its columns the block's place on the
diagonal can see (:func:`pallas_kernels._causal_plan`'s ranges) under
the predicate; a noised block's own tile runs over the block's own
``block_q`` columns of it under ``j // B == i // B``.  The backward is
the same seen from a K/V tile: a clean tile meets the clean Q blocks
from its own position on and the noised ones after it, a noised tile the
Q blocks of its own rows; a Q block's dQ rows start on its first live
tile and leave on its last.  ``B`` divides both blocks, the K/V tile is
a whole number of Q blocks and the half a whole number of K/V tiles
(:func:`blocks_for`; any other shape takes the ``jnp`` path, which has
the same predicate: :func:`sees`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..telemetry import plan as _plan
from . import pallas_kernels as pk

FLASH_FWD_BLOCKDIFF = "mxtpu_flash_fwd_blockdiff"
FLASH_BWD_BLOCKDIFF = "mxtpu_flash_bwd_blockdiff"
#: ``jax.named_scope`` of an attention call under the block-diffusion mask
SCOPE_BDA = "mxtpu.block.bda"

#: Q rows and K/V tiles (tried largest first: the largest that divides
#: the half is taken) of a call.  Measured on the v5e at (1, 8192, 32 over
#: 4, 128) bf16, B 4 (``tools/flash_causal_bench.py --diffusion-block 4``,
#: PR 40; PERF.md section 5 has the table), forward + backward ms at Q
#: blocks of 512 rows: K/V tiles of 2048 / 1024 / 512 take 3.27 + 5.47 /
#: 3.37 + 6.33 / 5.07 + 8.09 (the long product wins again; all three
#: compute 31.25% of the square, the mask needs 25.02); 256 rows against
#: 1024 / 512 take 4.24 + 7.43 / 6.13 + 10.61 (28.1% computed, and slower:
#: a Q block's fixed work), 1024 rows against 1024 take 3.06 + 6.98
#: (37.5%).  The causal kernels over the same rows take 4.87 + 9.35 at
#: their own 512 x 2048 and compute 53.1%: the new ones 61% of their time
#: for 47% of their pairs.
_Q_ROWS = 512
_KV_TILES = (2048, 1024, 512, 256, 128)


def sees(t, block):
    """The mask as a ``(t, t)`` bool array, row by key, from the three
    sentences of the module's docstring: ``t = 2L`` rows, clean copy
    first, blocks of ``block`` positions."""
    half = t // 2
    r = jnp.arange(t)
    noised = r >= half
    blk = jnp.where(noised, r - half, r) // int(block)
    qn, kn = noised[:, None], noised[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return jnp.where(qn, jnp.where(kn, kb == qb, kb < qb),
                     jnp.logical_and(jnp.logical_not(kn), kb <= qb))


def check_shape(t, block):
    """``block`` as an int, or ValueError where ``t`` rows are not two
    copies of a whole number of blocks."""
    block = int(block)
    if block <= 0 or t % 2 or (t // 2) % block:
        raise ValueError(
            "diffusion_block %d over %d rows; the rows are a clean and a "
            "noised copy of one document (an even count), each a whole "
            "number of blocks" % (block, t))
    return block


def blocks_for(t, block):
    """(block_q, block_k) of the kernels for ``t = 2L`` rows in blocks of
    ``block``, or None where no pair tiles the mask: the half is a whole
    number of K/V tiles, a tile a whole number of Q blocks, and a Q block
    a whole number of (power-of-two) blocks."""
    half = t // 2
    for block_k in _KV_TILES:
        block_q = min(_Q_ROWS, block_k)
        if half % block_k == 0 and _tiles_the_mask(t, block, block_q, block_k):
            return block_q, block_k
    return None


def _tiles_the_mask(t, block, block_q, block_k):
    half = t // 2
    return block > 0 and block & (block - 1) == 0 and t % 2 == 0 \
        and half % block_k == 0 and block_k % block_q == 0 \
        and block_q % block == 0


# ---- the mask by tile: plain integers on the host, grid indices in a kernel

def _clean_tiles(noised, qrel, block_q, block_k, block):
    """How many of the clean half's K/V tiles, from the first on, hold a
    key that Q block ``qrel`` of its half sees (``noised``: 0 for a clean
    block, 1 for a noised one): the keys before ``(qrel + 1) * block_q``,
    less its own block's for a noised one."""
    reach = (qrel + 1) * block_q - block * noised
    return (reach + block_k - 1) // block_k


def _interior(noised, qrel, ki, block_q, block_k, block):
    """Every row of the Q block sees every key of clean tile ``ki``."""
    return (ki + 1) * block_k <= qrel * block_q + block * (1 - noised)


def tiles_run(t, block_q, block_k, block, plan):
    """``[(Q block, first row, K/V tile, first column, columns)]`` of
    every product the kernels run for one head, in absolute rows and
    columns of the ``t x t`` square: what the forward grid executes and,
    seen from the tiles, the backward's."""
    m, ranges = plan
    half_q, half_k = t // 2 // block_q, t // 2 // block_k
    out = []
    for qpos in range(2 * half_q):
        noised = int(qpos >= half_q)
        qrel = qpos - half_q * noised
        for ki in range(_clean_tiles(noised, qrel, block_q, block_k, block)):
            cols = block_k if _interior(noised, qrel, ki, block_q, block_k,
                                        block) else \
                next(c for lo, hi, c in ranges if lo <= qrel % m < hi)
            out.append((qpos, qpos * block_q, ki, ki * block_k, cols))
        if noised:
            out.append((qpos, qpos * block_q, half_k + qrel // m,
                        t // 2 + qrel * block_q, block_q))
    return out


def scores_computed_pct(t, block_q, block_k, block, plan):
    """Score elements the kernels compute over ``t * t`` a head, in
    percent (:func:`tiles_run`); the mask needs ``100 (L^2 + L B) / (2L)^2``
    (25.02 at L 4096, B 4), the causal kernels over the same rows would
    compute 53.1."""
    return 100.0 * sum(block_q * cols for *_x, cols in
                       tiles_run(t, block_q, block_k, block, plan)) / (t * t)


def _clean_tile_steps(noised, qrel, ki, live, block_q, block_k, block, plan,
                      step):
    """Clean tile ``ki`` against Q block ``qrel`` of its half, where
    ``live`` holds: ``step(None)`` where nothing in it is masked,
    ``step(mask, cols)`` where the block's own position crosses it, over
    the columns its place on the tile's diagonal can see."""
    from jax.experimental import pallas as pl

    m, ranges = plan
    shift = block * (1 - noised)
    interior = _interior(noised, qrel, ki, block_q, block_k, block)
    pl.when(jnp.logical_and(live, interior))(functools.partial(step, None))

    def mask(s):
        # row i sees the clean keys j < (i // B) * B + (B if clean else 0)
        row = qrel * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (s.shape[0], 1), 0)
        bound = jnp.bitwise_and(row, -block) + shift
        col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return jnp.where(col < bound, s, -jnp.inf)

    pk._diagonal_tile(
        jax.lax.rem(qrel, m) if m > 1 else 0, ranges,
        functools.partial(step, mask),
        on=jnp.logical_and(live, jnp.logical_not(interior)))


def _own_tile_steps(own, place, block_q, block_k, block, step):
    """A noised Q block against its own rows' keys, the ``block_q``
    columns at ``place`` of its K/V tile, where ``own`` holds: under
    ``j // B == i // B``, or whole where the Q block is one block."""
    from jax.experimental import pallas as pl

    def mask(s):
        row = jnp.bitwise_and(
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 0), -block)
        col = jnp.bitwise_and(
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 1), -block)
        return jnp.where(row == col, s, -jnp.inf)

    m = block_k // block_q
    for p in range(m):
        pl.when(own if m == 1 else jnp.logical_and(own, place == p))(
            functools.partial(
                step, mask if block < block_q else None,
                None if m == 1 else slice(p * block_q, (p + 1) * block_q),
                False))


def _q_block(qpos, half_q):
    """``(noised as 0 / 1, place in its half)`` of Q block ``qpos``."""
    noised = (qpos >= half_q).astype(jnp.int32)
    return noised, qpos - half_q * noised


def _kv_tile(qpos, j, half_q, half_k, block_q, block_k, block):
    """Step ``j`` of the forward's streamed axis at Q block ``qpos``:
    ``(K/V tile fetched, clean tiles the block runs)``.  The clean tiles
    first; then, for a noised block, its own rows' tile, held for the
    steps past it; a clean block holds its last clean tile."""
    noised, qrel = _q_block(qpos, half_q)
    n_clean = _clean_tiles(noised, qrel, block_q, block_k, block)
    own = half_k + qrel * block_q // block_k
    return jnp.where(noised == 1, jnp.where(j < n_clean, j, own),
                     jnp.minimum(j, n_clean - 1)), n_clean


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, block_q, block_k, block, n_q, plan):
    """:func:`pallas_kernels._flash_fwd_kernel` under the block-diffusion
    mask: grid (key/value head, Q block, step), the step a Q block's
    ``j``-th live tile (:func:`_kv_tile`)."""
    from jax.experimental import pallas as pl

    qpos, j = jax.lax.rem(pl.program_id(1), n_q), pl.program_id(2)
    noised, qrel = _q_block(qpos, n_q // 2)
    n_clean = _clean_tiles(noised, qrel, block_q, block_k, block)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step(mask, cols=None, empty_rows=True):
        # a noised block's first rows see no clean key at all: their
        # running maximum is -inf until the block's own tile
        pk._online_softmax_tile(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                                scale, mask, cols,
                                empty_rows and mask is not None)

    _clean_tile_steps(noised, qrel, j, j < n_clean, block_q, block_k, block,
                      plan, _step)
    _own_tile_steps(jnp.logical_and(noised == 1, j == n_clean),
                    jax.lax.rem(qrel, block_k // block_q), block_q, block_k,
                    block, _step)

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        pk._emit_softmax(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _q_of_step(ki, jq, half_q, half_k, block_q, block_k, block):
    """Step ``jq`` of the backward's streamed axis at K/V tile ``ki``:
    ``(Q block fetched along the folded group axis, noised, place in its
    half, live)``.  Each query head of the group takes ``2 half_q``
    steps.  A clean tile meets the clean Q blocks from its own position
    on, then the noised ones that see a key of it; a noised tile the Q
    blocks of its own rows; the steps left over hold the last block and
    are not live."""
    m, n_q = block_k // block_q, 2 * half_q
    head, u = jq // n_q, jax.lax.rem(jq, n_q)
    # a noised Q block of one block sees nothing of the tile it starts
    first_noised = ki * m + int(block_q == block)
    n_c, n_n = half_q - ki * m, half_q - first_noised
    clean_tile = ki < half_k
    own = (ki - half_k) * m
    qpos = jnp.where(
        clean_tile,
        jnp.where(u < n_c, ki * m + u,
                  half_q + first_noised + jnp.clip(u - n_c, 0, n_n - 1)),
        half_q + own + jnp.minimum(u, m - 1))
    qpos = jnp.clip(qpos, 0, n_q - 1)
    live = jnp.where(clean_tile, u < n_c + n_n, u < m)
    noised, qrel = _q_block(qpos, half_q)
    return head * n_q + qpos, noised, qrel, live


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, scale, block_q,
                block_k, block, n_q, plan):
    """:func:`pallas_kernels._flash_bwd_kernel` under the block-diffusion
    mask: grid (key/value head, K/V tile, step), the step one of the Q
    blocks the tile meets (:func:`_q_of_step`).  A Q block's dQ rows
    start on its first live tile (the clean half's first, or its own for
    a noised block that sees no clean key) and are emitted on its last
    (a clean block's diagonal tile, a noised block's own), after which no
    step names that block again."""
    from jax.experimental import pallas as pl

    ki, jq = pl.program_id(1), pl.program_id(2)
    half_q, half_k = n_q // 2, n_q * block_q // 2 // block_k
    qi, noised, qrel, live = _q_of_step(ki, jq, half_q, half_k, block_q,
                                        block_k, block)
    n_clean = _clean_tiles(noised, qrel, block_q, block_k, block)
    own = half_k + qrel * block_q // block_k
    first = jnp.where(jnp.logical_and(noised == 1, n_clean == 0), own, 0)
    last = jnp.where(noised == 1, own, n_clean - 1)

    @pl.when(jq == 0)
    def _init_kv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _step(mask, cols=None, _empty_rows=False):
        pk._bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_acc,
                     dk_acc, dv_acc, scale, qi, block_q, ki, first, mask,
                     cols)

    _clean_tile_steps(noised, qrel, ki, jnp.logical_and(live, ki < half_k),
                      block_q, block_k, block, plan, _step)
    _own_tile_steps(jnp.logical_and(live, ki >= half_k),
                    jax.lax.rem(qrel, block_k // block_q), block_q, block_k,
                    block, _step)

    @pl.when(jnp.logical_and(live, ki == last))
    def _emit_dq():
        dq_ref[0] = dq_acc[pl.ds(qi * block_q, block_q), :]

    @pl.when(jq == pl.num_programs(2) - 1)
    def _emit_kv():
        dk_ref[0] = dk_acc[...]
        dv_ref[0] = dv_acc[...]


_STATIC = ("interpret", "block_q", "block_k", "block", "plan")


@functools.partial(jax.jit, inline=True, static_argnames=_STATIC)
def _fwd_call(q, k, v, *, interpret, block_q, block_k, block, plan):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d = q.shape
    dv = v.shape[-1]
    group = pk._kv_group(q, k, v)
    bk = b * h // group
    n_q = t // block_q
    half_q, half_k = n_q // 2, t // 2 // block_k

    def kv_index(bh, qi, j):
        return bh, _kv_tile(jax.lax.rem(qi, n_q), j, half_q, half_k, block_q,
                            block_k, block)[0], 0

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d),
                          block_q=block_q, block_k=block_k, block=block,
                          n_q=n_q, plan=plan),
        grid=(bk, group * n_q, half_k + 1),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, j: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, dv), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda bh, qi, j: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, j: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bk, group * t, dv), q.dtype),
            jax.ShapeDtypeStruct((bk, group * t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name=FLASH_FWD_BLOCKDIFF,
    )(pk._fold_queries(q, group), pk._fold_heads(k), pk._fold_heads(v))
    return (pk._unfold_heads(out.reshape(b * h, t, dv), b, h),
            lse.reshape(b * h, t, 1))


@functools.partial(jax.jit, inline=True, static_argnames=_STATIC)
def _bwd_call(q, k, v, o, lse, g, *, interpret, block_q, block_k, block,
              plan):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d = q.shape
    dv = v.shape[-1]
    group = pk._kv_group(q, k, v)
    bk, hk = b * h // group, h // group
    n_q = t // block_q
    half_q, half_k = n_q // 2, t // 2 // block_k

    qt, kt, vt = pk._fold_queries(q, group), pk._fold_heads(k), \
        pk._fold_heads(v)
    dot = pk._fold_queries(g, group)
    delta = jnp.sum(dot.astype(jnp.float32)
                    * pk._fold_queries(o, group).astype(jnp.float32),
                    axis=-1, keepdims=True)
    lse = lse.reshape(bk, group * t, 1)

    def q_index(bh, ki, jq):
        return bh, _q_of_step(ki, jq, half_q, half_k, block_q, block_k,
                              block)[0], 0

    def kv_index(bh, ki, jq):
        return bh, ki, 0

    qblock = pl.BlockSpec((1, block_q, d), q_index)
    doblock = pl.BlockSpec((1, block_q, dv), q_index)
    rows = pl.BlockSpec((1, block_q, 1), q_index)
    kblock = pl.BlockSpec((1, block_k, d), kv_index)
    vblock = pl.BlockSpec((1, block_k, dv), kv_index)
    dq, dk_, dv_ = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=1.0 / math.sqrt(d),
                          block_q=block_q, block_k=block_k, block=block,
                          n_q=n_q, plan=plan),
        grid=(bk, t // block_k, group * n_q),
        in_specs=[qblock, kblock, vblock, doblock, rows, rows],
        out_specs=[qblock, kblock, vblock],
        out_shape=[jax.ShapeDtypeStruct((bk, group * t, d), jnp.float32),
                   jax.ShapeDtypeStruct((bk, t, d), jnp.float32),
                   jax.ShapeDtypeStruct((bk, t, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((group * t, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)],
        interpret=interpret,
        name=FLASH_BWD_BLOCKDIFF,
        **pk._vmem_params(pk._vmem_need(max(d, dv), block_q, block_k,
                                        group * t, q.dtype.itemsize)),
    )(qt, kt, vt, dot, lse, delta)
    return (pk._unfold_heads(dq.reshape(b * h, t, d), b, h).astype(q.dtype),
            pk._unfold_heads(dk_, b, hk).astype(k.dtype),
            pk._unfold_heads(dv_, b, hk).astype(v.dtype))


def _plan_of(q, block, blocks):
    """``(block_q, block_k, block, plan)`` of a call: ``blocks`` (a
    measurement's or a test's pair) or :func:`blocks_for`'s."""
    t = q.shape[1]
    block = check_shape(t, block)
    pair = tuple(blocks) if blocks is not None else blocks_for(t, block)
    if pair is None or not _tiles_the_mask(t, block, *pair):
        raise ValueError(
            "block-diffusion kernels: blocks %r do not tile %d rows in "
            "blocks of %d" % (pair, t, block))
    return int(pair[0]), int(pair[1]), block, pk._causal_plan(*pair)


def _note(op, q, v, block_q, block_k, block, plan, n_matmuls, n_tensors):
    """The call's record in the cost database and, inside a
    ``causal_plan_recording``, in the step's plan: the fields of
    :func:`pallas_kernels._note_kernel_cost`'s records and
    ``diffusion_block``; ``scores_computed_pct`` and ``flops`` count the
    tiles the kernel runs (:func:`tiles_run`)."""
    try:
        from ..telemetry import costdb
        b, t, h, dk = q.shape
        dv = v.shape[-1]
        pct = scores_computed_pct(t, block_q, block_k, block, plan)
        config = {"block_q": block_q, "block_k": block_k,
                  "n_k": t // block_k, "causal": False,
                  "causal_ranges": len(plan[1]), "scores_computed_pct": pct,
                  "window": 0, "group_parts": 1,
                  "tiles_per_q_block": t // 2 // block_k + 1,
                  "diffusion_block": block}
        _plan.note(pk.PLAN_FLASH, **config, kernel=op,
                   shape=tuple(int(n) for n in q.shape),
                   dk=int(dk), dv=int(dv))
        itemsize = jnp.dtype(q.dtype).itemsize
        costdb.note_kernel(
            op, [tuple(q.shape)], [str(q.dtype)],
            flops=float(n_matmuls[0] * dk + n_matmuls[1] * dv)
            * b * h * t * t * pct / 100.0,
            bytes_accessed=float(n_tensors[0] * dk + n_tensors[1] * dv)
            * b * t * h * itemsize, block_config=config)
    except MemoryError:  # pragma: no cover - never mask resource exhaustion
        raise
    except Exception:  # mxlint: allow-broad-except(kernel labeling is observability inside a jit trace; any failure must not fail the compile)
        pass


def fwd(q, k, v, block, interpret=False, blocks=None):
    """``(o, lse)`` of the forward kernel; ``blocks``: an explicit
    (block_q, block_k)."""
    block_q, block_k, block, plan = _plan_of(q, block, blocks)
    _note("flash_attention_fwd_blockdiff", q, v, block_q, block_k, block,
          plan, (2, 2), (2, 2))
    return _fwd_call(q, k, v, interpret=bool(interpret), block_q=block_q,
                     block_k=block_k, block=block, plan=plan)


def bwd(q, k, v, o, lse, g, block, interpret=False, blocks=None):
    """``(dq, dk, dv)`` of the backward kernel."""
    block_q, block_k, block, plan = _plan_of(q, block, blocks)
    _note("flash_attention_bwd_blockdiff", q, v, block_q, block_k, block,
          plan, (6, 4), (4, 4))
    return _bwd_call(q, k, v, o, lse, g, interpret=bool(interpret),
                     block_q=block_q, block_k=block_k, block=block, plan=plan)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_blockdiff(q, k, v, block, interpret=False, blocks=None):
    """Attention of ``(batch, 2L, heads, head_dim)`` rows, clean copy
    first, under the block-diffusion mask in blocks of ``block``; the
    Pallas kernels (``interpret``: on any backend)."""
    return fwd(q, k, v, block, interpret, blocks)[0]


def _vjp_fwd(q, k, v, block, interpret, blocks):
    o, lse = fwd(q, k, v, block, interpret, blocks)
    return o, (q, k, v, o, lse)


def _vjp_bwd(block, interpret, blocks, res, g):
    q, k, v, o, lse = res
    return bwd(q, k, v, o, lse, g, block, interpret, blocks)


flash_attention_blockdiff.defvjp(_vjp_fwd, _vjp_bwd)


def kernels_take(q, k, v, block):
    """The kernels run this call: some pair of blocks tiles its mask and
    the group's dQ rows fit one call's VMEM."""
    t = q.shape[1]
    return k.shape[1] == t and blocks_for(t, block) is not None \
        and pk._group_parts(t, max(q.shape[-1], v.shape[-1]),
                            pk._kv_group(q, k, v)) == 1
