"""Pallas TPU kernels for hot ops.

The reference's hand-written CUDA kernels (mshadow/cuDNN, SURVEY §2.2) map
to XLA for almost everything; Pallas covers the ops XLA can't fuse well.
First resident: block-wise flash attention — Q blocks stream through VMEM
against the K/V panel, softmax runs on the VPU, both matmuls hit the MXU.
Used single-chip; the sequence-parallel wrapper
(:mod:`mxnet_tpu.parallel.sequence`) rings K/V between chips and calls the
same math per block.

Exposed as the ``_contrib_FlashAttention`` operator (q, k, v) with layout
(batch, seq, heads, head_dim).  Backward is a second Pallas kernel
(custom_vjp): P is reconstituted from the forward's saved log-sum-exp
and the (T, T) matrix never touches HBM.  (Replacing the earlier
jnp-recompute backward was worth +11 MFU points on the d=1024 LM
benchmark, docs/perf.md.)

Length dispatch (round 5): sequences whose K/V panel fits one VMEM
block (T <= _BLOCK_K) run the single-panel kernels — the measured
fastest formulation at those lengths; longer sequences stream K/V in
blocks along an extra grid axis with online-softmax rescaling (fwd)
and a full-sequence VMEM dQ accumulator (bwd).  VMEM then scales
O(T*D) instead of the panel's O(T*D + block_q*T) working set with its
(block_q, T) f32 score tiles, so S=4096+ trains; the dQ accumulator
(T*D*4 bytes — 1 MB at T=4096, D=64) becomes the next wall around
T~64k.

Causal scores (PR 27): a Q block's products run over the static K/V
prefix it can see, not over the tile and a mask's zeros.  The Q blocks
on a K/V tile's diagonal (the panel route: all of a head's) are split
into :data:`_CAUSAL_RANGES` static ranges, and a block runs the one
long product of the whole-tile kernel over its range's columns: a
static slice of the block that is in VMEM already, one copy of the
body a range under ``pl.when`` on the static grid, same blocks, index
maps and pipeline (:func:`_causal_plan`).  On the streamed grid tiles
above the diagonal are skipped and tiles below it carry no mask.  At
4 ranges the panel kernels compute 62.5% of the square and take 23%
less time at (4, 2048, 32, 64); every formulation that gave up the
long product to skip more (round 4's dynamic ``fori_loop``, round 5's
two-pass grid and small-K-block grids) had LOST 10-15% on v5e, and a
``pallas_call`` a range loses on the backward's partial dK/dV
(docs/perf.md, PERF.md section 6).

A sliding window (PR 33): under ``window`` position ``t`` sees the keys
``t - window < j <= t``.  On the streamed grid a Q block runs only the
K/V tiles of its band: the streamed axis is as long as the most tiles a
block reaches, step ``j`` is tile ``first + j`` of the block's own live
range, and the K/V index map names that tile, held at the block's last
one for the steps past it, so that a tile under the band is neither
multiplied (``pl.when``, still the static grid) nor fetched (a step
that names the block in VMEM issues no copy).  The backward is the same
band seen from a K/V tile; a Q block's dQ rows start on its first live
tile and leave on its last.  Tiles inside the band carry no mask, the
diagonal tile keeps its prefix ranges, the tile the lower edge crosses
runs whole under both inequalities.  The kernels are
``mxtpu_flash_{fwd,bwd}_window``; a window that reaches the whole
prefix is the causal call itself, and the panel route masks only.
``tools/flash_causal_bench.py --window`` measured K/V tiles of 2048,
1024 and 512 and Q blocks of 128 to 1024 rows at (1, 8192, 32 over 4,
128) under a window of 2048 (:data:`_WINDOW_BLOCKS`; PERF.md section 5
has the table): a windowed call runs Q blocks of 512 rows against K/V
tiles of 1024.

Block selection (ISSUE 9, PR 35): both kernels consult the persistent
tuning cache first (:mod:`mxnet_tpu.autotune`, ``MXNET_TPU_TUNE_CACHE``)
and fall back to :func:`_flash_blocks` on miss — a tuned (block_q,
block_k) measured by ``tools/autotune.py`` wins over the hand-written
rule, and the dispatched choice stays queryable through the cost
database's kernel records.  The rule reads the call's shape: the K/V
tile is :func:`_blocks`' (2048 columns, or the largest divisor of the
length: the long product wins), and so is the 128-row Q block of a call
that is not causal; a causal call takes the largest Q block of 512, 256,
128 rows that divides the length and the K/V tile, leaves the tile's
diagonal four places (so the prefix ranges and the share of the square
computed stay those of 128 rows) and whose backward kernel needs no more
VMEM than a kernel may ask for (:func:`_vmem_need`: two score tiles and,
on the streamed route, the dQ accumulator of a whole group's rows).  A
Q block's fixed work is a third of its time at 128 rows and is paid once
a block whatever its rows (PERF.md section 6, PR 27 and PR 35).  A
backward kernel whose reckoned need passes Mosaic's scoped default asks
for its VMEM (:func:`_vmem_params`), and none that the default holds: a
call that asks costs the ops round it 0.15 ms.  At 512 x 2048 in
bfloat16 the panel backward is reckoned at 15 MiB of the default's 16
(the compiler's least limit is 15) and asks for nothing; the streamed
backward asks where its accumulator makes it (grouped queries, two lane
tiles a row, or 512 rows at 8192 positions); no forward asks (the
compiler's least is 4-13 MiB).  A windowed call
keeps :data:`_WINDOW_BLOCKS`; ``last_causal_plan()["q_block_rows"]`` is
the smallest Q block over a traced step's causal and windowed kernels.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .. import context as _context
from ..base import MXNetError
from ..telemetry import plan as _plan
from .registry import register

_BLOCK_Q = 128


def _attention_jnp(q, k, v, causal, window=0, diffusion_block=0):
    """Reference path (CPU / fallback / backward recompute).  Fewer
    key/value heads than query heads are repeated here, which only this
    path does: the kernels index them (:func:`_fold_queries`).  Under
    ``window`` (needs ``causal``) position ``t`` sees the keys ``t -
    window < j <= t``; under ``diffusion_block`` (not ``causal``) a row
    sees what :func:`flash_blockdiff.sees` says."""
    group = _kv_group(q, k, v)
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool))
        if window:
            mask = jnp.logical_and(
                mask, jnp.logical_not(jnp.tril(mask, -int(window))))
        s = jnp.where(mask, s, -jnp.inf)
    elif diffusion_block:
        from .flash_blockdiff import sees
        s = jnp.where(sees(s.shape[-1], diffusion_block), s, -jnp.inf)
    s = s - s.max(-1, keepdims=True)
    p = jnp.exp(s)
    p = p / p.sum(-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)


_BLOCK_K = 2048


def _causal_live(qi, ki, block_q, block_k):
    """This (qi, ki) tile has any unmasked entry: k_start <= q_end."""
    return ki * block_k <= qi * block_q + block_q - 1


def _causal_interior(qi, ki, block_q, block_k):
    """No entry of this (qi, ki) tile can be masked: k_end <= q_start."""
    return ki * block_k + block_k - 1 <= qi * block_q


def _causal_mask(s, row0, col0=None, window=0):
    """Scores ``s`` of rows ``row0 ...`` against columns ``col0 ...``
    (None: from the first), with what lies above the diagonal at -inf
    and, under ``window``, what lies under its lower edge too: row ``t``
    keeps the columns ``t - window < j <= t``."""
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if col0 is not None:
        col = col0 + col
    keep = row >= col
    if window:
        keep = jnp.logical_and(keep, col > row - window)
    return jnp.where(keep, s, -jnp.inf)


class _Ints:
    """``maximum`` / ``minimum`` of Python integers: the band's
    arithmetic below runs on the host with these and on a grid's indices
    with ``jax.numpy``."""
    maximum, minimum = staticmethod(max), staticmethod(min)


def _live_k_tiles(qpos, block_q, block_k, window, xp=_Ints):
    """``(first, last)`` K/V tile that holds a key of Q block ``qpos``'s
    band under ``window``: the keys ``q_start - window + 1 .. q_end``.
    Every tile between the two is live; the backward starts a Q block's
    dQ rows on ``first`` and emits them on ``last``."""
    first = xp.maximum(qpos * block_q - (window - 1), 0) // block_k
    return first, (qpos * block_q + block_q - 1) // block_k


def _live_q_blocks(ki, block_q, block_k, window, n_q, xp=_Ints):
    """The band seen from K/V tile ``ki``: ``(first, last)`` Q block with
    a row that sees one of its keys, the rows ``k_start .. k_end + window
    - 1`` of the head's ``n_q`` blocks."""
    last = (ki * block_k + block_k + window - 2) // block_q
    return ki * block_k // block_q, xp.minimum(last, n_q - 1)


def _band_tile(qpos, ki, block_q, block_k, window):
    """``(above, below)`` of a live tile of the band: it holds an entry
    above the diagonal (``k_end > q_start``) / under the window's lower
    edge (``k_start <= q_end - window``).  Neither: no mask; ``above``
    alone: the diagonal tile of the causal kernels, prefix ranges and
    all; ``below``: the whole tile under both inequalities."""
    return (ki * block_k + block_k - 1 > qpos * block_q,
            ki * block_k <= qpos * block_q + block_q - 1 - window)


def _window_tiles_per_q_block(t, block_q, block_k, window):
    """K/V tiles the Q block with the most of them runs: the extent of
    the windowed forward's streamed grid axis."""
    return max(last - first + 1 for first, last in (
        _live_k_tiles(qpos, block_q, block_k, window)
        for qpos in range(t // block_q)))


def _window_q_blocks_per_tile(t, block_q, block_k, window):
    """Q blocks of one head the K/V tile with the most of them meets: the
    windowed backward's streamed grid axis is ``group`` times this."""
    return max(last - first + 1 for first, last in (
        _live_q_blocks(ki, block_q, block_k, window, t // block_q)
        for ki in range(t // block_k)))


#: Ranges a diagonal tile's Q blocks are split into under ``causal``
#: (:func:`_causal_plan`).  Measured on the v5e at 1, 2, 4, 8, 16
#: (``tools/flash_causal_bench.py``, PERF.md section 6): forward +
#: backward 6.66 / 5.55 / 5.10 / 5.03 / 5.43 ms at (4, 2048, 32, 64) and
#: 19.97 / 18.97 / 18.44 / 18.67 / 19.19 ms at (1, 8192, 32 over 8, 64):
#: every range is one more copy of the body, and a Q block's fixed
#: work does not shrink with its columns.
_CAUSAL_RANGES = 4


def _causal_plan(block_q, block_k, ranges=None):
    """Under ``causal``, which columns of the K/V tile on its diagonal a
    Q block multiplies: ``(m, ((lo, hi, cols), ...))``.  A K/V tile of
    ``block_k`` columns has ``m = block_k // block_q`` Q blocks on its
    diagonal (the panel route: all of a head's); the one at place ``j``
    can see ``(j + 1) * block_q`` of its columns.  The places are split
    into at most ``ranges`` static ranges (default
    :data:`_CAUSAL_RANGES`), and a Q block at ``lo <= j < hi`` runs its
    products over the first ``cols = hi * block_q`` columns: a static
    slice of the tile in VMEM, so each range is the same long product
    as the whole tile, only shorter.  Executed share of a diagonal
    tile: ``(S + 1) / 2S`` at S even ranges; 1 range is the whole tile.
    A ``block_k`` that ``block_q`` does not divide (a tuned pair may be
    any two divisors of ``t``) keeps its diagonal tiles whole."""
    if block_k % block_q:
        return 1, ((0, 1, block_k),)
    m = block_k // block_q
    s = max(1, min(_CAUSAL_RANGES if ranges is None else int(ranges), m))
    bounds = sorted({-(-i * m // s) for i in range(s + 1)})
    return m, tuple((lo, hi, hi * block_q)
                    for lo, hi in zip(bounds[:-1], bounds[1:]))


def _scores_computed_pct(t, block_q, block_k, plan, window=0):
    """Score elements the kernels compute under ``plan``, over ``t * t``
    a head, in percent: interior tiles whole, diagonal tiles by their
    range's columns, tiles above the diagonal not at all; under
    ``window`` (the streamed route's) a Q block's live tiles alone, the
    ones on the lower edge whole."""
    m, ranges = plan
    window = window or t            # the whole prefix: the causal half
    done = 0
    for qi in range(t // block_q):
        first, last = _live_k_tiles(qi, block_q, block_k, window)
        for ki in range(first, last + 1):
            above, below = _band_tile(qi, ki, block_q, block_k, window)
            done += next(c for lo, hi, c in ranges if lo <= qi % m < hi) \
                if above and not below else block_k
    return 100.0 * done * block_q / (t * t)


def _diagonal_tile(j, ranges, tile, on=None):
    """Run ``tile(cols)`` for the static range of ``ranges`` that holds
    ``j``, the Q block's place on its K/V tile's diagonal
    (:func:`_causal_plan`), where ``on`` (None: everywhere) holds.  One
    copy of the tile's body a range, under ``pl.when`` on the static
    grid: the blocks, their index maps and so the pipeline are those of
    the whole-tile kernel."""
    from jax.experimental import pallas as pl

    for lo, hi, cols in ranges:
        conds = ([] if on is None else [on]) \
            + ([j >= lo] if lo else []) \
            + ([j < hi] if hi < ranges[-1][1] else [])
        if conds:
            pl.when(functools.reduce(jnp.logical_and, conds))(
                functools.partial(tile, cols))
        else:
            tile(cols)


def _causal_tiles(qpos, ki, block_q, block_k, plan, step):
    """The streaming kernels' tile (``qpos``, ``ki``) under ``causal``:
    ``step(False)`` where nothing in it can be masked, ``step(True,
    cols)`` on the diagonal over the columns the Q block's range can
    see, nothing above it."""
    from jax.experimental import pallas as pl

    m, ranges = plan
    pl.when(_causal_interior(qpos, ki, block_q, block_k))(
        functools.partial(step, False))
    diagonal = jnp.logical_and(
        _causal_live(qpos, ki, block_q, block_k),
        jnp.logical_not(_causal_interior(qpos, ki, block_q, block_k)))
    j = jax.lax.rem(qpos, m) if m > 1 else 0
    _diagonal_tile(j, ranges, functools.partial(step, True), on=diagonal)


def _prefix(ref, cols, *lead):
    """Index of the first ``cols`` rows of ``ref`` under the leading
    indices ``lead``; ``cols`` None or all of them: the index the
    whole-tile kernels use; a ``slice``: those rows."""
    if isinstance(cols, slice):
        return lead + (cols,)
    if cols is None or cols == ref.shape[len(lead)]:
        return lead or Ellipsis
    return lead + (slice(0, cols),)


# Device names of the kernels: the ``name=`` of each ``pallas_call``
# below, which XLA makes the custom call's instruction name — what a
# profiler trace's "XLA Ops" line and its readers find them by.
FLASH_FWD_PANEL = "mxtpu_flash_fwd_panel"


def _q_block_pos(qi, n_q):
    """Position of Q block ``qi`` inside its own head's sequence.  With
    grouped queries the ``group`` query heads that read one key/value
    head lie one after another along the kernel's Q axis (``n_q`` blocks
    each); ``n_q`` None is one query head a key/value head, where the
    block index is the position."""
    return qi if n_q is None else jax.lax.rem(qi, n_q)


def _flash_fwd_panel_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                  block_q, n_q=None, plan=None, window=0):
    """One Q block against the K/V panel; under ``causal`` against the
    panel's prefix that its range can see (``plan``:
    :func:`_causal_plan`'s, None where not causal); a ``window`` is
    masked, nothing more is skipped for it."""
    from jax.experimental import pallas as pl

    qi = _q_block_pos(pl.program_id(1), n_q)

    def tile(cols=None):
        q = q_ref[0].astype(jnp.float32)             # (block_q, D)
        k = k_ref[_prefix(k_ref, cols, 0)].astype(jnp.float32)   # (cols, D)
        v = v_ref[_prefix(v_ref, cols, 0)].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi * block_q, window=window)
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = p.sum(axis=-1, keepdims=True)
        o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) / l
        o_ref[0] = o.astype(o_ref.dtype)
        # log-sum-exp per query row ((block_q, 1) — the trailing unit dim
        # keeps the block TPU-tileable): the backward kernel reconstitutes
        # the normalized p = exp(s - lse) without a second softmax pass
        lse_ref[0] = m + jnp.log(l)

    if causal:
        _diagonal_tile(qi, plan[1], tile)
    else:
        tile()


FLASH_FWD_STREAM = "mxtpu_flash_fwd_stream"
FLASH_FWD_WINDOW = "mxtpu_flash_fwd_window"


def _online_softmax_tile(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, scale,
                         mask=None, cols=None, empty_rows=False):
    """One K/V tile of the streamed forward: the Q block's scores against
    the tile's first ``cols`` columns (None: all) under ``mask`` (None:
    none), folded into the running (m, l, acc).  ``empty_rows``: a row
    may have no column left in this tile and in none before it (the
    window's lower edge on a Q block's first tile), so its running
    maximum is still -inf and is kept out of the exponents."""
    q = q_ref[0].astype(jnp.float32)             # (bq, D)
    k = k_ref[_prefix(k_ref, cols, 0)].astype(jnp.float32)   # (cols, D)
    v = v_ref[_prefix(v_ref, cols, 0)].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = mask(s)
    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    m_exp = jnp.where(m_new == -jnp.inf, 0.0, m_new) if empty_rows else m_new
    alpha = jnp.exp(m_prev - m_exp)
    p = jnp.exp(s - m_exp)
    l_ref[...] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *, scale, causal,
                      block_q, block_k, n_q=None, plan=None):
    """Online-softmax forward: K/V stream through VMEM in blocks along
    the innermost grid axis; the running (m, l, acc) row statistics
    live in VMEM scratch.  Under ``causal`` a tile above the diagonal
    is skipped, a tile below it runs with no mask (nothing in it can
    be masked), and the tile on the diagonal runs over the prefix of
    its columns that the Q block's range can see (``plan``:
    :func:`_causal_plan`) — all on the STATIC grid via pl.when, which
    keeps the Mosaic pipeline intact (a dynamic-trip-count fori_loop
    formulation measured 10 MFU points SLOWER in round 4,
    docs/perf.md)."""
    from jax.experimental import pallas as pl

    qi, ki = _q_block_pos(pl.program_id(1), n_q), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step(masked, cols=None):
        _online_softmax_tile(
            q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, scale,
            (lambda s: _causal_mask(s, qi * block_q, ki * block_k))
            if masked else None, cols)

    if causal:
        _causal_tiles(qi, ki, block_q, block_k, plan, _step)
    else:
        _step(False)

    @pl.when(ki == nk - 1)
    def _done():
        _emit_softmax(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _emit_softmax(o_ref, lse_ref, acc_ref, m_ref, l_ref):
    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
    # log-sum-exp per query row ((bq, 1); the trailing unit dim
    # keeps the block TPU-tileable): the backward reconstitutes
    # p = exp(s - lse) without a second softmax pass
    lse_ref[0] = m_ref[...] + jnp.log(l)


def _band_tiles(qpos, ki, live, block_q, block_k, window, plan, step):
    """The windowed kernels' tile (``qpos``, ``ki``), where ``live``
    holds (the tile is one of the Q block's band): ``step(None)`` where
    nothing in it is masked, ``step(causal mask, cols)`` on the diagonal
    as :func:`_causal_tiles` has it, ``step(band mask, None, True)`` over
    the whole tile where the window's lower edge crosses it."""
    from jax.experimental import pallas as pl

    m, ranges = plan
    above, below = _band_tile(qpos, ki, block_q, block_k, window)
    inside = jnp.logical_and(live, jnp.logical_not(below))
    pl.when(jnp.logical_and(inside, jnp.logical_not(above)))(
        functools.partial(step, None))
    _diagonal_tile(
        jax.lax.rem(qpos, m) if m > 1 else 0, ranges, functools.partial(
            step, lambda s: _causal_mask(s, qpos * block_q, ki * block_k)),
        on=jnp.logical_and(inside, above))
    pl.when(jnp.logical_and(live, below))(functools.partial(
        step, lambda s: _causal_mask(s, qpos * block_q, ki * block_k, window),
        None, True))


def _flash_fwd_window_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                             acc_ref, m_ref, l_ref, *, scale, block_q,
                             block_k, window, n_q, plan):
    """:func:`_flash_fwd_kernel` under a sliding window.  The streamed
    axis is as long as the most tiles a Q block's band reaches
    (:func:`_window_tiles_per_q_block`), step ``j`` is tile ``first +
    j`` of the block's own live range (:func:`_live_k_tiles`), and the
    K/V index map names that tile, held at ``last`` for the steps past
    it: they compute nothing and, naming the block that is in VMEM,
    fetch nothing."""
    from jax.experimental import pallas as pl

    qpos, j = jax.lax.rem(pl.program_id(1), n_q), pl.program_id(2)
    first, last = _live_k_tiles(qpos, block_q, block_k, window, jnp)
    ki = first + j

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step(mask, cols=None, empty_rows=False):
        _online_softmax_tile(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                             scale, mask, cols, empty_rows)

    _band_tiles(qpos, ki, ki <= last, block_q, block_k, window, plan, _step)

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        _emit_softmax(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _fold_heads(x):
    b, t, h, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, t, d)


def _unfold_heads(x, b, h):
    bh, t, d = x.shape
    return jnp.transpose(x.reshape(b, h, t, d), (0, 2, 1, 3))


def _kv_group(q, k, v):
    """How many query heads read one key/value head (1: as many
    key/value heads as query heads).  Query head ``h`` reads key/value
    head ``h // group``.  ``q`` and ``k`` share one head width, which
    ``v`` need not have (latent attention: scores over 192, values of
    128); the error says which of head count and widths is at fault."""
    hq, hk = q.shape[2], k.shape[2]
    shapes = "(q %s, k %s, v %s)" % (tuple(q.shape), tuple(k.shape),
                                     tuple(v.shape))
    if q.shape[-1] != k.shape[-1]:
        raise ValueError(
            "flash attention: query heads are %d wide and key heads %d "
            "%s; the score needs one width" % (q.shape[-1], k.shape[-1],
                                               shapes))
    if k.shape[:-1] != v.shape[:-1]:
        raise ValueError(
            "flash attention: keys and values differ in more than their "
            "head width %s; they share batch, positions and heads" % shapes)
    if hk <= 0 or hq % hk:
        raise ValueError(
            "flash attention: %d query heads cannot share %d key/value "
            "heads %s; the query heads must be a whole multiple of the "
            "key/value heads" % (hq, hk, shapes))
    return hq // hk


def _fold_queries(x, group):
    """(B, T, H, ...) -> (B*H/group, group*T, ...): the query heads of
    one key/value head one after another along the kernel's Q axis.  A
    reshape of :func:`_fold_heads`' result (head ``h`` is rows
    ``(h % group) * T ...`` of key/value head ``h // group``), so the
    kernels see K/V once a group and no copy of them is made."""
    y = _fold_heads(x)
    if group == 1:
        return y
    bh, t, d = y.shape
    return y.reshape(bh // group, group * t, d)


def _blocks(t):
    """The built-in block heuristic (the tuning-cache fallback)."""
    block_q = min(_BLOCK_Q, t)
    # K blocks as long as VMEM allows: long MXU contractions beat the
    # causal-skip savings on this chip (measured, docs/perf.md) — the
    # panel only streams once T outgrows the VMEM budget
    block_k = min(_BLOCK_K, t)
    if t % block_k:
        # ADVICE r5 perf cliff: t not a _BLOCK_K multiple used to
        # collapse straight to block_q, streaming t/128 tiny K blocks
        # (t=3200 -> 25).  Take the largest block_q-multiple divisor of
        # t that still fits the VMEM budget instead (3200 -> 5x640).
        block_k = block_q                  # t is a block_q multiple here
        m = 2 * block_q
        while m <= min(_BLOCK_K, t):
            if t % m == 0:
                block_k = m
            m += block_q
    return block_q, block_k


#: Rows a causal call's Q block may have, largest first.  A Q block's
#: fixed work (the grid step, the Q / dO / dQ blocks: 0.36 us forward and
#: 0.72 us backward of 1.13 / 2.12 us at 128 rows, PR 27) is paid once a
#: block whatever its rows; 1024 rows are out (the backward spills: 17.7
#: ms where 512 rows take 5.5, PR 33).  PERF.md section 6 (PR 35) has the
#: table that decided it, four shapes by three blocks.
_Q_BLOCKS = (512, 256, 128)
#: the most rows of a backward Q block where BOTH head widths take two lane
#: tiles a row (GLM-4.7-Flash's 256 / 256): at 512 rows the kernel spills,
#: 10.12 ms where 256 rows take 3.03 at (1, 4096, 20, 256 / 256), while the
#: forward wants its 512 (1.69 ms against 2.19; PERF.md section 5, PR 44).
#: 192 / 128 (Kimi Linear's) keeps 512 rows: 15.15 ms against 16.70 at 128
_BWD_ROWS_TWO_TILES = 256


def _flash_blocks(t, dk, dv=None, group=1, causal=False, backward=False):
    """(block_q, block_k) of a call from its shape: the heuristic the
    tuning cache falls back on.  ``block_k`` is :func:`_blocks`' (the
    long product wins), and so is the Q block of a call that is not
    causal.  A causal call takes the largest Q block of
    :data:`_Q_BLOCKS` that divides ``t``, leaves a K/V tile's diagonal
    at least :data:`_CAUSAL_RANGES` places (so :func:`_causal_plan`
    keeps its ranges and the share of the square computed is that of
    128 rows) and whose backward kernel, the larger of the two, needs
    no more VMEM than it may ask for (:func:`_vmem_need`; its dQ
    accumulator holds ``group * t`` rows on the streamed route, a part
    of the group's where the call runs in parts: :func:`_group_parts`);
    a length none of them suits keeps :func:`_blocks`' own.  Forward and
    backward (``backward``) take the same pair but where both widths
    pass one lane tile: there the backward stops at
    :data:`_BWD_ROWS_TWO_TILES` rows.  A windowed call:
    :func:`_window_blocks`."""
    block_q, block_k = _blocks(t)
    if not causal:
        return block_q, block_k
    d = max(dk, dv or dk)
    dq_rows = group // _group_parts(t, d, group) * t if t > block_k else 0
    most = _BWD_ROWS_TWO_TILES if backward and min(dk, dv or dk) > 128 \
        else _Q_BLOCKS[0]
    for rows in _Q_BLOCKS:
        if block_q < rows <= most and t % rows == 0 and block_k % rows == 0 \
                and block_k // rows >= _CAUSAL_RANGES \
                and _vmem_request(_vmem_need(d, rows, block_k,
                                             dq_rows)) <= _VMEM_MAX:
            return rows, block_k
    return block_q, block_k


def _group_parts(t, d, group):
    """In how many equal parts a streamed backward call runs the ``group``
    query heads of a key/value head: the fewest whose dQ accumulator
    (``group / parts * t`` float32 rows) the kernel may ask VMEM for
    (:func:`_vmem_need` at :func:`_blocks`' own pair, the least a call can
    take).  1 on the panel route, which has no accumulator, and for every
    grouping up to 8 x 8192 x 128; 16 query heads a key/value head at
    8192 x 128 would need 74 MiB and ask for 111, over :data:`_VMEM_MAX`,
    and run as two calls of 8."""
    block_q, block_k = _blocks(t)
    if t <= block_k:
        return 1
    for parts in range(1, group + 1):
        if group % parts == 0 and _vmem_request(_vmem_need(
                d, block_q, block_k, group // parts * t)) <= _VMEM_MAX:
            return parts
    return group


def _select_blocks(op, q, causal, v=None, group=1):
    """Block selection for one flash kernel instantiation: the
    persistent tuning cache first (``mxnet_tpu.autotune``, keyed by
    (op, q shape, dtype, backend, causal) — emits the cache hit/miss
    metrics and a ``tune_lookup`` flight event), the
    :func:`_flash_blocks` rule on miss/off/invalid (``v``: values of a
    width of their own; ``group``: query heads a key/value head).  A
    cached config only wins when it tiles this sequence exactly — a
    corrupt or stale entry degrades to the heuristic, never to a
    compile error."""
    t = q.shape[1]
    block_q, block_k = _flash_blocks(
        t, q.shape[-1], None if v is None else v.shape[-1], group, causal,
        backward=op.endswith("_bwd"))
    try:
        from .. import autotune
        cfg = autotune.kernel_config(
            op, [tuple(q.shape)], [str(q.dtype)],
            extra={"causal": bool(causal)})
    except MemoryError:  # pragma: no cover - never mask resource exhaustion
        raise
    except Exception:  # mxlint: allow-broad-except(the tuning-cache lookup is advisory; any failure must fall back to the heuristic, never fail the trace)
        cfg = None
    if cfg:
        try:
            bq = int(cfg.get("block_q", block_q))
            bk = int(cfg.get("block_k", block_k))
            if bq > 0 and bk > 0 and t % bq == 0 and t % bk == 0:
                return bq, bk
        except (TypeError, ValueError):
            pass
    return block_q, block_k


def _note_kernel_cost(op, q, block_q, block_k, causal, n_matmuls,
                      n_tensors, plan=None, v=None, window=0, group_parts=1):
    """Label this kernel instantiation's chosen block shapes in the
    cost database (telemetry.costdb) so block-size cliffs — e.g. the
    2176-length 17-tiny-K-blocks fallback ADVICE flagged — become
    queryable by (op, shape).  ``n_tensors``: how many (B, T, H, D)
    sized tensors the kernel moves (HBM traffic estimate — the
    backward touches twice the forward's).  With ``v`` (values of a
    width of their own) ``n_matmuls`` and ``n_tensors`` are pairs,
    ``(over the query/key width, over the value width)``: the forward's
    score product and Q, K run over ``dk``, its value product and V, O
    over ``dv``.  ``plan``: the kernel's
    :func:`_causal_plan` (None where not causal); the record says into
    how many static ranges a diagonal tile's Q blocks are split
    (``causal_ranges``) and which share of a head's ``t * t`` scores
    the kernel computes (``scores_computed_pct``), and a causal kernel
    joins :func:`last_causal_plan`.  ``window`` (0: none): the window a
    streamed kernel skips tiles by; its record is kept apart
    (``<op>_window``), says the window and the most K/V tiles a Q block
    runs (``tiles_per_q_block``), and counts its share and its ``flops``
    over the tiles of the band it executes, not over ``t * t``.
    ``group_parts``: in how many calls a streamed backward runs the query
    heads of a key/value head (:func:`_group_parts`; 1: one call).
    Host-side, once per compile; swallowed on failure (observability
    must not fail the trace)."""
    try:
        from ..telemetry import costdb
        b, t, h, dk = q.shape
        dv = dk if v is None else v.shape[-1]

        def over_widths(n):
            return n * dk if v is None else n[0] * dk + n[1] * dv

        flops = float(over_widths(n_matmuls)) * b * h * t * t
        itemsize = jnp.dtype(q.dtype).itemsize
        bytes_ = float(over_widths(n_tensors)) * b * t * h * itemsize
        config = {"block_q": int(block_q), "block_k": int(block_k),
                  "n_k": int(t // block_k), "causal": bool(causal),
                  "causal_ranges": len(plan[1]) if plan else 1,
                  "scores_computed_pct": _scores_computed_pct(
                      t, block_q, block_k, plan, window) if plan else 100.0,
                  "window": int(window), "group_parts": int(group_parts),
                  "tiles_per_q_block": _window_tiles_per_q_block(
                      t, block_q, block_k, window) if window
                  else int(t // block_k)}
        if window:
            op += "_window"
            flops *= config["scores_computed_pct"] / 100.0
        if plan:
            _plan.note(PLAN_FLASH, **config, kernel=op,
                       shape=tuple(int(n) for n in q.shape),
                       dk=int(dk), dv=int(dv))
        costdb.note_kernel(
            op, [tuple(q.shape)], [str(q.dtype)], flops=flops,
            bytes_accessed=bytes_, block_config=config)
    except MemoryError:  # pragma: no cover - never mask resource exhaustion
        raise
    except Exception:  # mxlint: allow-broad-except(kernel labeling is observability inside a jit trace; any failure must not fail the compile)
        pass


#: the recorder's scope of the causal, windowed and block-diffusion flash
#: kernels of a traced step, forward and backward (``telemetry.plan``)
PLAN_FLASH = "mxtpu.block.flash"
causal_plan_recording = _plan.recording


def last_causal_plan():
    """What the causal flash kernels of the step traced last in this
    process compute (None before any): per kernel its name, q shape,
    blocks, ``causal_ranges``, ``scores_computed_pct``, ``window`` and
    ``tiles_per_q_block`` as its cost database record has them
    (:func:`_note_kernel_cost`), and the largest of the first two over
    the step's kernels.  50 plus half a Q block's share is what the
    mask leaves; 100 is the whole square.  ``q_block_rows``: the
    smallest Q block over the step's causal and windowed kernels (512
    where :func:`_flash_blocks` engaged on every one of them).
    ``window_layers``: the
    forward kernels among them that skip tiles by a sliding window
    (``flash_attention_fwd_window``; a windowed layer on the panel
    route, which masks only, is not one), and
    ``window_scores_computed_pct`` the largest share over the windowed
    kernels (None without any).  ``diffusion_layers``: the forward
    kernels under the block-diffusion mask
    (``flash_attention_fwd_blockdiff``, :mod:`.flash_blockdiff`; their
    entries carry ``diffusion_block``, and a layer on the ``jnp`` path is
    not one), and ``diffusion_scores_computed_pct`` the largest share of
    the ``2L x 2L`` square over them (None without any; the mask needs
    25.02 at L 4096, B 4).  As ``moe.last_plan_summary()``."""
    kernels = _plan.last(PLAN_FLASH)
    if kernels is None:
        return None
    windowed = [k for k in kernels if k["window"]]
    diffusion = [k for k in kernels if k.get("diffusion_block")]
    return {
        "kernels": kernels,
        "causal_ranges": max(k["causal_ranges"] for k in kernels),
        "scores_computed_pct": max(k["scores_computed_pct"]
                                   for k in kernels),
        "q_block_rows": min(k["block_q"] for k in kernels),
        "window_layers": sum(
            k["kernel"] == "flash_attention_fwd_window" for k in windowed),
        "window_scores_computed_pct": max(
            (k["scores_computed_pct"] for k in windowed), default=None),
        "diffusion_layers": sum(
            k["kernel"] == "flash_attention_fwd_blockdiff"
            for k in diffusion),
        "diffusion_scores_computed_pct": max(
            (k["scores_computed_pct"] for k in diffusion), default=None)}


def _window_of(window, causal, t):
    """The window the kernels honour: 0 (none) for one that reaches the
    whole prefix, so that such a call IS the causal call."""
    window = int(window)
    if window < 0 or (window and not causal):
        raise ValueError("flash attention: window %d; a window is a positive "
                         "count of keys and needs causal" % window)
    return 0 if window >= t else window


#: (block_q, block_k) of a windowed call on the streamed route, where the
#: sequence is a whole number of such K/V tiles.  Measured on the v5e at
#: (1, 8192, 32 over 4, 128) bf16 under a window of 2048
#: (``tools/flash_causal_bench.py --window 2048``, PERF.md section 5),
#: forward + backward ms: K/V tiles of 2048 / 1024 / 512 at 128 rows a Q
#: block 11.50 / 13.54 / 16.69 (the long product wins again, though 512
#: computes 25% of the square and 2048 34%); at 256 rows 9.16 / 8.94 /
#: 11.35; at 512 rows 8.72 / 7.91 / 9.72; at 1024 rows 20.91 / 8.36 /
#: 10.73: a Q block's fixed work is a third of its time at 128 rows (PR
#: 27), and the band's steps are few.
_WINDOW_BLOCKS = (512, 1024)


def _window_blocks(t):
    """(block_q, block_k) of a windowed call: :data:`_WINDOW_BLOCKS` on
    the streamed route, the heuristic's up to one K/V panel (which masks
    and skips nothing) and for a length those tiles do not divide."""
    block_q, block_k = _blocks(t)
    if t > block_k and t % _WINDOW_BLOCKS[1] == 0:
        return _WINDOW_BLOCKS
    return block_q, block_k


def _stream_window(window, t, block_k):
    """The window a streamed call skips tiles by; the panel route
    (``t == block_k``) masks and skips nothing."""
    return window if t // block_k > 1 else 0


def _flash_attention_fwd_pallas(q, k, v, causal, interpret,
                                blocks=None, ranges=None, window=0):
    """q/k: (B, T, H, D), v: (B, T, Hk, Dv) -> (o (B, T, H, Dv), lse
    (BH, T, 1) f32); the scale is ``D ** -0.5``.
    ``blocks``: explicit (block_q, block_k) override (the autotuner
    measures candidates through it); default consults the tuning
    cache, then the heuristic (a windowed call: :func:`_window_blocks`).
    ``ranges``: explicit count of causal ranges (measurements and tests;
    default :func:`_causal_plan`'s).  ``window``: position ``t`` sees
    the keys ``t - window < j <= t`` (0 or ``>= T``: all before it)."""
    window = _window_of(window, causal, q.shape[1])
    block_q, block_k = blocks if blocks is not None else \
        _window_blocks(q.shape[1]) if window else \
        _select_blocks("flash_attention_fwd", q, causal, v,
                       _kv_group(q, k, v))
    assert q.shape[1] % block_q == 0, \
        "seq length must be a multiple of the Q block"
    plan = _causal_plan(block_q, block_k, ranges) if causal else None
    # 2 matmuls at 2*t*t*width flops each: QK^T over the query/key
    # width, PV over the value width; traffic: q, k, v read + o written
    # (lse is negligible)
    _note_kernel_cost("flash_attention_fwd", q, block_q, block_k, causal,
                      n_matmuls=(2, 2), n_tensors=(2, 2), plan=plan, v=v,
                      window=_stream_window(window, q.shape[1], block_k))
    return _flash_fwd_call(q, k, v, causal=bool(causal),
                           interpret=bool(interpret), block_q=int(block_q),
                           block_k=int(block_k), plan=plan, window=window)


#: A kernel's call (arrays first, then static keywords) traced once a
#: signature and inlined where it is called: the caller's jaxpr holds
#: what an undecorated function would have put there, and a second call
#: with the same shapes and keywords costs a cache lookup, not another
#: trace of the kernel's body.  A symbol's shape inference evaluates
#: each node's ancestors again: 232 traces of the forward kernel for 8
#: attention layers, before the step itself is traced.
_STATIC = ("causal", "interpret", "block_q", "block_k", "plan", "window")
_traced_once = functools.partial(jax.jit, inline=True,
                                 static_argnames=_STATIC)


@_traced_once
def _flash_fwd_call(q, k, v, *, causal, interpret, block_q, block_k, plan,
                    window=0):
    """The forward kernel's call for blocks and plan already chosen.
    ``q`` and ``k`` are ``d`` wide, ``v`` and the result ``dv``.  Under
    ``window`` the panel kernel masks the lower edge too and the
    streamed route is :func:`_flash_fwd_window_kernel`'s."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d = q.shape
    dv = v.shape[-1]
    group = _kv_group(q, k, v)
    bk = b * h // group                 # key/value heads over the batch
    scale = 1.0 / math.sqrt(d)
    # Q blocks of one key/value head: ``group`` query heads of t/block_q
    n_q = t // block_q
    grouped = dict(n_q=n_q) if group > 1 else {}

    if t // block_k == 1:
        # T fits one VMEM panel: single-panel kernel (measured fastest
        # at these lengths; streaming costs 10-15%, docs/perf.md)
        kernel = functools.partial(_flash_fwd_panel_kernel, scale=scale,
                                   causal=causal, block_q=block_q,
                                   plan=plan, window=window, **grouped)
        out, lse = pl.pallas_call(
            kernel,
            grid=(bk, group * n_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
                pl.BlockSpec((1, t, d), lambda bh, qi: (bh, 0, 0)),
                pl.BlockSpec((1, t, dv), lambda bh, qi: (bh, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, dv), lambda bh, qi: (bh, qi, 0)),
                pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bk, group * t, dv), q.dtype),
                jax.ShapeDtypeStruct((bk, group * t, 1), jnp.float32),
            ],
            interpret=interpret,
            name=FLASH_FWD_PANEL,
        )(_fold_queries(q, group), _fold_heads(k), _fold_heads(v))
        return (_unfold_heads(out.reshape(b * h, t, dv), b, h),
                lse.reshape(b * h, t, 1))
    kernel = functools.partial(_flash_fwd_kernel, scale=scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, plan=plan, **grouped)
    n_k, kv_index = t // block_k, lambda bh, qi, ki: (bh, ki, 0)
    if window:
        kernel = functools.partial(
            _flash_fwd_window_kernel, scale=scale, block_q=block_q,
            block_k=block_k, window=window, n_q=n_q, plan=plan)
        n_k = _window_tiles_per_q_block(t, block_q, block_k, window)

        def kv_index(bh, qi, j):
            first, last = _live_k_tiles(jax.lax.rem(qi, n_q), block_q,
                                        block_k, window, jnp)
            return bh, jnp.minimum(first + j, last), 0
    out, lse = pl.pallas_call(
        kernel,
        grid=(bk, group * n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, dv), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
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
        name=FLASH_FWD_WINDOW if window else FLASH_FWD_STREAM,
    )(_fold_queries(q, group), _fold_heads(k), _fold_heads(v))
    return (_unfold_heads(out.reshape(b * h, t, dv), b, h),
            lse.reshape(b * h, t, 1))


FLASH_BWD_PANEL = "mxtpu_flash_bwd_panel"


def _flash_bwd_panel_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *, scale, causal, block_q,
                      n_q=None, plan=None, window=0):
    """One Q block against the K/V panel, under ``causal`` against the
    panel's prefix that its range can see (``plan``:
    :func:`_causal_plan`'s), a ``window`` masked as the forward's is;
    dK/dV accumulate across the Q-block grid
    axis (their output block revisits per qi), which with grouped
    queries runs over every query head of the key/value head: the sum
    over the group happens here."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    qi = _q_block_pos(qi, n_q)

    def tile(cols=None):
        q = q_ref[0].astype(jnp.float32)             # (block_q, D)
        k = k_ref[_prefix(k_ref, cols, 0)].astype(jnp.float32)   # (cols, D)
        v = v_ref[_prefix(v_ref, cols, 0)].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)           # (block_q, D)
        lse = lse_ref[0]                             # (block_q, 1)
        delta = delta_ref[0]                  # (block_q, 1) rowsum(do*o)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi * block_q, window=window)
        p = jnp.exp(s - lse)                    # masked entries exp(-inf)=0
        # dV += P^T dO
        dv_ref[_prefix(dv_ref, cols, 0)] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dP = dO V^T ; dS = P o (dP - delta) * scale
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_ref[0] = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        dk_ref[_prefix(dk_ref, cols, 0)] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        _diagonal_tile(qi, plan[1], tile)
    else:
        tile()


FLASH_BWD_STREAM = "mxtpu_flash_bwd_stream"

#: VMEM a kernel may use without asking (Mosaic's scoped default on v5e)
_VMEM_DEFAULT = 16 * 2 ** 20
#: the most a kernel asks for (a v5e core has 128 MiB)
_VMEM_MAX = 100 * 2 ** 20


def _vmem_need(d, block_q, block_k, dq_rows=0, itemsize=2):
    """Bytes of VMEM one step of a backward flash kernel holds, reckoned
    from its blocks: the larger of a call's two kernels (every forward
    the rule can pick compiles under the scoped default, 4-13 MiB by the
    compiler's least, so no forward asks).  ``d``: the wider of the two
    head widths; a block's row takes whole tiles of 128 lanes, so latent
    attention's 192 takes two.  ``itemsize``: of the operands,
    bfloat16's by default.  ``dq_rows``: ``group * t`` on the streamed
    route, whose dQ accumulator holds that many float32 rows at whole
    lane tiles (32 MiB at 8 query heads of 128 a key/value head and 8192
    positions, 16 at 4 of 64); 0 on the panel route.  Counted: two
    float32 score tiles of ``block_q x block_k`` (``s`` / ``p`` and
    ``dp`` / ``ds`` share), K / V double-buffered, the float32 dK / dV
    output blocks double-buffered and, on the streamed route, their
    scratch, the accumulator, and the Q / dO blocks and the float32 dQ
    block double-buffered.  On every bfloat16 row of PERF.md section 6's
    table (PR 35) this reads within 3 MiB of the least limit the
    compiler accepts: the panel at 512 x 2048 15 MiB (least 15), the
    streamed kernel at 4 query heads of 64 a key/value head 33 (31), at
    8 of 128 49 (47), at 192 / 128 wide 34 (31), under a window at
    512 x 1024 41 (40), one query head a key/value head at 512 x 2048
    21 (21)."""
    lanes = -(-d // 128) * 128
    need = 2 * 4 * block_q * block_k                     # s / p, dp / ds
    need += 2 * 2 * itemsize * lanes * block_k           # k, v
    need += (2 + bool(dq_rows)) * 2 * 4 * lanes * block_k    # dk, dv
    need += 4 * lanes * dq_rows                          # dq accumulator
    return need + 2 * (2 * itemsize + 4) * lanes * block_q   # q, dO; dq


def _vmem_request(need):
    """What a kernel that needs ``need`` bytes asks for: half as much
    again, the reckoning being rough off the rows it was checked on."""
    return need * 3 // 2


def _vmem_params(need):
    """Extra ``pallas_call`` arguments of a backward kernel that needs
    ``need`` bytes of VMEM (:func:`_vmem_need`): nothing while the
    scoped default holds it, where the call stays as it always was (a
    call that asks costs the ops round it 0.15 ms whatever it asks for,
    1% of OPT-1.3B's step over its sixteen panel calls); past the
    default the kernel asks for what it needs.  In the cells that is the
    streamed backward under grouped queries or at two lane tiles a row,
    for its accumulator."""
    if need <= _VMEM_DEFAULT:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(_vmem_request(need), _VMEM_MAX))}


def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_acc,
              dk_acc, dv_acc, scale, qi, block_q, ki, first, mask=None,
              cols=None):
    """One (Q block, K/V tile) pair of the streamed backward over the
    tile's first ``cols`` columns (None: all) under ``mask`` (None:
    none): dK/dV into their scratch, the dQ of Q block ``qi`` into its
    ``block_q`` rows of the accumulator, which tile ``first`` of the Q
    block starts and the later ones add to."""
    from jax.experimental import pallas as pl

    q = q_ref[0].astype(jnp.float32)             # (bq, D)
    k = k_ref[_prefix(k_ref, cols, 0)].astype(jnp.float32)   # (cols, D)
    v = v_ref[_prefix(v_ref, cols, 0)].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)           # (bq, D)
    lse = lse_ref[0]                             # (bq, 1)
    delta = delta_ref[0]                         # (bq, 1)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = mask(s)
    p = jnp.exp(s - lse)                  # masked entries exp(-inf)=0
    dv_acc[_prefix(dv_acc, cols)] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dk_acc[_prefix(dk_acc, cols)] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    contrib = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    sl = pl.ds(qi * block_q, block_q)

    @pl.when(ki == first)
    def _dq_init():
        dq_acc[sl, :] = contrib

    @pl.when(ki > first)
    def _dq_add():
        dq_acc[sl, :] += contrib


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                      scale, causal, block_q, block_k, n_q=None, plan=None):
    """Single-pass streaming backward, grid (BH, ki, qi): one K/V block
    stays resident while Q/dO stream past it (inner axis).  dK/dV
    accumulate in per-ki scratch; dQ accumulates in a full-sequence
    VMEM scratch (T*D f32 — 1 MB at T=4096) and each dQ block is
    emitted on the final ki sweep.  Same 5-matmul count as the old
    full-panel kernel, with only the O(T*D) dQ accumulator (not the
    O(block_q*T) score tiles) scaling with sequence length.  Under
    ``causal``, on the static grid: tiles above the diagonal are
    skipped, tiles below it carry no mask, the tile on the diagonal
    runs over the prefix of its columns that the Q block's range can
    see (``plan``: :func:`_causal_plan`)."""
    from jax.experimental import pallas as pl

    ki, qi = pl.program_id(1), pl.program_id(2)
    nk, nq = pl.num_programs(1), pl.num_programs(2)
    # with grouped queries the Q axis runs over every query head of the
    # key/value head (dK/dV sum over the group in their scratch, dQ's
    # accumulator holds the group's rows); the causal position is the
    # block's place in its own head
    qpos = _q_block_pos(qi, n_q)

    @pl.when(qi == 0)
    def _init_kv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _step(masked, cols=None):
        _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_acc,
                  dk_acc, dv_acc, scale, qi, block_q, ki, 0,
                  (lambda s: _causal_mask(s, qpos * block_q, ki * block_k))
                  if masked else None, cols)

    if causal:
        _causal_tiles(qpos, ki, block_q, block_k, plan, _step)
    else:
        _step(False)

    @pl.when(ki == nk - 1)
    def _emit_dq():
        dq_ref[0] = dq_acc[pl.ds(qi * block_q, block_q), :]

    @pl.when(qi == nq - 1)
    def _emit_kv():
        dk_ref[0] = dk_acc[...]
        dv_ref[0] = dv_acc[...]


FLASH_BWD_WINDOW = "mxtpu_flash_bwd_window"


def _window_q_index(ki, jq, block_q, block_k, window, n_q, steps):
    """Step ``jq`` of the windowed backward's streamed axis at K/V tile
    ``ki``: ``(Q block fetched along the folded group axis, its place in
    its head, live)``.  Each query head of the group takes ``steps``
    steps, over the tile's live Q blocks (:func:`_live_q_blocks`) and
    then, held at the last of them, steps that are not live."""
    first, last = _live_q_blocks(ki, block_q, block_k, window, n_q, jnp)
    qpos = first + jax.lax.rem(jq, steps)
    return (jq // steps * n_q + jnp.minimum(qpos, last),
            jnp.minimum(qpos, last), qpos <= last)


def _flash_bwd_window_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                             dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                             *, scale, block_q, block_k, window, n_q, steps,
                             plan):
    """:func:`_flash_bwd_kernel` under a sliding window: the band seen
    from a K/V tile.  The streamed axis runs, a query head of the group,
    over the ``steps`` Q blocks a tile's band can reach
    (:func:`_window_q_index`; the Q / dO / row index maps name the same
    block), so the Q blocks under the band are neither multiplied nor
    fetched.  A Q block's dQ rows start on its first live tile and are
    emitted on its last (:func:`_live_k_tiles`), after which no step
    names that block again; the accumulator holds the whole group's
    rows, as the full kernel's does (the model's full-attention layer
    needs it whole anyway; following the band would save VMEM alone)."""
    from jax.experimental import pallas as pl

    ki, jq = pl.program_id(1), pl.program_id(2)
    qi, qpos, live = _window_q_index(ki, jq, block_q, block_k, window, n_q,
                                     steps)
    first, last = _live_k_tiles(qpos, block_q, block_k, window, jnp)

    @pl.when(jq == 0)
    def _init_kv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _step(mask, cols=None, _empty_rows=False):
        _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_acc,
                  dk_acc, dv_acc, scale, qi, block_q, ki, first, mask, cols)

    _band_tiles(qpos, ki, live, block_q, block_k, window, plan, _step)

    @pl.when(jnp.logical_and(live, ki == last))
    def _emit_dq():
        dq_ref[0] = dq_acc[pl.ds(qi * block_q, block_q), :]

    @pl.when(jq == pl.num_programs(2) - 1)
    def _emit_kv():
        dk_ref[0] = dk_acc[...]
        dv_ref[0] = dv_acc[...]


def _flash_attention_bwd_pallas(q, k, v, o, lse, g, causal, interpret,
                                blocks=None, ranges=None, window=0):
    """Flash backward: P is reconstituted per tile from the forward\'s
    saved log-sum-exp, the (T, T) matrix never touches HBM, and no ref
    spans the full sequence — S=4096+ runs where the old full-panel
    kernel hit the VMEM wall (VERDICT r4 #2).  ``blocks``: explicit
    (block_q, block_k) override (autotuner); default is
    cache-then-heuristic, keyed independently of the forward.
    ``ranges``, ``window``: as the forward's."""
    window = _window_of(window, causal, q.shape[1])
    group = _kv_group(q, k, v)
    parts = _group_parts(q.shape[1], max(q.shape[-1], v.shape[-1]), group)
    block_q, block_k = blocks if blocks is not None else \
        _window_blocks(q.shape[1]) if window else \
        _select_blocks("flash_attention_bwd", q, causal, v, group)
    plan = _causal_plan(block_q, block_k, ranges) if causal else None
    # 5 matmuls at 2*t*t*width each: dQ, dK and the recomputed S over
    # the query/key width, dV and dP over the value width; traffic:
    # q, k read + dq, dk written (dk wide), v, o, dO read + dv written
    # (dv wide; lse/delta rows are negligible)
    _note_kernel_cost("flash_attention_bwd", q, block_q, block_k, causal,
                      n_matmuls=(6, 4), n_tensors=(4, 4), plan=plan, v=v,
                      window=_stream_window(window, q.shape[1], block_k),
                      group_parts=parts)
    call = functools.partial(
        _flash_bwd_call, causal=bool(causal), interpret=bool(interpret),
        block_q=int(block_q), block_k=int(block_k), plan=plan, window=window)
    if parts == 1:
        return call(q, k, v, o, lse, g)
    # the query heads of a key/value head in ``parts`` calls, each the
    # kernel of a group ``parts`` times smaller; dK and dV are the sum of
    # the calls' float32 results
    b, t, h, _d = q.shape
    hk, sub = h // group, group // parts

    def part(x, i):
        x = x.reshape((b, t, hk, parts, sub) + x.shape[3:])[:, :, :, i]
        return x.reshape((b, t, hk * sub) + x.shape[4:])

    rows = lse.reshape(b, hk, parts, sub, t)
    grads = [call(part(q, i), k, v, part(o, i),
                  rows[:, :, i].reshape(b * hk * sub, t, 1), part(g, i),
                  rounded=False)
             for i in range(parts)]
    dq = jnp.stack([x[0].reshape(b, t, hk, sub, -1) for x in grads], axis=3)
    return (dq.reshape(q.shape).astype(q.dtype),
            sum(x[1] for x in grads).astype(k.dtype),
            sum(x[2] for x in grads).astype(v.dtype))


@functools.partial(jax.jit, inline=True,
                   static_argnames=_STATIC + ("rounded",))
def _flash_bwd_call(q, k, v, o, lse, g, *, causal, interpret, block_q,
                    block_k, plan, window=0, rounded=True):
    """The backward kernel's call for blocks and plan already chosen.
    ``q``, ``k`` and their gradients are ``d`` wide; ``v``, ``o``, ``g``
    and ``dV`` ``dv``.  ``window``: as :func:`_flash_fwd_call`'s.
    ``rounded`` false: the gradients stay the kernel's float32 (a call
    that is one part of a group's sums them before rounding)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d = q.shape
    dv = v.shape[-1]
    group = _kv_group(q, k, v)
    bk, hk = b * h // group, h // group
    scale = 1.0 / math.sqrt(d)
    n_q = t // block_q
    grouped = dict(n_q=n_q) if group > 1 else {}

    qt, kt, vt = _fold_queries(q, group), _fold_heads(k), _fold_heads(v)
    dot = _fold_queries(g, group)
    # delta_i = sum_d(dO_i * O_i): rowwise, cheap — computed outside
    delta = jnp.sum(dot.astype(jnp.float32)
                    * _fold_queries(o, group).astype(jnp.float32),
                    axis=-1, keepdims=True)
    lse = lse.reshape(bk, group * t, 1)
    dq_shape = jax.ShapeDtypeStruct((bk, group * t, d), jnp.float32)
    dk_shape = jax.ShapeDtypeStruct((bk, t, d), jnp.float32)
    dv_shape = jax.ShapeDtypeStruct((bk, t, dv), jnp.float32)

    qblock = pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0))
    kblock = pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0))
    doblock = pl.BlockSpec((1, block_q, dv), lambda bh, ki, qi: (bh, qi, 0))
    vblock = pl.BlockSpec((1, block_k, dv), lambda bh, ki, qi: (bh, ki, 0))
    rows = pl.BlockSpec((1, block_q, 1), lambda bh, ki, qi: (bh, qi, 0))
    n_k = t // block_k
    if n_k == 1:
        # T fits one VMEM panel: the round-4 single-panel kernel is
        # the measured fastest formulation at these lengths (every
        # streaming variant paid 10-15%, docs/perf.md)
        kernel = functools.partial(_flash_bwd_panel_kernel, scale=scale,
                                   causal=causal, block_q=block_q,
                                   plan=plan, window=window, **grouped)
        panel = pl.BlockSpec((1, t, d), lambda bh, qi: (bh, 0, 0))
        qb2 = pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0))
        vpanel = pl.BlockSpec((1, t, dv), lambda bh, qi: (bh, 0, 0))
        dob2 = pl.BlockSpec((1, block_q, dv), lambda bh, qi: (bh, qi, 0))
        rows2 = pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, 0))
        dq, dk_, dv_ = pl.pallas_call(
            kernel,
            grid=(bk, group * n_q),
            in_specs=[qb2, panel, vpanel, dob2, rows2, rows2],
            out_specs=[qb2, panel, vpanel],
            out_shape=[dq_shape, dk_shape, dv_shape],
            interpret=interpret,
            name=FLASH_BWD_PANEL,
            **_vmem_params(_vmem_need(max(d, dv), block_q, block_k,
                                      itemsize=q.dtype.itemsize)),
        )(qt, kt, vt, dot, lse, delta)
    else:
        kernel = functools.partial(_flash_bwd_kernel, scale=scale,
                                   causal=causal, block_q=block_q,
                                   block_k=block_k, plan=plan, **grouped)
        steps = group * n_q
        if window:
            per_head = _window_q_blocks_per_tile(t, block_q, block_k, window)
            kernel = functools.partial(
                _flash_bwd_window_kernel, scale=scale, block_q=block_q,
                block_k=block_k, window=window, n_q=n_q, steps=per_head,
                plan=plan)
            steps = group * per_head

            def q_index(bh, ki, jq):
                return bh, _window_q_index(ki, jq, block_q, block_k, window,
                                           n_q, per_head)[0], 0

            qblock = pl.BlockSpec((1, block_q, d), q_index)
            doblock = pl.BlockSpec((1, block_q, dv), q_index)
            rows = pl.BlockSpec((1, block_q, 1), q_index)
        dq, dk_, dv_ = pl.pallas_call(
            kernel,
            grid=(bk, t // block_k, steps),
            in_specs=[qblock, kblock, vblock, doblock, rows, rows],
            out_specs=[qblock, kblock, vblock],
            out_shape=[dq_shape, dk_shape, dv_shape],
            scratch_shapes=[pltpu.VMEM((group * t, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, dv), jnp.float32)],
            interpret=interpret,
            name=FLASH_BWD_WINDOW if window else FLASH_BWD_STREAM,
            **_vmem_params(_vmem_need(max(d, dv), block_q, block_k,
                                      group * t, q.dtype.itemsize)),
        )(qt, kt, vt, dot, lse, delta)
    grads = []
    for x, heads, like in ((dq.reshape(b * h, t, d), h, q), (dk_, hk, k),
                           (dv_, hk, v)):
        x = _unfold_heads(x, b, heads)
        grads.append(x.astype(like.dtype) if rounded else x)
    return tuple(grads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=False, interpret=False, window=0):
    """Block-wise attention; Pallas on TPU, jnp elsewhere."""
    o, _lse = _flash_attention_fwd_pallas(q, k, v, causal, interpret,
                                          window=window)
    return o


def _fa_fwd(q, k, v, causal, interpret, window):
    o, lse = _flash_attention_fwd_pallas(q, k, v, causal, interpret,
                                         window=window)
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, interpret, window, res, g):
    q, k, v, o, lse = res
    return _flash_attention_bwd_pallas(q, k, v, o, lse, g, causal,
                                       interpret, window=window)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


#: ``jax.named_scope`` of an attention call whose value heads have a
#: width of their own (latent attention)
SCOPE_MLA = "mxtpu.block.mla"
#: of an attention call under a sliding window shorter than the sequence
SCOPE_SWA = "mxtpu.block.swa"


@register("_contrib_FlashAttention", arg_names=("q", "k", "v"),
          params={"causal": False, "window": 0, "diffusion_block": 0})
def flash_attention_op(attrs, ctx, q, k, v):
    """Attention over (batch, seq, heads, head_dim) inputs.

    ``diffusion_block`` (default 0: none; refuses ``causal`` and
    ``window``): the block-diffusion training mask over ``seq = 2L``
    rows, a clean copy of a document (rows ``0 .. L-1``) and then a
    noised copy (rows ``L .. 2L-1``), both at positions ``0 .. L-1``, in
    blocks of that many positions.  A clean row ``i`` sees the clean key
    ``j`` iff ``j // B <= i // B``; a noised row ``i`` sees the noised
    key ``j`` iff ``j // B == i // B`` and the clean key ``j`` iff ``j //
    B < i // B``; no clean row sees a noised key.  One softmax runs over
    everything a row sees.  Where ``L`` is a whole number of K/V tiles of
    128 or more and ``B`` a power of two that divides them, the kernels
    ``mxtpu_flash_fwd_blockdiff`` / ``mxtpu_flash_bwd_blockdiff``
    (:mod:`mxnet_tpu.ops.flash_blockdiff`) multiply and fetch only the
    tiles a block sees; any other shape takes the ``jnp`` path under the
    same predicate.  Such a call carries the scope ``mxtpu.block.bda``.

    ``window`` (default 0: none; needs ``causal``): a sliding window,
    position ``t`` sees the keys ``t - window < j <= t``, its own among
    them.  A window that reaches the whole sequence (``>= seq``) is the
    causal call itself.  Past one K/V panel (``seq > 2048``) the
    kernels ``mxtpu_flash_fwd_window`` / ``mxtpu_flash_bwd_window``
    multiply and fetch only the K/V tiles (backward: the Q blocks) of a
    block's band; up to one panel the causal kernels mask the lower
    edge too and skip nothing more.  Such a call carries the scope
    ``mxtpu.block.swa``.

    ``v`` may have a head width of its own (latent attention: ``q``, ``k``
    of 192, ``v`` of 128): the scores run over ``q``'s width and are
    scaled by its ``** -0.5``, the result and ``dV`` have ``v``'s, in the
    same kernels; such a call carries the scope ``mxtpu.block.mla``.

    ``k`` and ``v`` may have fewer heads than ``q`` by a whole factor
    (grouped-query attention): query head ``h`` reads key/value head
    ``h // (q heads / kv heads)``.  The kernels index the shared head
    in their block maps and sum ``dK``/``dV`` over the group; no copy of
    ``K``/``V`` is repeated in HBM.

    New TPU-native capability (the reference era has no attention ops);
    Pallas kernel on TPU, jnp fallback elsewhere.
    """
    if int(attrs.get("diffusion_block", 0)):
        from .flash_blockdiff import SCOPE_BDA
        with jax.named_scope(SCOPE_BDA):
            return _flash_attention_op(attrs, q, k, v)
    if q.ndim == 4 and v.ndim == 4 and q.shape[-1] != v.shape[-1]:
        with jax.named_scope(SCOPE_MLA):
            return _flash_attention_op(attrs, q, k, v)
    if q.ndim == 4 and 0 < int(attrs.get("window", 0)) < q.shape[1]:
        with jax.named_scope(SCOPE_SWA):
            return _flash_attention_op(attrs, q, k, v)
    return _flash_attention_op(attrs, q, k, v)


def _flash_attention_op(attrs, q, k, v):
    causal = bool(attrs["causal"])
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise MXNetError(
            "_contrib_FlashAttention wants (batch, seq, heads, head_dim) "
            "inputs; got q %s, k %s, v %s"
            % (tuple(q.shape), tuple(k.shape), tuple(v.shape)))
    block = int(attrs.get("diffusion_block", 0))
    try:
        group = _kv_group(q, k, v)
        window = _window_of(attrs.get("window", 0), causal, q.shape[1])
        if block:
            from . import flash_blockdiff as _bd
            if causal or window:
                raise ValueError(
                    "diffusion_block %d with causal or window; the "
                    "block-diffusion mask is a mask of its own" % block)
            block = _bd.check_shape(q.shape[1], block)
    except ValueError as e:
        raise MXNetError("_contrib_FlashAttention: %s" % e) from None
    t = q.shape[1]
    block_q = min(_BLOCK_Q, t)
    if block and not _bd.kernels_take(q, k, v, block):
        return _attention_jnp(q, k, v, False, 0, block)

    def attend(q_, k_, v_):
        if block:
            return _bd.flash_attention_blockdiff(q_, k_, v_, block)
        return flash_attention(q_, k_, v_, causal, False, window)
    if _context.on_tpu() and t > 0 and t % block_q == 0 \
            and k.shape[1] == t:
        from ..parallel import mesh as _mesh
        mesh = _mesh.active_kernel_mesh()
        if mesh is None:
            return attend(q, k, v)
        # each device runs the kernel on its (batch/data, heads/model)
        # tile; attention mixes neither dim
        from jax.sharding import PartitionSpec as P
        # heads shard by key/value head, so that a group stays whole
        b_axis, h_axis = _mesh.kernel_axes(mesh, q.shape[0],
                                           q.shape[2] // group)
        spec = P(b_axis, None, h_axis, None)
        return _mesh.shard_map_nocheck(
            attend, mesh, in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)
    # ragged tails (seq not a multiple of the Q block) and cross-attention
    # (tk != tq) take the jnp path rather than failing; XLA still fuses it
    return _attention_jnp(q, k, v, causal, window, block)


@register("_contrib_RingAttention", arg_names=("q", "k", "v"),
          params={"causal": False, "window": 0})
def ring_attention_op(attrs, ctx, q, k, v):
    """Sequence-parallel attention over (batch, seq, heads, head_dim).

    Under an active ``parallel.sequence.sequence_parallel(mesh, axis)``
    context (ShardedTrainer(sequence_parallel=True) sets one), the seq
    dim is sharded over the mesh axis and K/V blocks rotate around the
    ICI ring with an online-softmax merge (parallel/sequence.py) — per-
    device attention memory is O(T/n).  Without a context the op IS
    plain attention (flash kernel on TPU, jnp elsewhere), so the same
    Symbol trains single-chip and sequence-parallel unchanged.

    New TPU-native capability: the reference's long-sequence story is
    bucketing (SURVEY §5.7); ring attention is this framework's
    first-class long-context translation.  A sliding ``window`` is not
    built here (the ring rotates whole K/V blocks): it is refused.
    """
    if int(attrs["window"]):
        raise MXNetError(
            "_contrib_RingAttention: window %d; the ring rotates every K/V "
            "block past every chip and takes no sliding window: use "
            "_contrib_FlashAttention" % int(attrs["window"]))
    from ..parallel import sequence as _seq
    sp = _seq.active_context()
    if sp is not None:
        mesh, axis, batch_axis = sp
        return _seq.ring_attention(q, k, v, mesh=mesh, seq_axis=axis,
                                   causal=bool(attrs["causal"]),
                                   batch_axis=batch_axis)
    # no context: the op IS plain attention — same dispatch as the
    # flash op (one shared implementation keeps the equivalence exact)
    return flash_attention_op(attrs, ctx, q, k, v)
