"""The gated delta rule with a per-channel decay, as a chunked scan.

Linear attention with a state a head (Kimi Delta Attention,
arXiv:2510.26692; ``fla.ops.kda``).  Per head, with ``q_t, k_t`` of
``dk`` channels, ``v_t`` of ``dv``, a log-decay ``g_t <= 0`` a key channel,
a write strength ``beta_t`` and the state ``S`` (``dk x dv``), ``S_0 = 0``::

    S'  = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

:func:`gated_delta_rule` computes that over chunks of ``chunk`` positions.
Inside a chunk, with ``G`` the running sum of ``g`` from the chunk's start,
``A_ij = sum_d k_id k_jd exp(G_id - G_jd)`` (``i > j``) and ``B_ij`` the
same with ``q_i`` (``i >= j``), the rank-one corrections ``u_i = beta_i
(v_i - S'^T k_i)`` solve one triangular system (the WY / UT transform)::

    (I + Diag(beta) tril(A, -1)) U = Diag(beta) (V - (K exp(G)) S_in)

so with ``T = (I + Diag(beta) tril(A, -1))^-1 Diag(beta)``, ``W = T (K
exp(G))`` and ``U' = T V`` (none of which needs the state), the chunk is
three products with the state it was handed::

    U     = U' - W S_in
    O     = (Q exp(G)) S_in + tril(B) U
    S_out = Diag(exp(G_last)) S_in + (K exp(G_last - G))^T U

Numerics.  ``g``, its running sums and the state are float32.  ``exp(G_i -
G_j)`` is never factored round the chunk's start (``exp(-G_j)`` overflows
float32 once ``-G`` passes 88, which a decay of 1.6 a position does inside
64 positions): ``A`` and ``B`` are summed over the levels of a binary tree
over the chunk's positions, each level one product whose two factors are
scaled round the running sum at the boundary between two sibling blocks
and so are at most 1 (:func:`_pairs`).  No factor can overflow whatever the
decay.  The triangular system is solved by forward substitution inside
blocks of :data:`SUB` positions (elementwise, float32) and by the block
formula between them at the matmuls' highest precision; every other
product takes operands in the inputs' dtype and accumulates in float32.

Both directions work :data:`GROUP` positions at a time: what needs no
state is made for a whole group of chunks at once, the products with the
state run chunk by chunk inside it.  The backward is a ``custom_vjp``: the
forward keeps its inputs and the state at each group's start (``T / GROUP``
states a head: fewer than one a chunk, never one a token), and the backward
walks the groups in reverse; for each it makes the state-free part again,
runs the group's chunks forward once more for the state each was handed,
then walks them in reverse.

Two lowerings of that algebra, chosen by what the code observes
(:func:`_lowering_for`: the backend and the shapes; no switch).  On a TPU,
at the chunk of 64 and head widths of whole lane tiles, the scan is two
Pallas kernels, :data:`KDA_FWD` and :data:`KDA_BWD`: grid (batch x heads,
groups), a group of :data:`GROUP` positions a step, the head's float32
state (backward: its cotangent) in VMEM scratch across the groups.  A
step makes the group's state-free part in VMEM (:func:`_tiles_state_free`:
the same tree, the same substitution inside blocks of :data:`SUB` and the
same block formula, on tiles: no reshape across the tiling, no ``stack``,
no row set in place) and walks the
chunks through their products with the state.  The forward kernel keeps one
thing more than the states: each chunk's triangular inverse ``(I +
Diag(beta) tril(A, -1))^-1``, float32 as :func:`_tiles_inverse` returned it,
``chunk x chunk`` a chunk, a group's chunks side by side along the lanes
(the bytes of a bfloat16 ``q`` at heads of 128).  The backward takes the
cotangents of the state-free part by ``jax.vjp`` of that function while the
kernel body is traced, so that Mosaic sees dots, elementwise ops, ``iota``
masks and row rolls, and it makes that part again with the inverse handed
in (:func:`_kept_inverse`): no row of the substitution and no product of
the block formula is in its body.  The inverse's cotangent is a rule and not
autodiff's transpose: the closed form ``-X^T X_bar X^T``, two
products at the block formula's precision with the kept ``X``,
where the transpose of the substitution and of the block formula is eight
such products and every row of the substitution walked back.  (``A`` itself
is still made in the backward body: ``beta``'s cotangent is the system's
times ``A``, row by row.)  The layer's plan says how many
highest-precision products the traced backward body holds
(``bwd_hi_products``) and the bytes kept beyond the inputs, states and
inverses (``state_bytes``).  Nothing of ``(chunks, heads, chunk, width)``
float32 is written to HBM.  Anywhere else (the CPU, a toy width, another chunk) the
same chunks run as ``jax.numpy`` ops under ``lax.scan`` (:func:`_forward`,
:func:`_backward`): the tests' oracle beside the recurrence, as
``_attention_jnp`` is for the flash kernels; it keeps the states alone and
makes its inverse again.  That inverse
(:func:`_unit_lower_inverse`) stays under autodiff on purpose: it is what
the rule is checked against.  Under a mesh of more than one device the
kernels' calls wrap themselves in a ``shard_map`` over (batch, heads).

``q`` and ``k`` may come raw: with ``qk_l2norm`` each head of both is
normalised (``x * rsqrt(sum x^2 + 1e-6)``, float32) and ``q`` scaled inside
the state-free part, so that the backward keeps the raw heads alone
(``fla``'s ``use_qk_l2norm_in_kernel``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import context as _context
from ..telemetry import plan as _plan

#: ``jax.named_scope`` of the op on the device
SCOPE_KDA = "mxtpu.block.kda"
#: positions of a chunk: one state a head is kept for each
CHUNK = 64
#: positions of a block of the triangular system solved by forward
#: substitution
SUB = 16
#: positions whose state-free part is made at once (bounds what the
#: forward and the recomputing backward hold beside the kept states: the
#: step program of the 8192-token Kimi Linear cell plans 0.42 GB less at
#: 512 than at 1024, PERF.md section 6, PR 31)
GROUP = 512

#: added to a head's sum of squares under ``qk_l2norm``
L2_EPS = 1e-6

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def _mm(a, b, spec, dtype):
    """``einsum(spec, a, b)`` with operands in ``dtype``, float32 result."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=_F32)


def _unit_lower_inverse(m, sub):
    """``(I + m)^-1`` for strictly lower triangular ``m`` ``(..., C, C)``,
    ``C`` a multiple of ``sub``: forward substitution row by row inside
    each ``sub x sub`` diagonal block (elementwise), then pairs of blocks
    merged by ``[[A, 0], [L, B]]^-1 = [[A^-1, 0], [-B^-1 L A^-1, B^-1]]``
    until one block is left."""
    c = m.shape[-1]
    n = c // sub
    # the n diagonal blocks side by side, solved in one sweep: row r of
    # the inverse is e_r - sum_j d[r, j] x[j], and rows not yet solved
    # are still the identity's, which d's zeros on and above the
    # diagonal leave out
    d = jnp.stack([m[..., i * sub:(i + 1) * sub, i * sub:(i + 1) * sub]
                   for i in range(n)], axis=-3)
    eye = jnp.eye(sub, dtype=_F32)
    x = jnp.broadcast_to(eye, d.shape)
    for r in range(1, sub):
        row = eye[r] - jnp.sum(d[..., r, :, None] * x, axis=-2)
        x = x.at[..., r, :].set(row)
    blocks = [x[..., i, :, :] for i in range(n)]
    size = sub
    while len(blocks) > 1:
        merged = []
        for i in range(0, len(blocks), 2):
            a, b = blocks[i], blocks[i + 1]
            lo = i * size
            low = m[..., lo + size:lo + 2 * size, lo:lo + size]
            x = -jnp.einsum("...ij,...jk,...kl->...il", b, low, a,
                            precision=_HI)
            top = jnp.concatenate([a, jnp.zeros_like(a)], axis=-1)
            merged.append(jnp.concatenate(
                [top, jnp.concatenate([x, b], axis=-1)], axis=-2))
        blocks, size = merged, 2 * size
    return blocks[0]


def _pairs(rows, k, big_g, dtype):
    """``(strict, inclusive)``: ``sum_d rows_id k_jd exp(G_id - G_jd)`` over
    ``i > j`` and over ``i >= j``, ``(..., C, C)`` with zeros elsewhere;
    ``rows`` is ``(k, q)`` stacked on a leading axis of 2 so that the
    score-like matrices of keys and of queries share their decays.

    One product a level of a binary tree over the chunk's positions.  At
    the level of blocks of ``b`` positions a pair ``(i, j)`` belongs to it
    when ``j``'s block is the left sibling of ``i``'s: then ``G_i - G_j =
    (G_i - R) + (R - G_j)`` with ``R`` the running sum where ``j``'s block
    ends and ``i``'s starts, both terms ``<= 0``, so the rows scaled by
    ``exp(G_i - R)`` and the columns by ``exp(R - G_j)`` are at most the
    keys themselves whatever the decay: nothing overflows, and what
    underflows is zero in the result too.  ``log2(C)`` products of ``C x
    dk`` panels, each kept where its level's pairs lie; the pairs ``i == j``
    carry no decay."""
    c, d = k.shape[-2], k.shape[-1]
    idx = jnp.arange(c)
    strict = 0.0
    b = 1
    while b < c:
        def blocks(x):
            return x.reshape(x.shape[:-2] + (c // b, b, d))

        gb = blocks(big_g)
        ends = gb[..., -1:, :]                # the running sum at a block's end
        starts = jnp.concatenate(
            [jnp.zeros_like(ends[..., :1, :, :]), ends[..., :-1, :, :]], axis=-3)
        left = (blocks(rows) * jnp.exp(gb - starts)).reshape(rows.shape)
        right = (blocks(k) * jnp.exp(ends - gb)).reshape(k.shape)
        m = _mm(left, jnp.broadcast_to(right, left.shape),
                "...id,...jd->...ij", dtype)
        bi, bj = idx[:, None] // b, idx[None, :] // b
        strict = strict + jnp.where((bi == bj + 1) & (bi % 2 == 1), m, 0.0)
        b *= 2
    own = jnp.sum(rows * k, axis=-1)          # i == j
    return strict, strict + own[..., None] * jnp.eye(c, dtype=_F32)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + L2_EPS)


def _state_free(q, k, v, g, beta, sub, l2norm, scale):
    """What a chunk needs that does not depend on the state it is handed.
    ``q, k, v`` ``(..., C, d)`` in the compute dtype, ``g`` float32,
    ``beta`` ``(..., C)``.  Returns ``(Q exp(G), W, U', K exp(G_last - G),
    exp(G_last), tril(B))``, the wide ones in the compute dtype."""
    dtype = q.dtype
    q32, k32 = q.astype(_F32), k.astype(_F32)
    if l2norm:
        q32, k32 = _l2(q32), _l2(k32)
    q32 = q32 * scale
    beta = beta.astype(_F32)
    big_g = jnp.cumsum(g.astype(_F32), axis=-2)
    strict, incl = _pairs(jnp.stack([k32, q32]), k32, big_g, dtype)
    a, b = strict[0], incl[1]
    t = _unit_lower_inverse(beta[..., :, None] * a, sub) * beta[..., None, :]
    decay = jnp.exp(big_g)
    last = big_g[..., -1:, :]
    w = _mm(t, k32 * decay, "...ij,...jd->...id", dtype)
    u = _mm(t, v, "...ij,...jd->...id", dtype)
    return ((q32 * decay).astype(dtype), w.astype(dtype), u.astype(dtype),
            (k32 * jnp.exp(last - big_g)).astype(dtype),
            jnp.exp(last[..., 0, :]), b.astype(dtype))


def _chunk_fwd(state, free):
    """One chunk's three products with the state it is handed."""
    qg, w, u0, kd, gamma, b = free
    dtype = qg.dtype
    u = u0.astype(_F32) - _mm(w, state, "...cd,...de->...ce", dtype)
    o = _mm(qg, state, "...cd,...de->...ce", dtype) \
        + _mm(b, u, "...ij,...je->...ie", dtype)
    new = gamma[..., :, None] * state \
        + _mm(kd, u, "...cd,...ce->...de", dtype)
    return new, o


def _chunk_bwd(d_state, free, state, d_o):
    """Cotangents of one chunk's ``_chunk_fwd``: of the state it was
    handed, and of each state-free input."""
    qg, w, u0, kd, gamma, b = free
    dtype = qg.dtype
    u = u0.astype(_F32) - _mm(w, state, "...cd,...de->...ce", dtype)
    d_u = _mm(b, d_o, "...ij,...ie->...je", dtype) \
        + _mm(kd, d_state, "...cd,...de->...ce", dtype)
    d_free = (_mm(d_o, state, "...ce,...de->...cd", dtype),       # Q exp(G)
              -_mm(d_u, state, "...ce,...de->...cd", dtype),      # W
              d_u,                                                # U'
              _mm(u, d_state, "...ce,...de->...cd", dtype),       # K decayed
              jnp.sum(d_state * state, axis=-1),                  # exp(G_last)
              _mm(d_o, u, "...ie,...je->...ij", dtype))           # tril(B)
    d_prev = _mm(qg, d_o, "...cd,...ce->...de", dtype) \
        - _mm(w, d_u, "...cd,...ce->...de", dtype) \
        + gamma[..., :, None] * d_state
    return d_prev, d_free


def _chunks(x, chunk):
    """(B, H, n * chunk, ...) -> (n, B, H, chunk, ...)"""
    b, h, t = x.shape[:3]
    return jnp.moveaxis(x.reshape((b, h, t // chunk, chunk) + x.shape[3:]),
                        2, 0)


def _unchunks(x):
    """(n, B, H, chunk, ...) -> (B, H, n * chunk, ...)"""
    x = jnp.moveaxis(x, 0, 2)
    return x.reshape(x.shape[:2] + (x.shape[2] * x.shape[3],) + x.shape[4:])


def _group_free(args, how):
    """The state-free parts of every chunk of one group, chunk-major.
    ``how``: the static ``(chunk, sub, group, l2norm, scale, lowering)``."""
    chunk, sub, _group, l2norm, scale, _lowering = how
    return _state_free(*(_chunks(a, chunk) for a in args), sub, l2norm, scale)


# ---- the same chunk algebra on two-dimensional tiles, for the kernels
#: ``name=`` of the two ``pallas_call``s: what a device trace shows
KDA_FWD = "mxtpu_kda_fwd"
KDA_BWD = "mxtpu_kda_bwd"
#: VMEM the kernels ask for: a group's state-free part and, backward, what
#: its cotangents are pulled through stay there
_VMEM_FWD = 48 * 2 ** 20
_VMEM_BWD = 100 * 2 ** 20


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _bmm(a, b, contract, precision=None):
    """Batched over the leading axis: ``a``'s and ``b``'s axes ``contract``
    summed, float32 result."""
    return jax.lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((0,), (0,))),
        precision=precision, preferred_element_type=_F32)


def _dot(a, b, contract, dtype):
    """Two-dimensional: operands in ``dtype``, float32 result."""
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype),
        ((contract[:1], contract[1:]), ((), ())),
        preferred_element_type=_F32)


def _sibling_mask(c, b):
    """``(c, c)``: ``j``'s block of ``b`` positions (a power of two) is the
    left sibling of ``i``'s."""
    by = b.bit_length() - 1
    bi, bj = _iota((c, c), 0) >> by, _iota((c, c), 1) >> by
    return (bi == bj + 1) & ((bi & 1) == 1)


def _tiles_inverse(m, chunk, sub):
    """:func:`_unit_lower_inverse` for ``(n, chunk, chunk)`` tiles: forward
    substitution row by row inside the diagonal blocks of ``sub`` positions
    (elementwise), then the same block formula at the same precision: with
    ``x`` the inverse of the diagonal blocks of ``b`` positions and ``low``
    the blocks under them that join two siblings, ``x - x low x`` is the
    inverse at ``2 b``.  No row is set in place and no block is cut out:
    row ``r`` of every block is made at once from the transposed blocks
    (their column ``r``, summed along the lanes, is what scales the rows
    solved so far), summed over each block's rows and chosen by a mask.
    The forward kernel's alone: the backward is handed what this returned
    (:func:`_kept_inverse`)."""
    n = m.shape[0]
    row, lane = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
    eye = jnp.where(row == lane, 1.0, 0.0).astype(_F32)
    by = sub.bit_length() - 1
    blocks = jnp.where((row >> by) == (lane >> by), m, 0.0)
    across = jnp.swapaxes(blocks, 1, 2)
    x = jnp.broadcast_to(eye, m.shape)
    for r in range(1, sub):
        scale = jnp.sum(jnp.where((lane & (sub - 1)) == r, across, 0.0),
                        axis=2, keepdims=True)
        solved = jnp.sum((scale * x).reshape(n, chunk // sub, sub, chunk),
                         axis=2, keepdims=True)
        solved = jnp.broadcast_to(solved, (n, chunk // sub, sub, chunk))
        x = jnp.where((row & (sub - 1)) == r, eye - solved.reshape(m.shape), x)
    b = sub
    while b < chunk:
        low = jnp.where(_sibling_mask(chunk, b), m, 0.0)
        x = x - _bmm(_bmm(x, low, (2, 1), _HI), x, (2, 1), _HI)
        b *= 2
    return x


@jax.custom_vjp
def _kept_inverse(m, x):
    """``(I + m)^-1`` for ``(n, chunk, chunk)`` tiles where it is at hand:
    ``x``, what :func:`_tiles_inverse` made of the same ``m`` in the forward
    kernel and kept.  Nothing is computed; ``m`` is taken for its
    cotangent."""
    del m
    return x


def _kept_inverse_bwd(x, d_x):
    """The cotangent of a matrix inverse in closed form: with ``X = (I +
    L)^-1``, ``dX = -X dL X``, so ``L_bar = -X^T X_bar X^T``: two products
    at the block formula's precision with the ``X`` the forward made, where
    autodiff would walk the substitution's rows back and transpose each
    level of the block formula (its four products become eight).  Kept where
    ``L`` lives, under the diagonal; the kept ``X`` is a constant."""
    chunk = x.shape[-1]
    p = _bmm(jnp.swapaxes(x, 1, 2), d_x, (2, 1), _HI)
    d_m = _bmm(p, x, (2, 2), _HI)
    under = _iota((chunk, chunk), 0) > _iota((chunk, chunk), 1)
    return jnp.where(under, -d_m, 0.0), None


_kept_inverse.defvjp(lambda m, x: (x, x), _kept_inverse_bwd)


def _tiles_state_free(q, k, v, g, beta, kept=None, *, chunk, sub, l2norm,
                      scale, roll):
    """:func:`_state_free` for the ``n`` chunks of one group at once, on
    tiles: ``q, k, v`` ``(n * chunk, d)`` in the compute dtype, ``g``
    float32, ``beta`` ``(n, chunk)`` float32 (a chunk a row: lane-dense in
    HBM; turned into a column here).  Returns a pair: ``(Q exp(G), W, U', K
    exp(G_last - G))`` as ``(n * chunk, d)``, ``exp(G_last)`` ``(n, 1, dk)``
    and ``tril(B)`` ``(n, chunk, chunk)``; and the chunks' ``(I + Diag(beta)
    tril(A, -1))^-1`` in float32, ``(n, chunk, chunk)``: made here
    (:func:`_tiles_inverse`) unless it is handed in as ``kept``
    (:func:`_kept_inverse`: the backward kernel, which differentiates this
    function).

    What differs from the ``jax.numpy`` form is where things live, not what
    is computed: the running sums are one product with a triangle of ones
    (float32, highest precision); the running sum at a block's boundary is
    found by ``roll``ing rows (``pltpu.roll``), level by level, and carries
    no gradient (it cancels between the two factors it scales); the two
    factors of a level share one ``exp`` (rows of a right sibling take
    ``exp(G - start)``, rows of a left sibling ``exp(end - G)``: the only
    rows the level keeps); the substitution makes a row of every block at
    once by masks and sums (:func:`_tiles_inverse`); ``beta`` scales the
    rows of the operands of ``(I + Diag(beta) tril(A, -1))^-1`` instead of
    its columns; masks are made a chunk's tile at a time and shared by the
    group's chunks."""
    dtype = q.dtype
    rows, dk = k.shape
    n = rows // chunk

    def tiles(x):
        return x.reshape((n, chunk) + x.shape[1:])

    def rolled(x, by):          # rows move down by ``by``, round the group
        return tiles(roll(x.reshape(rows, dk), by % rows))

    q32, k32 = tiles(q).astype(_F32), tiles(k).astype(_F32)
    if l2norm:
        q32, k32 = _l2(q32), _l2(k32)
    q32 = q32 * scale
    eye = _iota((chunk, chunk), 0) == _iota((chunk, chunk), 1)
    # (n, chunk) -> (n, chunk, 1) by masks and sums alone: chunk c's row,
    # then its entries down the diagonal of a tile, summed along the lanes
    chunk_of = _iota(beta.shape, 0)
    beta = tiles(jnp.concatenate([
        jnp.sum(jnp.where(eye, jnp.sum(jnp.where(chunk_of == c, beta, 0.0),
                                       axis=0, keepdims=True), 0.0),
                axis=1, keepdims=True) for c in range(n)], axis=0))
    ones = jnp.where(_iota((chunk, chunk), 0) >= _iota((chunk, chunk), 1),
                     1.0, 0.0).astype(_F32)
    big_g = _bmm(jnp.broadcast_to(ones, (n, chunk, chunk)),
                 tiles(g.astype(_F32)), (2, 1), _HI)
    pos = _iota((chunk, dk), 0)                # place inside the chunk
    ends = jax.lax.stop_gradient(big_g)        # of blocks of one position
    strict_k = strict_q = 0.0
    b = 1
    while b < chunk:
        # rows of a right sibling look back to where their block starts
        # (the left sibling's end), rows of a left sibling ahead to where
        # theirs ends; a chunk's first block is a left sibling, so that what
        # rolls in from the chunk before is never looked at
        right_sibling = (pos & b) != 0
        e = jnp.exp(jnp.where(right_sibling, big_g - rolled(ends, b),
                              ends - big_g))
        ke, qe = (k32 * e).astype(dtype), (q32 * e).astype(dtype)
        sel = _sibling_mask(chunk, b)
        strict_k = strict_k + jnp.where(sel, _bmm(ke, ke, (2, 2)), 0.0)
        strict_q = strict_q + jnp.where(sel, _bmm(qe, ke, (2, 2)), 0.0)
        ends = jnp.where(right_sibling, ends, rolled(ends, -b))
        b *= 2
    own = jnp.sum(q32 * k32, axis=-1, keepdims=True)
    incl = strict_q + jnp.where(eye, own, 0.0)
    system = beta * strict_k
    x = _tiles_inverse(system, chunk, sub) if kept is None \
        else _kept_inverse(system, kept)
    inv = x.astype(dtype)
    decay = jnp.exp(big_g)
    last = jnp.sum(jnp.where(pos == chunk - 1, big_g, 0.0),
                   axis=1, keepdims=True)                     # (n, 1, dk)
    w = _bmm(inv, (beta * k32 * decay).astype(dtype), (2, 1))
    u = _bmm(inv, (beta * tiles(v).astype(_F32)).astype(dtype), (2, 1))
    kd = k32 * jnp.exp(last - big_g)

    def flat(x):
        return x.reshape(rows, x.shape[-1]).astype(dtype)

    return (flat(q32 * decay), flat(w), flat(u), flat(kd), jnp.exp(last),
            incl.astype(dtype)), x


def _tile_fwd(state, free):
    """:func:`_chunk_fwd` on one chunk's tiles; the state is held
    transposed, ``(dv, dk)``, so that a decay a key channel scales its
    lanes."""
    qg, w, u0, kd, gamma, b = free
    dtype = qg.dtype
    u = u0.astype(_F32) - _dot(w, state, (1, 1), dtype)
    o = _dot(qg, state, (1, 1), dtype) + _dot(b, u, (1, 0), dtype)
    return gamma * state + _dot(u, kd, (0, 0), dtype), o


def _tile_bwd(d_state, free, state, d_o):
    """:func:`_chunk_bwd` on one chunk's tiles, states transposed."""
    qg, w, u0, kd, gamma, b = free
    dtype = qg.dtype
    u = u0.astype(_F32) - _dot(w, state, (1, 1), dtype)
    d_u = _dot(b, d_o, (0, 0), dtype) + _dot(kd, d_state, (1, 1), dtype)
    d_free = (_dot(d_o, state, (1, 0), dtype),
              -_dot(d_u, state, (1, 0), dtype),
              d_u,
              _dot(u, d_state, (1, 0), dtype),
              jnp.sum(d_state * state, axis=0, keepdims=True),
              _dot(d_o, u, (1, 1), dtype))
    d_prev = _dot(d_o, qg, (0, 0), dtype) - _dot(d_u, w, (0, 0), dtype) \
        + gamma * d_state
    return d_prev, d_free


def _tile(free, c, chunk):
    """Chunk ``c``'s part of a group's state-free tiles."""
    rows = slice(c * chunk, (c + 1) * chunk)
    return tuple(x[rows] for x in free[:4]) + (free[4][c], free[5][c])


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, starts_ref,
                    x_ref, state, *, free_of, chunk):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _first_group():
        state[...] = jnp.zeros_like(state)

    s = state[...]
    starts_ref[0, 0] = s
    free, x = free_of(q_ref[0], k_ref[0], v_ref[0], g_ref[0], beta_ref[0, 0])
    for c in range(q_ref.shape[1] // chunk):
        s, o = _tile_fwd(s, _tile(free, c, chunk))
        o_ref[0, c * chunk:(c + 1) * chunk, :] = o.astype(o_ref.dtype)
        x_ref[0, 0, :, c * chunk:(c + 1) * chunk] = x[c]
    state[...] = s


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, starts_ref, x_ref,
                    do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, d_state,
                    *, free_of, chunk):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _last_group():
        d_state[...] = jnp.zeros_like(d_state)

    n = q_ref.shape[1] // chunk
    kept = jnp.stack([x_ref[0, 0, :, c * chunk:(c + 1) * chunk]
                      for c in range(n)])
    # autodiff runs while this body is traced: what reaches Mosaic is the
    # state-free part's own ops, less the inverse it is handed, and their
    # transposes
    free, pull, _ = jax.vjp(
        lambda *args: free_of(*args, kept), q_ref[0], k_ref[0], v_ref[0],
        g_ref[0], beta_ref[0, 0], has_aux=True)
    s, handed = starts_ref[0, 0], []
    for c in range(n):
        handed.append(s)
        if c + 1 < n:
            s = _tile_fwd(s, _tile(free, c, chunk))[0]
    ds, d_free = d_state[...], [None] * n
    for c in reversed(range(n)):
        ds, d_free[c] = _tile_bwd(ds, _tile(free, c, chunk), handed[c],
                                  do_ref[0, c * chunk:(c + 1) * chunk, :])
    d_state[...] = ds
    cot = tuple(jnp.concatenate([d[i] for d in d_free], axis=0).astype(f.dtype)
                for i, f in enumerate(free[:4])) \
        + tuple(jnp.stack([d[i] for d in d_free]).astype(f.dtype)
                for i, f in zip((4, 5), free[4:]))
    grads = pull(cot)
    for ref, grad in zip((dq_ref, dk_ref, dv_ref, dg_ref), grads):
        ref[0] = grad.astype(ref.dtype)
    dbeta_ref[0, 0] = grads[4]


def _kernel_parts(q, how, reverse=False):
    """What both kernels' calls share: ``(the state-free part bound to its
    statics, block specs by width, the call's keywords by VMEM asked for, the
    arguments as the kernels take them)``.  ``reverse``: the grid walks the
    groups from the last."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    chunk, sub, group, l2norm, scale, lowering = how
    b, h, t = q.shape[:3]
    last = t // group - 1
    free_of = functools.partial(
        _tiles_state_free, chunk=chunk, sub=sub, l2norm=l2norm, scale=scale,
        roll=lambda x, by: pltpu.roll(x, by, 0))

    def group_of(gi):
        return last - gi if reverse else gi

    def rows(width):
        """A group's rows of a ``(heads, T, width)`` array; ``width`` None:
        of ``beta`` as ``(heads, groups, group / chunk, chunk)``, a chunk a
        row; ``"inverse"``: the group's kept inverses of ``(heads, groups,
        chunk, group)``, a chunk beside a chunk along the lanes; a ``(dv,
        dk)`` pair: the group's state of ``(groups, heads, dv, dk)``."""
        tiles = {None: (group // chunk, chunk), "inverse": (chunk, group)}
        if width in tiles:
            return pl.BlockSpec((1, 1) + tiles[width],
                                lambda bh, gi: (bh, group_of(gi), 0, 0))
        if isinstance(width, tuple):
            return pl.BlockSpec((1, 1) + width,
                                lambda bh, gi: (group_of(gi), bh, 0, 0))
        return pl.BlockSpec((1, group, width),
                            lambda bh, gi: (bh, group_of(gi), 0))

    def keywords(vmem):
        if lowering == "interpret":
            return {"interpret": True}
        return {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem)}

    def flat(*wide, beta):
        return [x.reshape((b * h, t) + x.shape[3:]) for x in wide] \
            + [beta.astype(_F32).reshape(b * h, t // group, group // chunk,
                                         chunk)]

    return free_of, rows, keywords, flat


#: traced once a signature and inlined where it is called, as the flash
#: kernels' calls are (``pallas_kernels._traced_once``): a symbol's shape
#: inference evaluates a node's ancestors again and again, and the tree
#: of products and the substitution are unrolled in Python
_traced_once = functools.partial(jax.jit, inline=True, static_argnames=("how",))


@_traced_once
def _forward(q, k, v, g, beta, *, how):
    """Head-major inputs ``(B, H, T, ...)``, ``T`` a multiple of the group
    and the group of the chunk.  Returns ``(o (B, H, T, dv) in v's dtype,
    the state each group was handed (T / group, B, H, dk, dv))``."""
    b, h, _t, dk = q.shape
    dv = v.shape[-1]

    def one_group(state, args):
        def one_chunk(s, f):
            new, o = _chunk_fwd(s, f)
            return new, o.astype(v.dtype)

        new, o = jax.lax.scan(one_chunk, state, _group_free(args, how))
        return new, (_unchunks(o), state)

    groups = tuple(_chunks(a, how[2]) for a in (q, k, v, g, beta))
    _, (o, starts) = jax.lax.scan(
        one_group, jnp.zeros((b, h, dk, dv), _F32), groups)
    return _unchunks(o), starts


@_traced_once
def _backward(q, k, v, g, beta, starts, d_o, *, how):
    """Reverse walk over the groups and, inside each, over its chunks."""
    chunk, group = how[0], how[2]

    def one_group(d_state, xs):
        args, start, d_out = xs[:5], xs[5], xs[6]
        free, pull = jax.vjp(functools.partial(_group_free, how=how), args)
        # the state each chunk of the group was handed, made again
        _, handed = jax.lax.scan(
            lambda s, f: (_chunk_fwd(s, f)[0], s), start, free)

        def one_chunk(ds, x):
            f, s, do = x
            prev, d_free = _chunk_bwd(ds, f, s, do)
            return prev, tuple(c.astype(o.dtype) for c, o in zip(d_free, f))

        d_state, d_free = jax.lax.scan(
            one_chunk, d_state, (free, handed, _chunks(d_out, chunk)),
            reverse=True)
        return d_state, pull(d_free)[0]

    groups = tuple(_chunks(a, group) for a in (q, k, v, g, beta, d_o))
    xs = groups[:5] + (starts, groups[5])
    _, grads = jax.lax.scan(one_group, jnp.zeros(starts.shape[1:], _F32),
                            xs, reverse=True)
    return tuple(_unchunks(x) for x in grads)


@_traced_once
def _forward_kernel(q, k, v, g, beta, *, how):
    """:func:`_forward` as one ``pallas_call``: grid (batch x heads, groups),
    a group a step, the head's float32 state in VMEM across the groups.  The
    states come back transposed, ``(T / group, B, H, dv, dk)``, and with them
    each chunk's float32 triangular inverse, ``(B, H, T / group, chunk,
    group)``, a group's chunks side by side (whole lane tiles in HBM, where
    ``(chunk, chunk)`` tiles of 64 would be padded to twice their bytes):
    both are :func:`_backward_kernel`'s alone."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    free_of, rows, keywords, flat = _kernel_parts(q, how)
    (b, h, t, dk), dv = q.shape, v.shape[-1]
    n_groups = t // how[2]
    chunk, group = how[0], how[2]
    o, starts, x = pl.pallas_call(
        functools.partial(_kda_fwd_kernel, free_of=free_of, chunk=chunk),
        grid=(b * h, n_groups),
        in_specs=[rows(dk), rows(dk), rows(dv), rows(dk), rows(None)],
        out_specs=[rows(dv), rows((dv, dk)), rows("inverse")],
        out_shape=[jax.ShapeDtypeStruct((b * h, t, dv), v.dtype),
                   jax.ShapeDtypeStruct((n_groups, b * h, dv, dk), _F32),
                   jax.ShapeDtypeStruct((b * h, n_groups, chunk, group), _F32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        name=KDA_FWD, **keywords(_VMEM_FWD),
    )(*flat(q, k, v, g, beta=beta))
    return (o.reshape(b, h, t, dv), starts.reshape(n_groups, b, h, dv, dk),
            x.reshape(b, h, n_groups, chunk, group))


@_traced_once
def _backward_kernel(q, k, v, g, beta, starts, x, d_o, *, how):
    """:func:`_backward` as one ``pallas_call`` that walks the groups in
    reverse, the cotangent of the state in VMEM across them; ``x``: the
    inverses :func:`_forward_kernel` kept."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    free_of, rows, keywords, flat = _kernel_parts(q, how, reverse=True)
    (b, h, t, dk), dv = q.shape, v.shape[-1]
    args = flat(q, k, v, g, beta=beta)
    wide = [rows(dk), rows(dk), rows(dv), rows(dk), rows(None)]
    grads = pl.pallas_call(
        functools.partial(_kda_bwd_kernel, free_of=free_of, chunk=how[0]),
        grid=(b * h, t // how[2]),
        in_specs=wide + [rows((dv, dk)), rows("inverse"), rows(dv)],
        out_specs=wide,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in args],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        name=KDA_BWD, **keywords(_VMEM_BWD),
    )(*args, starts.reshape((-1, b * h, dv, dk)),
      x.reshape((b * h,) + x.shape[2:]), d_o.reshape(b * h, t, dv))
    return tuple(x.reshape(like.shape).astype(like.dtype)
                 for x, like in zip(grads, (q, k, v, g, beta)))


def _sizes(t, chunk, group):
    """``(padded length, group)`` for ``t`` positions: whole chunks, and
    whole groups of chunks once there is more than one group."""
    group = max(chunk, group // chunk * chunk)
    if t <= group:
        padded = -(-t // chunk) * chunk
        return padded, padded
    return -(-t // group) * group, group


def _head_major(x, padded):
    """(B, T, H, ...) -> (B, H, padded, ...), zeros past ``T``: a padded
    position has no key, no value and no decay, so it leaves the state as
    it found it."""
    x = jnp.moveaxis(x, 2, 1)
    pad = padded - x.shape[2]
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))
    return x


def _lowerings(how):
    """``(forward, backward)`` of the lowering ``how`` names."""
    return (_forward, _backward) if how[5] == "xla" \
        else (_forward_kernel, _backward_kernel)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(q, k, v, g, beta, how):
    return _lowerings(how)[0](q, k, v, g, beta, how=how)[0]


def _scan_fwd(q, k, v, g, beta, how):
    # kept beside the inputs: the groups' states and, from the kernels, the
    # chunks' inverses
    o, *kept = _lowerings(how)[0](q, k, v, g, beta, how=how)
    if _plan.active() and how[5] != "xla":
        _note_backward_body((q, k, v, g, beta, *kept, o), how)
    return o, (q, k, v, g, beta, *kept)


def _scan_bwd(how, res, d_o):
    return _lowerings(how)[1](*res, d_o, how=how)


_scan.defvjp(_scan_fwd, _scan_bwd)


def _lowering_for(chunk, dk, dv):
    """``"pallas"`` where the two kernels run (a TPU, the chunk of 64 and
    widths of whole lane tiles), else ``"xla"``: the ``jax.numpy`` form.
    (``"interpret"``, the kernels under Pallas's interpreter at any width,
    is the tests' to ask for.)"""
    if _context.on_tpu() and chunk == CHUNK and dk % 128 == 0 \
            and dv % 128 == 0:
        return "pallas"
    return "xla"


def _on_the_mesh(args, how):
    """``_scan`` over head-major ``args``; the kernels under a mesh of more
    than one device wrapped in a ``shard_map`` over (batch, heads), as the
    flash kernels are: the state mixes neither axis."""
    from ..parallel import mesh as _mesh
    mesh = None if how[5] == "xla" else _mesh.active_kernel_mesh()
    if mesh is None:
        return _scan(*args, how)
    from jax.sharding import PartitionSpec as P
    b_axis, h_axis = _mesh.kernel_axes(mesh, args[0].shape[0],
                                       args[0].shape[1])
    wide, narrow = P(b_axis, h_axis, None, None), P(b_axis, h_axis, None)
    return _mesh.shard_map_nocheck(
        lambda *a: _scan(*a, how), mesh,
        in_specs=(wide, wide, wide, wide, narrow), out_specs=wide)(*args)


# mxlint: allow-dtype-widening(the log-decay, its running sums and the state are float32 by the op's definition)
def gated_delta_rule(q, k, v, g, beta, chunk=CHUNK, sub=SUB, group=GROUP,
                     qk_l2norm=False, scale=1.0):
    """The gated delta rule over ``q, k (B, T, H, dk)``, ``v (B, T, H,
    dv)``, the log-decay ``g (B, T, H, dk)`` (float32, ``<= 0``) and the
    write strength ``beta (B, T, H)``; returns ``o (B, T, H, dv)`` in
    ``v``'s dtype.  ``q`` is multiplied by ``scale``, after each head of
    ``q`` and ``k`` is normalised where ``qk_l2norm``.  The module's
    docstring has the recurrence, the chunk algebra and what is kept for
    the backward."""
    t = q.shape[1]
    chunk = int(chunk)
    if chunk <= 0 or chunk & (chunk - 1):
        raise ValueError("gated_delta_rule: chunk=%d is not a power of two"
                         % chunk)
    sub = min(int(sub), chunk)
    padded, group = _sizes(t, chunk, int(group))
    lowering = _lowering_for(chunk, int(q.shape[3]), int(v.shape[3]))
    how = (chunk, sub, group, bool(qk_l2norm), float(scale), lowering)
    with jax.named_scope(SCOPE_KDA):
        args = [_head_major(x, padded)
                for x in (q, k, v.astype(q.dtype), g.astype(_F32),
                          beta.astype(q.dtype))]
        o = _on_the_mesh(args, how)
        o = jnp.moveaxis(o[:, :, :t], 1, 2).astype(v.dtype)
    # float32 kept beyond the inputs: a state a head and group and, from
    # the kernels, a (chunk, chunk) inverse a head and chunk
    kept = int(q.shape[3]) * int(v.shape[3]) * (padded // group)
    if lowering != "xla":
        kept += chunk * padded
    info = dict(heads=int(q.shape[2]), dk=int(q.shape[3]), dv=int(v.shape[3]),
                positions=int(t), chunk=chunk, group=group, form="chunked",
                lowering="xla" if lowering == "xla" else "pallas",
                state_bytes=4 * int(q.shape[0]) * int(q.shape[2]) * kept)
    traced = _BWD_HI_PRODUCTS.get(_body_key(args[0], args[2], how))
    if traced is not None:
        info["bwd_hi_products"] = traced
    _plan.note(SCOPE_KDA, **info)
    return o


# ---- what the last traced step's linear-attention layers are
plan_recording = _plan.recording
#: highest-precision products in the backward kernel's body, by what the
#: body's trace depends on (:func:`_body_key`)
_BWD_HI_PRODUCTS = {}


def _hi_products(jaxpr):
    """``dot_general``s at ``Precision.HIGHEST`` in ``jaxpr`` and in the
    jaxprs its equations hold (a ``pallas_call``'s body among them)."""
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            precision = eqn.params["precision"]
            count += _HI in (precision if isinstance(precision, tuple)
                             else (precision,))
        count += sum(_hi_products(inner)
                     for inner in jax.core.jaxprs_in_params(eqn.params))
    return count


def _body_key(q, v, how):
    """What a kernel body's trace depends on: a group's rows, the widths,
    the compute dtype and the statics; not the batch, the heads or the
    number of groups, which are the grid's."""
    return (q.dtype.name, int(q.shape[3]), int(v.shape[3]), how)


def _note_backward_body(args, how):
    """Counts the highest-precision products of the backward kernel's body
    for the layer's plan.  The body is traced when the cotangents are
    pulled, after the forward trace that a :class:`plan_recording` spans,
    so it is traced here, from the forward rule, on the shapes the backward
    will be handed: :func:`_backward_kernel` is traced once a signature, and
    the pull finds this trace."""
    key = _body_key(args[0], args[2], how)
    if key not in _BWD_HI_PRODUCTS:
        shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in args]
        # the pull traces under the mesh context spelled out, the forward
        # rule under none: the same context, but another key of jit's cache
        with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
            traced = jax.make_jaxpr(
                functools.partial(_backward_kernel, how=how))(*shapes)
        _BWD_HI_PRODUCTS[key] = _hi_products(traced.jaxpr)


def last_plan_summary():
    """Summary of the linear-attention layers of the step traced last in
    this process (None before any): per layer its heads, widths, positions,
    chunk and group lengths, the form it lowered to (``chunked``: this
    module's scan), its ``lowering`` (``pallas``: the two kernels; ``xla``:
    ``jax.numpy`` ops), the float32 bytes its forward keeps beyond the inputs
    (one state a head and group; on the kernels also one ``chunk x chunk``
    triangular inverse a head and chunk) and, on the kernels where the
    recording saw the
    layer differentiated, ``bwd_hi_products`` (the ``dot_general``s at the
    highest precision in the backward kernel's traced body);
    ``chunked_layers``, ``kernel_layers`` and ``state_bytes`` over all of
    them, ``bwd_hi_products`` the largest.  As ``moe.last_plan_summary()``."""
    layers = _plan.last(SCOPE_KDA)
    if layers is None:
        return None
    summary = {
        "layers": layers,
        "chunked_layers": sum(1 for x in layers if x["form"] == "chunked"),
        "kernel_layers": sum(1 for x in layers if x["lowering"] == "pallas"),
        "state_bytes": sum(x["state_bytes"] for x in layers)}
    traced = [x["bwd_hi_products"] for x in layers if "bwd_hi_products" in x]
    if traced:
        summary["bwd_hi_products"] = max(traced)
    return summary
