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

``q`` and ``k`` may come raw: with ``qk_l2norm`` each head of both is
normalised (``x * rsqrt(sum x^2 + 1e-6)``, float32) and ``q`` scaled inside
the state-free part, so that the backward keeps the raw heads alone
(``fla``'s ``use_qk_l2norm_in_kernel``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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
    ``how``: the static ``(chunk, sub, group, l2norm, scale)``."""
    chunk, sub, _group, l2norm, scale = how
    return _state_free(*(_chunks(a, chunk) for a in args), sub, l2norm, scale)


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(q, k, v, g, beta, how):
    return _forward(q, k, v, g, beta, how=how)[0]


def _scan_fwd(q, k, v, g, beta, how):
    o, starts = _forward(q, k, v, g, beta, how=how)
    return o, (q, k, v, g, beta, starts)


def _scan_bwd(how, res, d_o):
    return _backward(*res, d_o, how=how)


_scan.defvjp(_scan_fwd, _scan_bwd)


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
    how = (chunk, sub, group, bool(qk_l2norm), float(scale))
    with jax.named_scope(SCOPE_KDA):
        args = [_head_major(x, padded)
                for x in (q, k, v.astype(q.dtype), g.astype(_F32),
                          beta.astype(q.dtype))]
        o = _scan(*args, how)
        o = jnp.moveaxis(o[:, :, :t], 1, 2).astype(v.dtype)
    note_layer(heads=int(q.shape[2]), dk=int(q.shape[3]), dv=int(v.shape[3]),
               positions=int(t), chunk=chunk, group=group, form="chunked",
               state_bytes=4 * int(q.shape[0]) * int(q.shape[2])
               * int(q.shape[3]) * int(v.shape[3]) * (padded // group))
    return o


# ---- what the last traced step's linear-attention layers are
_RECORDING = None
_LAST_SUMMARY = None


class plan_recording:
    """Collects what each linear-attention layer of one traced step is; on
    a clean exit with at least one layer the collection becomes
    :func:`last_plan_summary`.  ``ShardedTrainer`` opens one round the
    step's forward trace, as it does ``moe.plan_recording``."""

    def __enter__(self):
        global _RECORDING
        self._prev, _RECORDING = _RECORDING, []
        return self

    def __exit__(self, exc_type, *_exc):
        global _RECORDING, _LAST_SUMMARY
        layers, _RECORDING = _RECORDING, self._prev
        if exc_type is None and layers:
            _LAST_SUMMARY = {
                "layers": layers,
                "chunked_layers": sum(1 for x in layers
                                      if x["form"] == "chunked"),
                "state_bytes": sum(x["state_bytes"] for x in layers)}
        return False


def note_layer(**info):
    """One layer's plan, from :func:`gated_delta_rule` (no-op outside a
    :class:`plan_recording`)."""
    if _RECORDING is not None:
        _RECORDING.append(info)


def last_plan_summary():
    """Summary of the linear-attention layers of the step traced last in
    this process (None before any): per layer its heads, widths, positions,
    chunk and group lengths, the form it lowered to (``chunked``: this
    module's scan) and the bytes of state its backward keeps (one state a
    head and group); ``chunked_layers`` and
    ``state_bytes`` over all of them.  As ``moe.last_plan_summary()``."""
    return _LAST_SUMMARY
