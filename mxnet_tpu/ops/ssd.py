"""Mamba-2's state-space layer as a chunked scan (state-space duality).

A selective state-space layer with one scalar decay a head and position
(Mamba-2, arXiv:2405.21060; ``mamba_ssm``'s ``Mamba2``).  ``H`` heads of
``P`` channels read ``G`` groups of ``N``-wide ``B_t`` and ``C_t`` (head
``h`` reads group ``h // (H / G)``); per head, with ``A = -exp(A_log)``, a
step ``dt_t >= 0`` and the state ``S`` (``P x N``), ``S_0 = 0``::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

There is no correction term and no triangular system (compare
:mod:`mxnet_tpu.ops.delta_rule`): with ``a_t = dt_t A`` and, inside a chunk
of ``chunk`` positions, ``L_ij = exp(sum_{j<k<=i} a_k)`` for ``i >= j``,
:func:`ssd_scan` computes a chunk as four batched products::

    CB     = C B^T                                   once a group of heads
    Y_diag = (CB o L o dt_j) X
    S_own  = (X exp(sum_{k>j} a_k) dt_j)^T B         the chunk's own state
    Y_off  = exp(sum_{k<=i} a_k) C S_in

and carries the state from chunk to chunk by ``S_in' = exp(sum a) S_in +
S_own``, the sum over the whole chunk.

Numerics.  ``a``, its running sums (one product with a triangle of ones
at the matmuls' highest precision), ``L`` and the state are float32.
``L`` is the exponential of DIFFERENCES of running sums under the mask,
never ``exp(cum_i) * exp(-cum_j)``: a step of 3 under a decay of 16 passes
``exp(-88)`` inside two positions, and ``exp(-cum_j)`` would overflow where
the difference is a number below 0 and its exponential at most 1.  The four
products take operands in the inputs' dtype and accumulate in float32.

Both directions work :data:`GROUP` positions at a time: what needs no state
is made for a whole group of chunks at once, the state walks the group's
chunks.  The backward is a ``custom_vjp``: the forward keeps its inputs and
the state each group was handed (``T / GROUP`` states a head: never ``L``,
never a state a chunk), and the backward walks the groups in reverse, each
group's work made again under ``jax.vjp``.

The lowering is ``jax.numpy`` (``einsum``s under ``lax.scan`` over the
groups) on every backend, under the scope :data:`SCOPE_SSD`.  Under a mesh
of more than one device the scan is wrapped in a ``shard_map`` over (batch,
heads), whole groups of heads a device; a mesh that divides neither is
refused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..telemetry import plan as _plan

#: ``jax.named_scope`` of the op on the device
SCOPE_SSD = "mxtpu.block.ssd"
#: positions of a chunk (the model's ``chunk_size``)
CHUNK = 128
#: positions whose state-free part is made at once, and between two kept
#: states: 16 float32 states a head at 8192 positions
GROUP = 512

_F32 = jnp.float32


def _mm(spec, a, b, dtype):
    """``einsum(spec, a, b)`` with operands in ``dtype``, float32 result."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=_F32)


# mxlint: allow-dtype-widening(the decay, its running sums, L and the state are float32 by the op's definition)
def _group(state, x, dt, bm, cm, a_log, d):
    """One group of ``c`` chunks of ``q`` positions.  ``x (B, c, q, G, R,
    P)``, ``dt (B, c, q, G, R)`` float32, ``bm, cm (B, c, q, G, N)``,
    ``a_log, d (G, R)`` float32, ``state (B, G, R, P, N)`` float32: the
    state the group is handed.  Returns ``(the state it hands on, y)``."""
    dtype = x.dtype
    n_chunks, q = x.shape[1], x.shape[2]
    a = dt * -jnp.exp(a_log)                                # <= 0
    seen = jnp.tril(jnp.ones((q, q), bool))
    # the running sums as one float32 product with a triangle of ones (a
    # ``cumsum`` is a windowed reduction on a TPU: with it the four scans
    # of the 8192-token cell took 28.7 ms a step, with the product 14.1:
    # PERF.md section 6, PR 38)
    cum = jnp.einsum("ls,bcsgr->bclgr", seen.astype(_F32), a,
                     precision=jax.lax.Precision.HIGHEST)
    rows = jnp.moveaxis(cum, 2, -1)                         # (B, c, G, R, q)
    decay = jnp.exp(jnp.where(
        seen, rows[..., :, None] - rows[..., None, :], -jnp.inf))
    cb = _mm("bclgn,bcsgn->bcgls", cm, bm, dtype)
    m = cb[:, :, :, None] * decay * jnp.moveaxis(dt, 2, -1)[..., None, :]
    y = _mm("bcgrls,bcsgrp->bclgrp", m, x, dtype)
    last = cum[:, :, -1:]
    own = _mm("bcsgrp,bcsgn->bcgrpn",
              x.astype(_F32) * (jnp.exp(last - cum) * dt)[..., None], bm,
              dtype)
    whole = jnp.exp(last[:, :, 0])                          # (B, c, G, R)
    handed = []
    for i in range(n_chunks):
        handed.append(state)
        state = whole[:, i, :, :, None, None] * state + own[:, i]
    y = y + _mm("bclgn,bcgrpn->bclgrp", cm, jnp.stack(handed, axis=1),
                dtype) * jnp.exp(cum)[..., None] \
        + d[:, :, None] * x.astype(_F32)
    return state, y.astype(dtype)


def _groups(x, how, heads):
    """(B, T, ...) -> (T / group, B, chunks a group, chunk, ...), the heads
    axis (where ``heads``) split into (G, R)."""
    chunk, per_group, n_heads_groups = how
    b, t = x.shape[:2]
    tail = x.shape[2:]
    if heads:
        tail = (n_heads_groups, tail[0] // n_heads_groups) + tail[1:]
    return jnp.moveaxis(x.reshape(
        (b, t // (chunk * per_group), per_group, chunk) + tail), 1, 0)


def _ungroups(x, like):
    """:func:`_groups` undone, to ``like``'s shape."""
    return jnp.moveaxis(x, 0, 1).reshape(like.shape)


def _inputs(x, dt, bm, cm, a_log, d, how):
    g = how[2]
    per_group = (a_log.astype(_F32).reshape(g, -1),
                 d.astype(_F32).reshape(g, -1))
    return (_groups(x, how, True), _groups(dt, how, True),
            _groups(bm, how, False), _groups(cm, how, False)), per_group


#: traced once a signature and inlined where it is called, as
#: ``delta_rule``'s lowerings are: a symbol's shape inference evaluates a
#: node's ancestors again and again
_traced_once = functools.partial(jax.jit, inline=True,
                                 static_argnames=("how",))


@_traced_once
def _forward(x, dt, bm, cm, a_log, d, *, how):
    """``(y, the state each group was handed (T / group, B, G, R, P, N))``."""
    xs, (a_log, d) = _inputs(x, dt, bm, cm, a_log, d, how)
    b, _t, h, p = x.shape

    def one_group(state, args):
        new, y = _group(state, *args, a_log, d)
        return new, (y, state)

    zero = jnp.zeros((b, how[2], h // how[2], p, bm.shape[-1]), _F32)
    _, (y, starts) = jax.lax.scan(one_group, zero, xs)
    return _ungroups(y, x), starts


@_traced_once
def _backward(x, dt, bm, cm, a_log, d, starts, d_y, *, how):
    """Reverse walk over the groups, each made again from the state it was
    handed."""
    xs, (a_f, d_f) = _inputs(x, dt, bm, cm, a_log, d, how)

    def one_group(carry, args):
        d_state, d_a, d_d = carry
        start, d_out = args[4], args[5]
        _, pull = jax.vjp(_group, start, *args[:4], a_f, d_f)
        grads = pull((d_state, d_out))
        return (grads[0], d_a + grads[5], d_d + grads[6]), grads[1:5]

    carry = (jnp.zeros(starts.shape[1:], _F32), jnp.zeros_like(a_f),
             jnp.zeros_like(d_f))
    (_, d_a, d_d), grads = jax.lax.scan(
        one_group, carry, xs + (starts, _groups(d_y, how, True)),
        reverse=True)
    return tuple(_ungroups(g, like)
                 for g, like in zip(grads, (x, dt, bm, cm))) \
        + (d_a.reshape(a_log.shape).astype(a_log.dtype),
           d_d.reshape(d.shape).astype(d.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, bm, cm, a_log, d, how):
    return _forward(x, dt, bm, cm, a_log, d, how=how)[0]


def _scan_fwd(x, dt, bm, cm, a_log, d, how):
    y, starts = _forward(x, dt, bm, cm, a_log, d, how=how)
    return y, (x, dt, bm, cm, a_log, d, starts)


def _scan_bwd(how, res, d_y):
    return _backward(*res, d_y, how=how)


_scan.defvjp(_scan_fwd, _scan_bwd)


def _on_the_mesh(args, how):
    """``_scan`` over ``args``; under a mesh of more than one device inside
    a ``shard_map`` over (batch, heads), whole groups of heads a device (the
    state mixes neither axis).  A mesh that can split neither is refused:
    the scan would else run whole on every device."""
    from ..parallel import mesh as _mesh
    mesh = _mesh.active_kernel_mesh()
    if mesh is None:
        return _scan(*args, how)
    from jax.sharding import PartitionSpec as P
    x, groups = args[0], how[2]
    b_axis, h_axis = _mesh.kernel_axes(mesh, x.shape[0], groups)
    if b_axis is None and h_axis is None:
        raise ValueError(
            "ssd_scan: a mesh of %s divides neither the batch of %d nor the "
            "%d groups of heads" % (dict(mesh.shape), x.shape[0], groups))
    local = how[:2] + (groups // (mesh.shape[h_axis] if h_axis else 1),)
    wide, narrow = P(b_axis, None, h_axis, None), P(b_axis, None, h_axis)
    return _mesh.shard_map_nocheck(
        lambda *a: _scan(*a, local), mesh,
        in_specs=(wide, narrow, wide, wide, P(h_axis), P(h_axis)),
        out_specs=wide)(*args)


# mxlint: allow-dtype-widening(the step, the decay and the state are float32 by the op's definition)
def ssd_scan(x, dt, b, c, a_log, d, chunk=CHUNK):
    """Mamba-2's recurrence over ``x (B, T, H, P)``, the steps ``dt (B, T,
    H)`` (after their softplus; float32), ``b, c (B, T, G, N)``, ``a_log
    (H,)`` and ``d (H,)``; returns ``y (B, T, H, P)`` in ``x``'s dtype.
    ``T`` is a whole number of chunks; a group is the most whole chunks
    within :data:`GROUP` positions that divide them.  The module's
    docstring has the recurrence, the chunk algebra and what is kept for
    the backward."""
    t, h = int(x.shape[1]), int(x.shape[2])
    chunk, groups = int(chunk), int(b.shape[2])
    if chunk <= 0 or t % chunk:
        raise ValueError(
            "ssd_scan: %d positions are not a whole number of chunks of %d; "
            "pad the sequence to one" % (t, chunk))
    if h % groups:
        raise ValueError("ssd_scan: %d heads do not share %d groups evenly"
                         % (h, groups))
    n = t // chunk
    per_group = max(p for p in range(1, max(1, GROUP // chunk) + 1)
                    if n % p == 0)
    how = (chunk, per_group, groups)
    with jax.named_scope(SCOPE_SSD):
        y = _on_the_mesh((x, dt.astype(_F32), b.astype(x.dtype),
                          c.astype(x.dtype), a_log, d), how)
    _plan.note(SCOPE_SSD, heads=h, head_dim=int(x.shape[3]),
               state=int(b.shape[3]), groups=groups, positions=t,
               chunk=chunk, group=chunk * per_group,
               state_bytes=4 * int(x.shape[0]) * h * int(x.shape[3])
               * int(b.shape[3]) * (n // per_group))
    return y


# ---- what the last traced step's state-space layers are
plan_recording = _plan.recording


def last_plan_summary():
    """Summary of the state-space layers of the step traced last in this
    process (None before any): per layer its ``heads``, ``head_dim``,
    ``state``, ``groups``, ``positions``, ``chunk`` and ``group`` lengths
    and the bytes of state its backward keeps (one float32 state a head and
    group of chunks); ``chunked_layers`` (every layer here is this module's
    scan: there is one lowering and no recurrence to fall to) and
    ``state_bytes`` over all of them.  As
    ``delta_rule.last_plan_summary()``."""
    layers = _plan.last(SCOPE_SSD)
    if layers is None:
        return None
    return {"layers": layers, "chunked_layers": len(layers),
            "state_bytes": sum(x["state_bytes"] for x in layers)}
