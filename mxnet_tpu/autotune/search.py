"""TVM-style search harness: measure candidate block configs, keep the
best (arXiv:1802.04799, adapted to the Pallas kernel surface).

The harness owns ONE timing code path — :func:`measure` — with the cost
database's semantics: synchronized dispatch (a value fetch closes
the async chain), **min-of-N** wall, compile excluded by an untimed warm-up
call, and optional in-program chaining (``chain=K`` scans K
data-dependent applications inside one jitted program, dividing the
wall by K — the same dispatch-overhead amortization ``bench.py``
uses).

The tuner (``tune_flash``) enumerates a candidate space that ALWAYS
contains the built-in heuristic, measures every candidate
(``interpret=True`` keeps the real kernel code path exercisable on CPU
CI), records each measurement into
the cost database (kind=``kernel``, ``source="autotune"`` — the
learned cost model's training data accumulates as a side effect), and
commits the winner to the persistent tuning cache with the heuristic's
wall alongside — so the A/B evidence (tuned <= heuristic on the
measured run, by construction) persists with the entry.

:func:`inline_search` is the bounded variant ``MXNET_TPU_AUTOTUNE=
search`` triggers on a trace-time cache miss: few candidates, one
repeat, batch/head dims shrunk to 1 (block choice is governed by the
sequence/row geometry), committed under the ORIGINAL key so the very
next trace of that shape hits the cache.
"""
from __future__ import annotations

import math
import time

__all__ = [
    "measure", "divisors",
    "candidate_flash_configs", "tune_flash", "inline_search",
]


# ------------------------------------------------------------- runner

def _tap(out):
    """A scalar tap of the first array leaf of ``out`` (the value whose
    fetch closes the async dispatch chain)."""
    import jax
    leaves = [l for l in jax.tree_util.tree_leaves(out)
              if hasattr(l, "dtype") and getattr(l, "size", 0)]
    if not leaves:
        return out
    return leaves[0].reshape((-1,))[0]


def measure(fn, args=(), repeats=3, chain=1):
    """Min-of-N synchronized wall seconds of one ``fn(*args)``
    application.  Compile is excluded (untimed warm-up call);
    each timed sample ends in a VALUE fetch of a scalar tap.

    ``chain=K`` (K > 1) chains K applications inside ONE jitted
    program via ``lax.scan`` with a cross-iteration data dependence
    (the scalar tap of each output perturbs the first argument of the
    next application by a factor-1e-12 term, so iterations cannot be
    CSE'd), and the measured wall divides by K — use it where
    per-dispatch overhead would bury the kernel time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    args = tuple(args)
    if chain <= 1:
        jfn = jax.jit(lambda *a: fn(*a))
    else:
        def _chained(first, *rest):
            def body(carry, _):
                out = fn(first + carry.astype(first.dtype), *rest)
                tap = _tap(out)
                return (tap.astype(jnp.float32) * 1e-12), tap
            _c, taps = jax.lax.scan(body, jnp.float32(0.0), None,
                                    length=int(chain))
            return taps
        jfn = jax.jit(_chained)

    def _run():
        out = jfn(*args)
        jax.block_until_ready(out)
        np.asarray(jax.device_get(_tap(out)))

    _run()                                    # warm-up: compile excluded
    walls = []
    for _ in range(max(1, int(repeats))):
        t0 = time.perf_counter()
        _run()
        walls.append(time.perf_counter() - t0)
    return min(walls) / max(1, int(chain))


# ------------------------------------------------- candidate spaces

def divisors(n, lo=1, hi=None):
    """Sorted divisors of ``n`` in ``[lo, hi]``."""
    hi = n if hi is None else hi
    out = [d for d in range(1, int(math.isqrt(n)) + 1) if n % d == 0]
    out = sorted(set(out + [n // d for d in out]))
    return [d for d in out if lo <= d <= hi]


def candidate_flash_configs(t, limit=8, heur=None):
    """Block configs for a flash kernel at sequence length ``t``:
    ``block_q`` from the MXU-friendly divisors of t, ``block_k`` from
    the divisor lattice up to the VMEM-scale bound — ``heur``, the pair
    the kernels fall back on for the call being tuned
    (``ops.pallas_kernels._flash_blocks`` of its shape; ``_blocks(t)``,
    a call's that is not causal, where none is given), always leads the
    list, so a tuned winner can never measure worse than it."""
    from ..ops.pallas_kernels import _BLOCK_K, _blocks
    heur = tuple(heur or _blocks(t))
    bq_cands = [b for b in (64, 128, 256) if t % b == 0] or [heur[0]]
    if heur[0] not in bq_cands:
        bq_cands.insert(0, heur[0])
    bk_bound = min(t, max(_BLOCK_K, 4096))
    out, seen = [], set()

    def add(bq, bk):
        cfg = {"block_q": int(bq), "block_k": int(bk),
               "n_k": int(t // bk)}
        k = (cfg["block_q"], cfg["block_k"])
        if k not in seen and t % bq == 0 and t % bk == 0:
            seen.add(k)
            out.append(cfg)

    add(*heur)
    for bq in bq_cands:
        for bk in reversed(divisors(t, lo=bq, hi=bk_bound)):
            add(bq, bk)
    return out[:max(2, int(limit))]


# ------------------------------------------------------------ tuners

def _interpret_default(interpret):
    if interpret is not None:
        return bool(interpret)
    from ..telemetry import costdb
    return costdb.backend_name() != "tpu"


def _record_candidate(op, shapes, dtypes, cfg, wall, flops=None,
                      bytes_accessed=None):
    """Ground-truth side channel: every measured candidate becomes a
    costdb kernel record (source=autotune) the learned cost model can
    fit on.  Never raises."""
    try:
        from ..telemetry import costdb
        costdb.record("kernel", op, wall_s=wall, flops=flops,
                      bytes_accessed=bytes_accessed, shapes=shapes,
                      dtypes=dtypes, block_config=dict(cfg),
                      source="autotune")
    except Exception:  # mxlint: allow-broad-except(costdb recording is an observability side channel of the tuner; a failure must not abort the search)
        pass


def _finish(op, shapes, dtypes, extra, results, heur_cfg, commit,
            cache, source, proxy=False):
    """Pick the winner, commit to the cache, return the report dict."""
    from . import cache as _cache
    best = min(results, key=lambda r: r["wall_s"])
    heur = next((r for r in results
                 if _same_cfg(r["config"], heur_cfg)), None)
    entry = None
    if commit:
        c = cache or _cache.CACHE
        entry = c.put(op, shapes, dtypes, best["config"],
                      wall_s=best["wall_s"], extra=extra,
                      heuristic_config=heur_cfg,
                      heuristic_wall_s=heur["wall_s"] if heur else None,
                      candidates=len(results), source=source,
                      proxy=proxy)
    return {
        "op": op, "shapes": [list(s) for s in shapes],
        "dtypes": [str(d) for d in dtypes], "extra": extra,
        "best": best, "heuristic": heur,
        "candidates": results, "entry": entry,
    }


def same_config(a, b):
    """Loose config equality over the SHARED keys (a heuristic config
    may omit derived fields like ``n_k`` that a candidate
    carries) — also the comparator ``tools/perf_top.py --suggest``
    uses to decide "already-tuned"."""
    if not a or not b:
        return False
    keys = set(a) & set(b)
    return bool(keys) and all(a[k] == b[k] for k in keys)


_same_cfg = same_config


def tune_flash(shape, dtype="float32", causal=False, which="fwd",
               repeats=3, max_candidates=8, interpret=None,
               commit=True, cache=None, key_shape=None, seed=0,
               source="search"):
    """Tune the flash-attention ``which`` (``fwd``/``bwd``) kernel at
    q/k/v shape ``(B, T, H, D)``.  Measures every candidate with
    :func:`measure` (interpret mode off-TPU, so the REAL Pallas code
    path runs on CPU CI), records each into the cost database, and
    commits the winner keyed at ``key_shape or shape``.  Returns the
    report dict (``best``/``heuristic``/``candidates``/``entry``)."""
    import jax
    import numpy as np
    from ..ops import pallas_kernels as pk

    b, t, h, d = shape
    interpret = _interpret_default(interpret)
    rng = np.random.RandomState(seed)
    mk = lambda: rng.normal(0, 1, (b, t, h, d)).astype(dtype)
    q, k, v = mk(), mk(), mk()
    # q, k and v share the key's shape: values as wide as the keys, one
    # query head a key/value head
    heur = pk._flash_blocks(t, d, d, 1, causal)
    heur_cfg = dict(zip(("block_q", "block_k"), heur))
    heur_cfg["n_k"] = t // heur_cfg["block_k"]
    op = "flash_attention_%s" % which
    key_shapes = [tuple(key_shape or shape)]
    dtypes = [str(np.dtype(dtype))]
    n_mat, n_tens = (4, 4) if which == "fwd" else (10, 8)
    flops = float(n_mat) * b * h * t * t * d
    bytes_ = float(n_tens) * b * t * h * d * np.dtype(dtype).itemsize

    if which == "bwd":
        # residuals via the heuristic blocks, passed explicitly: the
        # block-selecting path would consult the cache (and in search
        # mode recurse into another inline search) mid-tune
        o, lse = pk._flash_attention_fwd_pallas(
            q, k, v, causal, interpret,
            blocks=(heur_cfg["block_q"], heur_cfg["block_k"]))
        g = rng.normal(0, 1, (b, t, h, d)).astype(dtype)

    results = []
    for cfg in candidate_flash_configs(t, limit=max_candidates, heur=heur):
        blocks = (cfg["block_q"], cfg["block_k"])
        if which == "fwd":
            fn = lambda q_, k_, v_: pk._flash_attention_fwd_pallas(
                q_, k_, v_, causal, interpret, blocks=blocks)[0]
            args = (q, k, v)
        else:
            fn = lambda g_, q_, k_, v_: pk._flash_attention_bwd_pallas(
                q_, k_, v_, o, lse, g_, causal, interpret,
                blocks=blocks)
            args = (g, q, k, v)
        try:
            wall = measure(fn, args, repeats=repeats)
        except Exception as e:  # mxlint: allow-broad-except(a candidate that fails to compile/execute is simply not a winner; the search continues with the rest of the space)
            results.append({"config": cfg, "wall_s": None,
                            "error": str(e)[:200]})
            continue
        results.append({"config": cfg, "wall_s": wall})
        # ground truth describes what was MEASURED (the flops above
        # are the measured shape's), even when the cache entry is
        # keyed at a different original shape
        _record_candidate(op, [tuple(shape)], dtypes, cfg, wall,
                          flops=flops, bytes_accessed=bytes_)
    measured = [r for r in results if r["wall_s"] is not None]
    if not measured:
        raise RuntimeError("tune_flash: no candidate measured for %r"
                           % (shape,))
    # a reduced-proxy measurement (key_shape != measured shape) must
    # not pass its tiny walls off as full-shape ones in the cache
    proxy = key_shape is not None and tuple(key_shape) != tuple(shape)
    rep = _finish(op, key_shapes, dtypes, {"causal": bool(causal)},
                  measured, heur_cfg, commit, cache, source,
                  proxy=proxy)
    rep["candidates"] = results
    return rep


# ------------------------------------------------------ inline search

#: bounded inline-search budget (MXNET_TPU_AUTOTUNE=search on a miss)
_INLINE_CANDIDATES = 4
_INLINE_REPEATS = 1


def inline_search(op, shapes, dtypes, mesh=None, extra=None):
    """The bounded search a trace-time cache miss triggers in
    ``search`` mode.  Proxy measurement: flash shapes shrink batch and
    heads to 1 (block choice is governed by the sequence geometry),
    one repeat, few candidates — then the winner is committed under
    the ORIGINAL key so the next trace hits.  Returns the committed
    entry or None; never raises (the caller treats None as a plain
    miss)."""
    try:
        extra = dict(extra or {})
        if op in ("flash_attention_fwd", "flash_attention_bwd"):
            b, t, h, d = shapes[0]
            rep = tune_flash((1, t, 1, d), dtype=dtypes[0],
                             causal=bool(extra.get("causal")),
                             which=op.rsplit("_", 1)[1],
                             repeats=_INLINE_REPEATS,
                             max_candidates=_INLINE_CANDIDATES,
                             key_shape=tuple(shapes[0]),
                             source="inline-search")
            return rep["entry"]
        return None
    except MemoryError:  # pragma: no cover - never mask resource exhaustion
        raise
    except Exception:  # mxlint: allow-broad-except(an inline search failure must read as a plain cache miss — the trace falls back to the heuristic)
        return None
