"""Plan recorder: what the layers of the step traced last turned out to be.

A layer that chooses its lowering while it is traced (a flash kernel's
blocks, the delta rule's kernels or ``jax.numpy`` form, an expert layer's
buffer) says so with :func:`note` under its own scope, a
``mxtpu.block.<kind>`` string.  Whoever traces a step opens one
:func:`recording` round the trace (``ShardedTrainer`` does, round the
forward trace and the pull); the layer's module turns its scope's last plan
into the summary its readers read (``moe.last_plan_summary()`` and the
like: pure functions of :func:`last`).  A new kind of layer needs a scope
and a ``note``, and no edit where the step is built.

A leaf like :mod:`.spans`: it imports nothing of the package.
"""
from __future__ import annotations

import contextlib

__all__ = ["recording", "active", "note", "last", "annotate", "annotations"]

_RECORDING = None     # {scope: [note, ...]} of the innermost open recording
_LAST = {}            # {scope: (notes, annotations)} of the last clean exits


@contextlib.contextmanager
def recording():
    """Collect the notes made within, by scope.  Recordings nest: the inner
    one collects its own and hands collecting back to the outer one when it
    ends.  On a clean exit each scope that got a note becomes that scope's
    last plan, without annotations; a scope that got none keeps the plan it
    had.  An exception publishes nothing."""
    global _RECORDING
    outer, _RECORDING = _RECORDING, {}
    try:
        yield
        _LAST.update((scope, (notes, {}))
                     for scope, notes in _RECORDING.items())
    finally:
        _RECORDING = outer


def active():
    """Whether a recording is open (a layer asks before it does work that
    only a note needs)."""
    return _RECORDING is not None


def note(scope, **info):
    """One entry of ``scope``'s plan; a no-op outside a recording."""
    if _RECORDING is not None:
        _RECORDING.setdefault(scope, []).append(info)


def last(scope):
    """The entries of ``scope``'s last plan in the order noted, or None."""
    return _LAST[scope][0] if scope in _LAST else None


def annotate(scope, **extra):
    """Add to ``scope``'s last plan what was learnt after its trace (from
    the compiled program, say); a no-op without a plan."""
    if scope in _LAST:
        _LAST[scope][1].update(extra)


def annotations(scope):
    """What :func:`annotate` added to ``scope``'s last plan."""
    return dict(_LAST[scope][1]) if scope in _LAST else {}
