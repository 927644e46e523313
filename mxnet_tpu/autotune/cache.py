"""Persistent Pallas tuning cache: measured-best block configs by key.

The commit target of the search harness (:mod:`.search`) and the
trace-time lookup the kernels consult (``ops/pallas_kernels._select_blocks``)
and the plan search's commit target (``graph_plan`` entries).  One entry
maps a tunable-kernel key — ``(op, shape signature, dtypes, mesh shape,
backend, extra statics)``, hashed exactly like a costdb record key — to
the block configuration that measured fastest, together with the walls
of both the winner and the built-in heuristic (the A/B evidence
``tools/autotune.py --report`` renders).

Persistence is JSONL (schema ``mxtpu-tunecache/1``, one entry per line)
under ``MXNET_TPU_TUNE_CACHE``; every file named ``tunecache*.jsonl``
in the directory is **merged on load** with best-measured-wall-wins per
key, so caches written by multiple hosts/runs compose instead of
clobbering.  A corrupt or empty cache file degrades to the heuristic —
the lookup path never raises into a trace.

``MXNET_TPU_AUTOTUNE`` controls the trace-time behavior:

==========  ==========================================================
``off``     no lookups at all (heuristics only, zero overhead)
``cache``   lookup; on miss fall back to the heuristic (the default)
``search``  lookup; on miss run a *bounded* inline search for the ops
            the harness knows (flash fwd/bwd), commit
            the winner, and use it
==========  ==========================================================

Every lookup increments ``mxtpu_tune_cache_{hit,miss}_total{op=...}``
and drops a ``tune_lookup`` flight event, so a run's tuned-vs-heuristic
dispatch mix is visible in BENCH JSON (``bench.py`` embeds
:func:`summary`) and in postmortem flight dumps.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
import time

__all__ = [
    "SCHEMA", "TuneCache", "CACHE",
    "autotune_mode", "cache_dir", "key_sig",
    "kernel_config", "lookup", "put",
    "read_entries", "reload_cache", "summary", "reset_stats",
]

SCHEMA = "mxtpu-tunecache/1"

_MODES = ("off", "cache", "search")


def autotune_mode():
    """``MXNET_TPU_AUTOTUNE``: ``off`` | ``cache`` (default) |
    ``search``.  Unknown values read as ``cache`` (lookups are safe;
    silent inline searching is not)."""
    v = os.environ.get("MXNET_TPU_AUTOTUNE", "cache").strip().lower()
    return v if v in _MODES else "cache"


def cache_dir():
    """Persistence directory (``MXNET_TPU_TUNE_CACHE``), or None when
    the cache is in-memory only (puts do not persist)."""
    return os.environ.get("MXNET_TPU_TUNE_CACHE") or None


def _backend():
    from ..telemetry import costdb
    return costdb.backend_name()


def key_sig(op, shapes, dtypes, mesh=None, backend=None, extra=None):
    """The 12-hex key of one tunable-kernel identity — same hashing
    convention as a costdb record key, so cache entries and costdb
    records of one kernel instantiation correlate by construction."""
    payload = {
        "op": str(op),
        "shapes": [list(s) for s in shapes],
        "dtypes": [str(d) for d in dtypes],
        "mesh": dict(mesh) if mesh else None,
        "backend": backend or _backend(),
        "extra": dict(extra) if extra else None,
    }
    blob = json.dumps(payload, sort_keys=True, default=repr)
    return hashlib.sha1(blob.encode("utf-8")).hexdigest()[:12], payload


class TuneCache:
    """In-memory merged view of the persistent tuning cache.

    One module-level instance (:data:`CACHE`) serves the process and
    lazily loads ``MXNET_TPU_TUNE_CACHE`` on first use; tests build
    private ones.  Thread-safe; the lookup path never raises."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries = {}        # sig -> entry dict
        self._loaded_dir = None   # dir the entries were merged from

    # ------------------------------------------------------------ load
    def load(self, path, merge=True):
        """Merge entries from a ``tunecache*.jsonl`` file or a
        directory of them (best measured wall wins per key).  Corrupt
        lines/files are skipped — a broken cache degrades to the
        heuristics, it must never break a trace.  Returns the number
        of entries merged."""
        entries, _skipped = read_entries(path, strict=False)
        with self._lock:
            if not merge:
                self._entries.clear()
            n = 0
            for e in entries:
                if self._merge_locked(e):
                    n += 1
            return n

    def _merge_locked(self, entry):
        sig = entry.get("sig")
        if not sig or not isinstance(entry.get("config"), dict):
            return False
        prev = self._entries.get(sig)
        if prev is None:
            self._entries[sig] = entry
            return True
        # a full-shape measurement always displaces a proxy one (an
        # inline search measures at batch/heads shrunk to 1, so its
        # tiny walls would otherwise shadow every later real re-tune
        # of the key); within the same fidelity, best measured wall
        # wins and ties/unmeasured resolve to the newer ts
        ep, pp = bool(entry.get("proxy")), bool(prev.get("proxy"))
        if ep != pp:
            if pp and not ep:
                self._entries[sig] = entry
                return True
            return False
        pw = prev.get("wall_s")
        ew = entry.get("wall_s")
        if ew is not None and (pw is None or ew < pw or
                               (ew == pw and _ts(entry) >= _ts(prev))):
            self._entries[sig] = entry
            return True
        if ew is None and pw is None and _ts(entry) >= _ts(prev):
            self._entries[sig] = entry
            return True
        return False

    def ensure_loaded(self):
        """Lazily merge the env-configured cache directory (re-merges
        when ``MXNET_TPU_TUNE_CACHE`` changes between calls)."""
        d = cache_dir()
        with self._lock:
            if d == self._loaded_dir:
                return
            self._loaded_dir = d
        if d:
            try:
                self.load(d)
            except Exception:  # mxlint: allow-broad-except(cache loading is best-effort; a broken cache directory degrades to the heuristics)
                pass

    # ---------------------------------------------------------- lookup
    def lookup(self, op, shapes, dtypes, mesh=None, backend=None,
               extra=None):
        """The tuned entry for this key, or None (miss)."""
        sig, _payload = key_sig(op, shapes, dtypes, mesh=mesh,
                                backend=backend, extra=extra)
        with self._lock:
            e = self._entries.get(sig)
            return dict(e) if e else None

    # ------------------------------------------------------------- put
    def put(self, op, shapes, dtypes, config, wall_s=None, mesh=None,
            backend=None, extra=None, heuristic_config=None,
            heuristic_wall_s=None, candidates=None, source="search",
            proxy=False, persist=True):
        """Commit one tuned entry (merged under best-wall-wins within
        the same measurement fidelity; a full-shape entry displaces a
        ``proxy`` one) and, when ``persist`` and
        ``MXNET_TPU_TUNE_CACHE`` is set, append it to
        ``<dir>/tunecache-<pid>.jsonl``.  ``proxy=True`` marks an
        entry measured at a reduced proxy shape (inline search) whose
        wall is not comparable to full-shape measurements.  Returns
        the entry dict."""
        sig, payload = key_sig(op, shapes, dtypes, mesh=mesh,
                               backend=backend, extra=extra)
        entry = {
            "schema": SCHEMA, "sig": sig,
            "op": payload["op"], "shapes": payload["shapes"],
            "dtypes": payload["dtypes"], "mesh": payload["mesh"],
            "backend": payload["backend"], "extra": payload["extra"],
            "config": dict(config),
            "wall_s": None if wall_s is None else float(wall_s),
            "heuristic_config": dict(heuristic_config)
            if heuristic_config else None,
            "heuristic_wall_s": None if heuristic_wall_s is None
            else float(heuristic_wall_s),
            "candidates": None if candidates is None else int(candidates),
            "proxy": bool(proxy),
            "source": source, "ts": round(time.time(), 6),
        }
        with self._lock:
            self._merge_locked(entry)
        if persist:
            self._persist(entry)
        return entry

    def _persist(self, entry):
        d = cache_dir()
        if not d:
            return None
        path = os.path.join(d, "tunecache-%d.jsonl" % os.getpid())
        try:
            os.makedirs(d, exist_ok=True)
            with open(path, "a") as f:
                f.write(json.dumps(entry, sort_keys=True, default=repr)
                        + "\n")
        except OSError as e:
            import logging
            logging.getLogger(__name__).warning(
                "tunecache: cannot write %r: %s", path, e)
            return None
        return path

    def entries(self):
        """Snapshot of every merged entry (copies)."""
        with self._lock:
            return [dict(e) for e in self._entries.values()]

    def __len__(self):
        with self._lock:
            return len(self._entries)

    def clear(self):
        with self._lock:
            self._entries.clear()
            self._loaded_dir = None


def _ts(entry):
    ts = entry.get("ts")
    return float(ts) if isinstance(ts, (int, float)) else float("-inf")


#: the process-wide cache (module-level helpers below)
CACHE = TuneCache()

# lookup statistics for bench.py / tests — independent of the telemetry
# registry so telemetry.reset cannot silently zero the BENCH evidence
_STATS_LOCK = threading.Lock()
_STATS = {"hits": 0, "misses": 0, "searches": 0}
_HIT_LOG = {}          # sig -> {op, shapes, config} (bounded)
_HIT_LOG_CAP = 256


def reset_stats():
    """Zero the hit/miss counters and the tuned-key log (tests)."""
    with _STATS_LOCK:
        _STATS.update(hits=0, misses=0, searches=0)
        _HIT_LOG.clear()


def _note_lookup(op, sig, hit, entry, searched=False):
    """``hit`` reflects the CACHE lookup; ``entry`` is what the trace
    will dispatch with (the cached entry, or an inline-search winner
    on a searched miss, or None)."""
    with _STATS_LOCK:
        _STATS["hits" if hit else "misses"] += 1
        if searched:
            _STATS["searches"] += 1
        if entry is not None and sig not in _HIT_LOG \
                and len(_HIT_LOG) < _HIT_LOG_CAP:
            _HIT_LOG[sig] = {"op": str(op),
                             "shapes": entry.get("shapes"),
                             "config": entry.get("config")}
    try:
        from ..telemetry import counter, flight
        name = ("mxtpu_tune_cache_hit_total" if hit
                else "mxtpu_tune_cache_miss_total")
        counter(name).labels(op=str(op)).inc()
        flight.record("tune_lookup", op=str(op), sig=sig, hit=hit,
                      searched=bool(searched),
                      config=entry.get("config")
                      if entry is not None else None)
    except Exception:  # mxlint: allow-broad-except(lookup accounting is observability inside a jit trace; a metric failure must not fail the compile)
        pass


def lookup(op, shapes, dtypes, mesh=None, backend=None, extra=None):
    """Raw cache lookup on the default cache (no mode gate, no
    metrics) — the entry dict or None."""
    CACHE.ensure_loaded()
    return CACHE.lookup(op, shapes, dtypes, mesh=mesh, backend=backend,
                        extra=extra)


def put(*args, **kwargs):
    """Commit to the default cache — see :meth:`TuneCache.put`."""
    return CACHE.put(*args, **kwargs)


def reload_cache():
    """Drop the in-memory view and re-merge ``MXNET_TPU_TUNE_CACHE``."""
    CACHE.clear()
    CACHE.ensure_loaded()


def kernel_config(op, shapes, dtypes, mesh=None, extra=None):
    """The trace-time entry point: the tuned block config for this key,
    or None (use the heuristic).  Honors ``MXNET_TPU_AUTOTUNE``
    (``off`` skips the lookup entirely); emits the hit/miss metric and
    a ``tune_lookup`` flight event; in ``search`` mode a miss
    triggers a bounded inline search whose winner is committed and
    returned.  Never raises — any failure reads as a heuristic
    fallback."""
    try:
        mode = autotune_mode()
        if mode == "off":
            return None
        sig, _payload = key_sig(op, shapes, dtypes, mesh=mesh,
                                extra=extra)
        entry = lookup(op, shapes, dtypes, mesh=mesh, extra=extra)
        hit = entry is not None
        searched = False
        if entry is None and mode == "search":
            from . import search as _search
            entry = _search.inline_search(op, shapes, dtypes, mesh=mesh,
                                          extra=extra)
            searched = True
        _note_lookup(op, sig, hit, entry, searched=searched)
        if entry is None:
            return None
        cfg = entry.get("config")
        return dict(cfg) if isinstance(cfg, dict) else None
    except MemoryError:  # pragma: no cover - never mask resource exhaustion
        raise
    except Exception:  # mxlint: allow-broad-except(the tuning-cache lookup runs inside jit traces; any failure must degrade to the built-in heuristic, never fail the compile)
        return None


def summary():
    """Roll-up for BENCH JSON: mode, cache location/size, hit/miss/
    search counts, and the distinct tuned keys that actually hit this
    process (op + shapes + dispatched config)."""
    CACHE.ensure_loaded()
    with _STATS_LOCK:
        stats = dict(_STATS)
        tuned = [dict(v) for v in _HIT_LOG.values()]
    return {
        "schema": SCHEMA,
        "mode": autotune_mode(),
        "cache": cache_dir(),
        "entries": len(CACHE),
        "hits": stats["hits"],
        "misses": stats["misses"],
        "searches": stats["searches"],
        "tuned": tuned,
    }


# ------------------------------------------------------------- reader

_REQUIRED = ("schema", "sig", "op", "config")


def _validate(entry, where):
    if not isinstance(entry, dict):
        raise ValueError("%s: entry is not an object" % where)
    for f in _REQUIRED:
        if f not in entry:
            raise ValueError("%s: entry missing %r" % (where, f))
    if entry["schema"] != SCHEMA:
        raise ValueError("%s: schema %r != %r"
                         % (where, entry["schema"], SCHEMA))
    if not isinstance(entry["config"], dict):
        raise ValueError("%s: config is not an object" % where)
    return entry


def read_entries(path, strict=False):
    """Load tuning-cache entries from a ``tunecache*.jsonl`` file or a
    directory of them, merged best-measured-wall-wins per key.
    ``strict=True`` raises :class:`ValueError` on the first malformed
    line / wrong-schema entry; the default skips bad lines and returns
    ``(entries, skipped)``."""
    if os.path.isdir(path):
        files = sorted(
            os.path.join(path, f) for f in os.listdir(path)
            if f.startswith("tunecache") and f.endswith(".jsonl"))
        if not files and strict:
            raise ValueError("no tunecache*.jsonl files under %r" % path)
    else:
        files = [path]
    merged = TuneCache()
    skipped = 0
    for fp in files:
        try:
            fh = open(fp)
        except OSError as e:
            if strict:
                raise ValueError("cannot read %r: %s" % (fp, e))
            skipped += 1
            continue
        with fh:
            for i, line in enumerate(fh, 1):
                line = line.strip()
                if not line:
                    continue
                where = "%s:%d" % (os.path.basename(fp), i)
                try:
                    entry = _validate(json.loads(line), where)
                except ValueError:
                    if strict:
                        raise
                    skipped += 1
                    continue
                with merged._lock:
                    merged._merge_locked(entry)
    return merged.entries(), skipped
