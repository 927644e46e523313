"""Learned TPU cost model over the costdb ground truth.

The "learned cost model" half of ROADMAP item 2 (arXiv:2008.01040,
scaled to this codebase): a small ridge regression — numpy ``lstsq``
over roofline-normalized features, no third-party deps — fit on the
persistent cost-database records (``telemetry.costdb``; the autotuner
feeds candidate measurements in as a side effect of every search).

Target: ``log(wall_s)``.  Features per record (all log-domain, so the
linear model captures the multiplicative structure of a roofline):

=================  ===================================================
``log_attainable``  roofline lower bound max(flops/peak, bytes/bw) —
                    a perfectly roofline-attaining kernel makes this
                    feature's coefficient 1 and everything else 0
``log_flops``       work term
``log_bytes``       traffic term
``log_ai``          arithmetic intensity (flops/byte)
``log_bq``          Q block edge (``block_q``)
``log_bk``          K block edge (``block_k``)
``log_grid``        inner grid length (``n_k``) — the
                    block-count cliff term (2176 -> 17 tiny K blocks)
``pad_waste``       padded-compute fraction when the config carries it
=================  ===================================================

``fit``/``predict``/``save``/``load`` plus :meth:`CostModel.calibration`
(predicted-vs-measured report: geometric-mean error factor, log-domain
MAE/RMSE, r², worst records).  Consumers: ``tools/autotune.py
--fit-model/--report`` and analysis rule **MXG010**
(:mod:`mxnet_tpu.analysis.perf`), which flags graph nodes whose
predicted wall exceeds their roofline-attainable time by a
configurable factor — predicted-slow graphs are named *before* any
compile."""
from __future__ import annotations

import json
import math
import os

__all__ = ["SCHEMA", "FEATURES", "CostModel", "featurize",
           "fit_cost_model", "load_model"]

SCHEMA = "mxtpu-costmodel/1"

FEATURES = ("bias", "log_attainable", "log_flops", "log_bytes",
            "log_ai", "log_bq", "log_bk", "log_grid", "pad_waste")

_FLOOR = 1e-12


def _log(x):
    return math.log(max(float(x), _FLOOR))


def featurize(flops=None, bytes_accessed=None, block_config=None,
              backend=None):
    """Feature vector (len == FEATURES) for one record-like cost
    description; None when the record carries no flops (nothing to
    model)."""
    if flops is None:
        return None
    from ..telemetry import costdb
    flops = float(flops)
    bytes_ = float(bytes_accessed) if bytes_accessed else 0.0
    pf = costdb.peak_flops(backend)
    pbw = costdb.peak_bandwidth(backend)
    att = costdb._attainable_s(flops, bytes_ or None, pf, pbw) or _FLOOR
    ai = flops / bytes_ if bytes_ > 0 else 0.0
    cfg = dict(block_config or {})
    bq = cfg.get("block_q") or 0
    bk = cfg.get("block_k") or 0
    grid = cfg.get("n_k") or 1
    waste = float(cfg.get("pad_waste") or 0.0)
    return [1.0, _log(att), _log(flops), _log(bytes_ + 1.0),
            _log(ai + 1.0), _log(bq + 1.0), _log(bk + 1.0),
            _log(grid), waste]


def _record_features(rec):
    return featurize(rec.get("flops"), rec.get("bytes_accessed"),
                     rec.get("block_config"), rec.get("backend"))


#: indices of the block-geometry features in FEATURES (log_bq, log_bk,
#: log_grid, pad_waste) — substituted by their training means when a
#: prediction carries no block config, so a graph-level MXG010 query
#: stays inside the distribution the model was fit on instead of
#: extrapolating through zeroed geometry terms
_GEOMETRY_IDX = tuple(FEATURES.index(f) for f in
                      ("log_bq", "log_bk", "log_grid", "pad_waste"))


class CostModel:
    """Ridge regression ``log(wall) ~ theta . features``."""

    def __init__(self, theta=None, stats=None, l2=1e-3,
                 feature_means=None):
        self.theta = list(theta) if theta is not None else None
        self.stats = dict(stats or {})
        self.l2 = float(l2)
        self.feature_means = (list(feature_means)
                              if feature_means is not None else None)

    # ------------------------------------------------------------- fit
    def fit(self, records):
        """Fit on costdb records (dicts with ``wall_s``/``flops``/
        ``bytes_accessed``/``block_config``/``backend``).  Records
        without a measured wall or flops are skipped.  Returns self;
        raises ValueError when fewer than 2 usable records exist.
        Below ``len(FEATURES)`` records the ridge penalty keeps the
        system solvable but the fit is underdetermined —
        ``stats["underdetermined"]`` flags it, and the calibration
        (computed on the TRAINING records) will look better than the
        model generalizes."""
        import numpy as np
        X, y = [], []
        for rec in records:
            wall = rec.get("wall_s")
            if wall is None or wall <= 0:
                continue
            f = _record_features(rec)
            if f is None:
                continue
            X.append(f)
            y.append(_log(wall))
        if len(X) < 2:
            raise ValueError(
                "cost model needs >= 2 measured records with flops "
                "(got %d); run a tuning pass or a sampled training "
                "run under MXNET_TPU_COSTDB first" % len(X))
        X = np.asarray(X, np.float64)
        yv = np.asarray(y, np.float64)
        # ridge: (X^T X + l2 I) theta = X^T y (bias unpenalized)
        d = X.shape[1]
        reg = self.l2 * np.eye(d)
        reg[0, 0] = 0.0
        theta = np.linalg.solve(X.T @ X + reg, X.T @ yv)
        self.theta = [float(t) for t in theta]
        self.feature_means = [float(v) for v in X.mean(axis=0)]
        self.stats = self._calibration_stats(X, yv)
        self.stats["n"] = len(y)
        self.stats["underdetermined"] = len(y) < len(FEATURES)
        return self

    def _calibration_stats(self, X, y):
        import numpy as np
        pred = X @ np.asarray(self.theta)
        err = pred - y
        ss_res = float(np.sum(err ** 2))
        ss_tot = float(np.sum((y - y.mean()) ** 2)) or _FLOOR
        return {
            "mae_log": float(np.mean(np.abs(err))),
            "rmse_log": float(np.sqrt(np.mean(err ** 2))),
            "geo_err_factor": float(np.exp(np.mean(np.abs(err)))),
            "r2": 1.0 - ss_res / ss_tot,
        }

    # --------------------------------------------------------- predict
    def predict(self, flops=None, bytes_accessed=None,
                block_config=None, backend=None):
        """Predicted wall seconds, or None (unfitted model / no
        flops).  Without a ``block_config`` (graph-level MXG010
        queries), the geometry features take their TRAINING MEANS —
        the model was fit on records that carry block configs, and
        zeroed geometry terms would push the prediction an arbitrary
        factor out of the fitted distribution."""
        if self.theta is None:
            return None
        f = featurize(flops, bytes_accessed, block_config, backend)
        if f is None:
            return None
        if not block_config and self.feature_means is not None:
            for i in _GEOMETRY_IDX:
                f[i] = self.feature_means[i]
        z = sum(t * x for t, x in zip(self.theta, f))
        # clamp: a wild extrapolation must not overflow exp
        return math.exp(min(z, 50.0))

    def predict_record(self, rec):
        """Predicted wall seconds for one costdb record dict."""
        return self.predict(rec.get("flops"), rec.get("bytes_accessed"),
                            rec.get("block_config"), rec.get("backend"))

    # ----------------------------------------------------- calibration
    def calibration(self, records, worst=5):
        """Predicted-vs-measured report over ``records``: aggregate
        stats plus the ``worst`` records by log-error (the
        model-debugging view ``tools/autotune.py --report`` emits)."""
        rows = []
        for rec in records:
            wall = rec.get("wall_s")
            if wall is None or wall <= 0:
                continue
            pred = self.predict_record(rec)
            if pred is None:
                continue
            rows.append({
                "kind": rec.get("kind"), "name": rec.get("name"),
                "measured_s": float(wall), "predicted_s": float(pred),
                "err_factor": float(max(pred, _FLOOR)
                                    / max(wall, _FLOOR)),
                "block_config": rec.get("block_config"),
            })
        if not rows:
            return {"n": 0, "fit": dict(self.stats), "rows": []}
        errs = [abs(math.log(r["err_factor"])) for r in rows]
        rows.sort(key=lambda r: -abs(math.log(r["err_factor"])))
        return {
            "n": len(rows),
            "fit": dict(self.stats),
            "mae_log": sum(errs) / len(errs),
            "geo_err_factor": math.exp(sum(errs) / len(errs)),
            "worst": rows[:worst],
            "rows": rows,
        }

    # ------------------------------------------------------- save/load
    def save(self, path):
        doc = {"schema": SCHEMA, "features": list(FEATURES),
               "theta": self.theta, "l2": self.l2,
               "feature_means": self.feature_means,
               "stats": self.stats}
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, sort_keys=True, indent=1)
        os.replace(tmp, path)
        return path

    @classmethod
    def load(cls, path):
        with open(path) as f:
            doc = json.load(f)
        if doc.get("schema") != SCHEMA:
            raise ValueError("%s: schema %r != %r"
                             % (path, doc.get("schema"), SCHEMA))
        if list(doc.get("features") or ()) != list(FEATURES):
            raise ValueError("%s: feature set %r does not match this "
                             "build's %r — refit the model"
                             % (path, doc.get("features"),
                                list(FEATURES)))
        return cls(theta=doc["theta"], stats=doc.get("stats"),
                   l2=doc.get("l2", 1e-3),
                   feature_means=doc.get("feature_means"))


def fit_cost_model(costdb_path=None, records=None, l2=1e-3):
    """Fit a :class:`CostModel` on ``records``, or on the costdb
    JSONL under ``costdb_path`` (default: ``MXNET_TPU_COSTDB``)."""
    if records is None:
        from ..telemetry import costdb
        path = costdb_path or costdb.db_dir()
        if not path:
            raise ValueError("no records given and MXNET_TPU_COSTDB "
                             "is unset")
        records, _skipped = costdb.read_records(path)
    return CostModel(l2=l2).fit(records)


def load_model(path_or_model):
    """Coerce a path or an already-built model to a :class:`CostModel`
    (the analysis entry points accept either)."""
    if isinstance(path_or_model, CostModel):
        return path_or_model
    return CostModel.load(path_or_model)
