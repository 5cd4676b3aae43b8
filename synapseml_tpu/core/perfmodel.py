"""Learned performance model behind every auto-configuration knob.

One subsystem replaces the independently hand-tuned decision points
(wire-dtype ladder, tree-learner routing, bucket-ladder geometry, dl
``param_sharding``/``accum_steps``, ``partition_stages`` cuts, chunk
geometry) with a single measurement-backed model in the spirit of
"A Learned Performance Model for Tensor Processing Units" (arXiv:2008.01040):

* a **featurizer** maps a candidate configuration (shapes, dtypes, mesh
  fingerprint, wire dtype, chunk geometry, platform) to a numeric feature
  vector (:class:`Candidate`);
* a **regressor** predicts runtime from three sources, in order of trust:
  near-matched replay of recorded training rows, a least-squares fit of
  ``ln(runtime)`` against log1p-features (analytic roofline terms enter as
  features via ``analytic_s``), and the caller's analytic prior alone;
* :func:`predict_runtime` returns ``(seconds, confidence)`` with a
  provenance record of every input;
* :func:`choose` ranks candidates and **falls back to the hand-tuned
  default** whenever confidence is low — callers always keep their
  explicit-flag bypass, so the model can only ever replace a *default*.

Training rows live in ``docs/measurements.jsonl`` (appended by every bench
arm) plus cheap cached micro-probes reused through ``core/tuned.measured_or``.
``SYNAPSEML_TPU_PERFMODEL=0`` disables the model globally (every ``choose``
returns its fallback, tagged ``"disabled"``).

See ``docs/perf-model.md`` for the feature schema and the retrain procedure.
"""
from __future__ import annotations

import collections
import json
import math
import os
import re
import threading
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import tuned

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MEASUREMENTS_JSONL = os.path.join(_REPO, "docs", "measurements.jsonl")
MEASUREMENTS_JSON = os.path.join(_REPO, "docs", "measurements.json")


def _journal_path() -> str:
    """Training-row journal path; ``SYNAPSEML_TPU_PERF_ROWS`` overrides the
    committed ``docs/measurements.jsonl`` (tests point it at a tempdir so
    workloads never match rows captured by real bench runs)."""
    return os.environ.get("SYNAPSEML_TPU_PERF_ROWS") or MEASUREMENTS_JSONL

SCHEMA_VERSION = 1

# Confidence/fallback policy (documented in docs/perf-model.md).
MIN_CONFIDENCE = 0.5       # below this a candidate cannot displace the fallback
HYSTERESIS = 0.05          # predicted win required to move off the fallback
MATCH_DISTANCE = 0.15      # max per-feature log-space distance for a "match"
ANALYTIC_CONFIDENCE = 0.4  # trust in a pure analytic prior (< MIN_CONFIDENCE)
_FIT_MIN_R2 = 0.5          # reject fits that do not explain the data


def enabled() -> bool:
    """Global kill switch: ``SYNAPSEML_TPU_PERFMODEL=0`` disables the model."""
    return os.environ.get("SYNAPSEML_TPU_PERFMODEL", "1") not in ("0", "false")


# ---------------------------------------------------------------------------
# calibration drift: demote a family whose audits go bad
# ---------------------------------------------------------------------------

DRIFT_WINDOW = 8        # audits kept per (kind, platform)
DRIFT_MIN_AUDITS = 5    # don't judge a family on fewer
DRIFT_RATIO = 2.0       # median predicted/observed off by >2x either way


class PerfModelDriftWarning(UserWarning):
    """A decision family's predicted-vs-observed calibration degraded past
    ``DRIFT_RATIO`` (median over the last ``DRIFT_WINDOW`` audits); the
    family is demoted to its hand-tuned fallback until the process restarts
    or :func:`reset_drift` clears it."""


_drift_lock = threading.Lock()
_drift_audits: Dict[Tuple[str, str], collections.deque] = {}
_drift_warned: set = set()


def record_audit(kind: str, ratio: float,
                 platform: Optional[str] = None) -> None:
    """Feed one predicted-over-observed ratio into the drift monitor.

    Called by :meth:`Decision.audit` whenever a call site reports what a
    priced decision actually cost — the audit trail every auto-config
    decision already journals is thereby also the model's health signal.
    Crossing into drift emits one :class:`PerfModelDriftWarning` per
    family per process.
    """
    if not (ratio and math.isfinite(ratio) and ratio > 0):
        return
    key = (str(kind), platform or current_platform())
    with _drift_lock:
        dq = _drift_audits.setdefault(key, collections.deque(
            maxlen=DRIFT_WINDOW))
        dq.append(float(ratio))
        drifted, med = _drift_eval(dq)
        if drifted and key not in _drift_warned:
            _drift_warned.add(key)
            warnings.warn(
                f"perf-model drift: family {key[0]!r} on {key[1]!r} has "
                f"median predicted/observed {med:.2f}x over the last "
                f"{len(dq)} audits (bound {DRIFT_RATIO}x) — demoting to the "
                f"hand-tuned fallback", PerfModelDriftWarning,
                stacklevel=3)


def _drift_eval(ratios) -> Tuple[bool, float]:
    if len(ratios) < DRIFT_MIN_AUDITS:
        return False, 0.0
    med = float(np.median(list(ratios)))
    return (med > DRIFT_RATIO or med < 1.0 / DRIFT_RATIO), med


def drift_demoted(kind: str, platform: Optional[str] = None) -> bool:
    """True when ``kind``'s audited calibration is past the drift bound —
    :func:`choose` then returns the hand-tuned fallback unconditionally."""
    key = (str(kind), platform or current_platform())
    with _drift_lock:
        dq = _drift_audits.get(key)
        return False if dq is None else _drift_eval(dq)[0]


def reset_drift() -> None:
    """Clear the in-process drift state (tests / operator override)."""
    with _drift_lock:
        _drift_audits.clear()
        _drift_warned.clear()


# ---------------------------------------------------------------------------
# candidates, predictions, decisions
# ---------------------------------------------------------------------------

@dataclass
class Candidate:
    """One configuration alternative for a decision point.

    ``kind`` names the decision family (e.g. ``"gbdt_tree_learner"``),
    ``arm`` the alternative (e.g. ``"voting"``).  ``features`` is the
    featurizer output: a flat dict of non-negative numerics describing the
    workload (shapes, bytes, bandwidths).  ``analytic_s`` is an optional
    analytic roofline prior in seconds (or consistent relative units within
    one ``choose`` call).  ``config`` is an opaque payload handed back to
    the caller when this arm wins.
    """

    kind: str
    arm: str
    features: Dict[str, float] = field(default_factory=dict)
    analytic_s: Optional[float] = None
    config: Any = None


@dataclass
class Prediction:
    seconds: float
    confidence: float
    source: str               # "matched" | "fitted" | "analytic" | "none"
    detail: Dict[str, Any] = field(default_factory=dict)


@dataclass
class Decision:
    """Outcome of :func:`choose`, with full provenance for audit trails."""

    kind: str
    arm: str
    config: Any
    predicted_s: Optional[float]
    confidence: float
    used_fallback: bool
    fallback_arm: str
    source: str
    candidates: List[Dict[str, Any]] = field(default_factory=list)
    features: Dict[str, float] = field(default_factory=dict)

    def provenance(self) -> Dict[str, Any]:
        """JSON-safe audit record for model/trainer metadata."""
        return {
            "kind": self.kind,
            "arm": self.arm,
            "predicted_s": self.predicted_s,
            "confidence": round(float(self.confidence), 4),
            "used_fallback": self.used_fallback,
            "fallback_arm": self.fallback_arm,
            "source": self.source,
            "features": {k: float(v) for k, v in self.features.items()},
            "candidates": self.candidates,
        }

    def audit(self, observed_s: Optional[float] = None) -> Dict[str, Any]:
        """Provenance plus predicted-vs-observed, for post-hoc calibration.

        Ratios also feed the in-process drift monitor: a family whose
        audited median goes past ``DRIFT_RATIO`` is demoted to its
        hand-tuned fallback (see :func:`record_audit`)."""
        rec = self.provenance()
        if observed_s is not None:
            rec["observed_s"] = float(observed_s)
            if self.predicted_s and observed_s:
                ratio = float(self.predicted_s) / float(observed_s)
                rec["predicted_over_observed"] = round(ratio, 4)
                record_audit(self.kind, ratio)
        return rec


# ---------------------------------------------------------------------------
# featurizer
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f32": 4.0, "float32": 4.0, "bf16": 2.0, "bfloat16": 2.0,
                "int8": 2.0, "f16": 2.0, "float16": 2.0, "int32": 4.0,
                "f64": 8.0, "float64": 8.0}


def featurize(shape_like: Optional[Sequence[int]] = None,
              dtype: Optional[str] = None,
              mesh: Any = None,
              wire_dtype: Optional[str] = None,
              chunk_rows: Optional[int] = None,
              depth: Optional[int] = None,
              **extra: float) -> Dict[str, float]:
    """Map a candidate configuration to a flat numeric feature dict.

    All values are non-negative floats; distances between feature dicts are
    taken per-key in log1p space, so features should scale multiplicatively
    (rows, bytes, bandwidths), not categorically.  Categorical inputs
    (platform, wire dtype) are folded into numerics (byte widths) or left to
    the ``(kind, arm, platform)`` row key.
    """
    f: Dict[str, float] = {}
    if shape_like is not None:
        dims = [int(d) for d in shape_like]
        f["rows"] = float(dims[0]) if dims else 0.0
        if len(dims) > 1:
            f["cols"] = float(np.prod(dims[1:]))
    if dtype is not None:
        f["dtype_bytes"] = _DTYPE_BYTES.get(str(dtype), 4.0)
    if wire_dtype is not None:
        # int8 wire ships value+count planes: 2 effective bytes (see voting.py)
        f["wire_bytes"] = {"f32": 4.0, "bf16": 8.0 / 3.0,
                           "int8": 2.0}.get(str(wire_dtype), 4.0)
    if mesh is not None:
        try:
            f["workers"] = float(np.prod([d for d in mesh.devices.shape]))
        except Exception:  # feature is best-effort
            pass
    if chunk_rows is not None:
        f["chunk_rows"] = float(chunk_rows)
    if depth is not None:
        f["depth"] = float(depth)
    for k, v in extra.items():
        if v is None:
            continue
        f[k] = float(v)
    return {k: max(0.0, float(v)) for k, v in f.items()}


def current_platform() -> str:
    return tuned.initialized_platform() or "cpu"


def mesh_tag(mesh: Any) -> Optional[str]:
    if mesh is None:
        return None
    try:
        return "x".join(f"{k}{v}" for k, v in
                        zip(mesh.axis_names, mesh.devices.shape))
    except Exception:  # tag is best-effort
        return None


# ---------------------------------------------------------------------------
# training-row store (docs/measurements.jsonl)
# ---------------------------------------------------------------------------

_rows_lock = threading.Lock()
_rows_cache: Dict[str, Any] = {"stat": None, "rows": None}


def append_training_row(kind: str, arm: str, features: Dict[str, float],
                        observed_s: float,
                        platform: Optional[str] = None,
                        mesh: Any = None,
                        captured_at: Optional[str] = None,
                        path: Optional[str] = None,
                        **extra: Any) -> Dict[str, Any]:
    """Append one structured training row to ``docs/measurements.jsonl``.

    Rows are the schema the featurizer consumes: the model's training set
    grows with every bench run.  Writes are single ``O_APPEND`` lines, safe
    under concurrent bench arms.  Unlike ``bench.record_measurement`` these
    rows are honest about platform — a cpu row trains the cpu model and can
    never leak into tpu predictions (rows are keyed by platform).
    """
    if captured_at is None:
        import datetime
        captured_at = datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds")
    row = {
        "perf_row": SCHEMA_VERSION,
        "kind": str(kind),
        "arm": str(arm),
        "features": {k: float(v) for k, v in features.items()},
        "observed_s": float(observed_s),
        "platform": platform or current_platform(),
        "captured_at": captured_at,
    }
    tag = mesh_tag(mesh) if mesh is not None else None
    if tag:
        row["mesh"] = tag
    row.update(extra)
    path = path or _journal_path()
    line = json.dumps(row, sort_keys=True) + "\n"
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
    try:
        os.write(fd, line.encode("utf-8"))
    finally:
        os.close(fd)
    return row


def _parse_journal(path: str) -> List[Dict[str, Any]]:
    rows: List[Dict[str, Any]] = []
    with open(path, "r", encoding="utf-8") as fh:  # host-side journal read, never under trace
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if not isinstance(rec, dict) or not rec.get("perf_row"):
                continue
            if not isinstance(rec.get("features"), dict):
                continue
            try:
                rec["observed_s"] = float(rec["observed_s"])
            except (KeyError, TypeError, ValueError):
                continue
            if rec["observed_s"] <= 0:
                continue
            rows.append(rec)
    return rows


def training_rows(kind: Optional[str] = None,
                  platform: Optional[str] = None,
                  path: Optional[str] = None) -> List[Dict[str, Any]]:
    """Parse training rows from the jsonl journal (mtime/size-cached)."""
    path = path or _journal_path()
    try:
        st = os.stat(path)
        stat_key = (path, st.st_mtime_ns, st.st_size)
    except OSError:
        return []
    with _rows_lock:
        cached = _rows_cache["stat"] == stat_key
        rows = list(_rows_cache["rows"]) if cached else None
    if rows is None:
        # parse OUTSIDE the lock: the journal read is host file I/O and
        # heartbeat/monitor threads price steps through this cache — two
        # racing fills both parse the same snapshot (idempotent), nobody
        # stalls behind the file
        parsed = _parse_journal(path)
        with _rows_lock:
            _rows_cache["stat"] = stat_key
            _rows_cache["rows"] = parsed
        rows = list(parsed)
    if kind is not None:
        rows = [r for r in rows if r.get("kind") == kind]
    if platform is not None:
        rows = [r for r in rows if r.get("platform") == platform]
    return rows


def backfill_training_rows(json_path: Optional[str] = None,
                           jsonl_path: Optional[str] = None) -> int:
    """Convert legacy ``docs/measurements.json`` replay data to perf rows.

    Idempotent: rows carry ``backfilled_from`` = (metric, captured_at) and a
    second run appends nothing.  Only the record family that encodes a real
    A/B is converted: the voting-vs-data collective A/B.
    """
    json_path = json_path or MEASUREMENTS_JSON
    jsonl_path = jsonl_path or _journal_path()
    try:
        with open(json_path, "r", encoding="utf-8") as fh:  # host-side journal read, never under trace
            recs = json.load(fh)
    except (OSError, ValueError):
        return 0
    have = {tuple(r.get("backfilled_from", ()))
            for r in training_rows(path=jsonl_path)}
    added = 0
    for rec in recs if isinstance(recs, list) else []:
        metric = rec.get("metric")
        src = (metric, rec.get("captured_at"))
        if src in have:
            continue
        platform = rec.get("platform", "cpu").split("-")[0]
        if metric == "gbdt_voting_vs_data_parallel_speedup" and \
                "mesh" in rec.get("platform", ""):
            # rates are embedded in the unit string: "... voting 3856 r-i/s
            # ... data-parallel 26600 r-i/s ..."
            m = re.search(r"voting ([\d.]+) r-i/s.*data-parallel ([\d.]+) "
                          r"r-i/s", rec.get("unit", ""))
            if not m:
                continue
            workers = rec.get("platform", "").rsplit("-", 1)[-1]
            feats = {"workers": float(workers)} if workers.isdigit() else {}
            cm = re.search(r"(\d+) cols", rec.get("unit", ""))
            if cm:
                feats["nfeat"] = float(cm.group(1))
            for arm, rate in (("voting", m.group(1)), ("data", m.group(2))):
                append_training_row(
                    "gbdt_tree_learner", arm, feats, 1.0 / float(rate),
                    platform=platform, captured_at=rec.get("captured_at"),
                    path=jsonl_path, backfilled_from=list(src),
                    unit="s/row-iteration")
                added += 1
            have.add(src)
    return added


# ---------------------------------------------------------------------------
# the regressor
# ---------------------------------------------------------------------------

def _feature_distance(a: Dict[str, float], b: Dict[str, float]) -> float:
    """Max per-key distance in log1p space; missing keys count as far."""
    keys = set(a) | set(b)
    if not keys:
        return 0.0
    worst = 0.0
    for k in keys:
        if k not in a or k not in b:
            return math.inf
        worst = max(worst, abs(math.log1p(a[k]) - math.log1p(b[k])))
    return worst


def predict_runtime(candidate: Candidate,
                    rows: Optional[List[Dict[str, Any]]] = None,
                    platform: Optional[str] = None) -> Tuple[float, float]:
    """Predict runtime for one candidate: ``(seconds, confidence)``.

    Prefers near-matched replay of recorded rows, then a least-squares fit
    of ``ln(observed_s)`` on ``[1, log1p(features)...]``, then the caller's
    analytic prior.  Use :func:`predict` for the full provenance record.
    """
    p = predict(candidate, rows=rows, platform=platform)
    return p.seconds, p.confidence


def predict(candidate: Candidate,
            rows: Optional[List[Dict[str, Any]]] = None,
            platform: Optional[str] = None) -> Prediction:
    platform = platform or current_platform()
    if rows is None:
        rows = training_rows(kind=candidate.kind, platform=platform)
    arm_rows = [r for r in rows if r.get("arm") == candidate.arm]

    # 1. near-matched replay: the strongest evidence is a recorded run of
    #    this very (kind, arm, platform) at (log-)nearby feature values.
    scored = []
    for r in arm_rows:
        d = _feature_distance(candidate.features, r["features"])
        if d <= MATCH_DISTANCE:
            scored.append((d, r["observed_s"]))
    if scored:
        weights = [math.exp(-(d / MATCH_DISTANCE) ** 2) for d, _ in scored]
        sec = sum(w * s for w, (_, s) in zip(weights, scored)) / sum(weights)
        d_best = min(d for d, _ in scored)
        conf = max(0.6, min(0.95, 0.92 - d_best))
        return Prediction(sec, conf, "matched",
                          {"rows_matched": len(scored),
                           "distance": round(d_best, 4)})

    # 2. fitted residual model: ln(observed) ~ [1, log1p(f_k)...] by least
    #    squares across this arm's rows (analytic terms enter as features).
    keys = sorted({k for r in arm_rows for k in r["features"]})
    usable = [r for r in arm_rows
              if all(k in r["features"] for k in keys)]
    if keys and len(usable) >= len(keys) + 2 and \
            all(k in candidate.features for k in keys):
        X = np.array([[1.0] + [math.log1p(r["features"][k]) for k in keys]
                      for r in usable])
        y = np.array([math.log(r["observed_s"]) for r in usable])
        if np.linalg.matrix_rank(X) == X.shape[1]:
            beta, *_ = np.linalg.lstsq(X, y, rcond=None)
            resid = y - X @ beta
            ss_tot = float(((y - y.mean()) ** 2).sum())
            r2 = 1.0 - float((resid ** 2).sum()) / ss_tot if ss_tot > 0 else 0.0
            if r2 >= _FIT_MIN_R2:
                xc = np.array([1.0] + [math.log1p(candidate.features[k])
                                       for k in keys])
                sec = float(math.exp(float(xc @ beta)))
                conf = min(0.75, 0.5 + 0.25 * r2)
                # extrapolating past the training envelope is a guess
                for j, k in enumerate(keys, start=1):
                    lo, hi = X[:, j].min(), X[:, j].max()
                    if not (lo - 1.0 <= xc[j] <= hi + 1.0):
                        conf = min(conf, ANALYTIC_CONFIDENCE)
                return Prediction(sec, conf, "fitted",
                                  {"rows_fit": len(usable),
                                   "r2": round(r2, 4), "keys": keys})

    # 3. analytic roofline prior from the caller (bandwidth probes etc.)
    if candidate.analytic_s is not None:
        return Prediction(float(candidate.analytic_s), ANALYTIC_CONFIDENCE,
                          "analytic", {})

    return Prediction(math.inf, 0.0, "none", {})


def choose(candidates: Sequence[Candidate],
           fallback_arm: str,
           min_confidence: float = MIN_CONFIDENCE,
           hysteresis: float = HYSTERESIS,
           platform: Optional[str] = None) -> Decision:
    """Rank candidates; fall back to the hand-tuned default on low confidence.

    The fallback arm (the existing hand-tuned choice) wins unless some other
    candidate is predicted at least ``hysteresis`` faster *and* both sides of
    that comparison are confident.  Every input lands in the returned
    :class:`Decision` so call sites can audit the choice into metadata.
    """
    if not candidates:
        raise ValueError("choose() needs at least one candidate")
    kind = candidates[0].kind
    platform = platform or current_platform()
    by_arm = {c.arm: c for c in candidates}
    fb = by_arm.get(fallback_arm, candidates[0])

    if not enabled():
        return Decision(kind, fb.arm, fb.config, None, 0.0, True,
                        fallback_arm, "disabled", [], dict(fb.features))
    if drift_demoted(kind, platform):
        # audited calibration for this family went bad — the hand-tuned
        # fallback wins until the process restarts or reset_drift()
        return Decision(kind, fb.arm, fb.config, None, 0.0, True,
                        fallback_arm, "drift_demoted", [], dict(fb.features))

    rows = training_rows(kind=kind, platform=platform)
    preds = {c.arm: predict(c, rows=rows, platform=platform)
             for c in candidates}
    prov = [{"arm": a, "predicted_s": (None if math.isinf(p.seconds)
                                       else round(p.seconds, 9)),
             "confidence": round(p.confidence, 4), "source": p.source,
             **p.detail}
            for a, p in preds.items()]

    confident = {a: p for a, p in preds.items()
                 if p.confidence >= min_confidence
                 and not math.isinf(p.seconds)}
    fbp = preds[fb.arm]
    pick = fb
    used_fallback = True
    if confident:
        best_arm = min(confident, key=lambda a: confident[a].seconds)
        best = confident[best_arm]
        if best_arm == fb.arm:
            pick, used_fallback = by_arm[best_arm], False
        elif fb.arm in confident and \
                best.seconds < confident[fb.arm].seconds * (1 - hysteresis):
            # only displace the hand-tuned default on a confident, clear win
            pick, used_fallback = by_arm[best_arm], False
    p = preds[pick.arm]
    return Decision(
        kind, pick.arm, pick.config,
        None if math.isinf(p.seconds) else float(p.seconds),
        float(p.confidence) if not used_fallback else float(fbp.confidence),
        used_fallback, fallback_arm, p.source if not used_fallback
        else (fbp.source if not math.isinf(fbp.seconds) else "fallback"),
        prov, dict(pick.features))


# ---------------------------------------------------------------------------
# micro-probes (cached through core/tuned.measured_or)
# ---------------------------------------------------------------------------

def link_bandwidth(mesh: Any) -> Optional[float]:
    """Cached ~1MB timed all-reduce link probe (bytes/s), or None."""
    try:
        from ..parallel.collectives import probe_link_bandwidth
        fp = tuned.mesh_fingerprint(mesh)
        return float(tuned.measured_or(("link_bytes_per_s", fp),
                                       lambda: probe_link_bandwidth(mesh)))
    except Exception:  # probe failure means "unknown"
        return None


def h2d_bandwidth() -> Optional[float]:
    """Cached 4MiB host-to-device copy probe (bytes/s), or None."""
    try:
        from ..io.ingest import _probe_h2d_bandwidth
        return float(_probe_h2d_bandwidth())
    except Exception:  # probe failure means "unknown"
        return None


# ---------------------------------------------------------------------------
# per-picker suggestion helpers
# ---------------------------------------------------------------------------

def suggest_wire_dtype(n_rows: float, nfeat: float, workers: float,
                       max_bin: float, num_leaves: float,
                       link_bps: Optional[float],
                       fallback: str = "f32",
                       platform: Optional[str] = None) -> Tuple[str, Decision]:
    """Suggest ``hist_allreduce_dtype`` for distributed histogram merges.

    Analytic prior: per-tree collective seconds = splits x histogram wire
    bytes / link bandwidth (matching ``voting.collective_bytes_per_split``).
    Recorded bench rows (kind ``gbdt_wire_dtype``) override it when matched.
    """
    cands = []
    for wd in ("f32", "bf16", "int8"):
        feats = featurize(wire_dtype=wd, rows=n_rows, nfeat=nfeat,
                          workers=workers, max_bin=max_bin,
                          num_leaves=num_leaves)
        analytic = None
        if link_bps:
            wire_bytes = feats["wire_bytes"]
            per_split = nfeat * max_bin * 3.0 * wire_bytes
            analytic = max(1, num_leaves - 1) * per_split / float(link_bps)
        cands.append(Candidate("gbdt_wire_dtype", wd, feats,
                               analytic_s=analytic, config=wd))
    dec = choose(cands, fallback_arm=fallback, platform=platform)
    return dec.arm, dec


def suggest_bucket_growth(max_batch_size: int,
                          fallback: float = 2.0,
                          platform: Optional[str] = None
                          ) -> Tuple[float, Decision]:
    """Suggest the bucket-ladder growth factor for :class:`BucketedRunner`.

    No analytic prior — compile cost vs padding waste is exactly the kind of
    trade only measurement settles. Arms come from recorded ladder A/Bs
    (kind ``serving_bucket_growth``, written by the ci.sh auto-config
    guard's micro benchmark); absent a near-matched row the hand-tuned 2.0
    wins.
    """
    cands = [Candidate("serving_bucket_growth", f"g{g}",
                       featurize(max_batch_size=max_batch_size),
                       config=g)
             for g in (1.5, 2.0, 4.0)]
    dec = choose(cands, fallback_arm=f"g{fallback}", platform=platform)
    return (float(dec.config) if dec.config is not None else fallback), dec


def suggest_param_sharding(param_bytes: float, batch: float, devices: float,
                           stages: float = 0.0,
                           fallback: str = "replicated",
                           platform: Optional[str] = None
                           ) -> Tuple[str, Decision]:
    """Suggest dl ``param_sharding`` from recorded sharding-arm step times."""
    arms = ["replicated", "zero"] + (["pipeline"] if stages >= 2 else [])
    cands = [Candidate("dl_param_sharding", a,
                       featurize(param_bytes=param_bytes, batch=batch,
                                 workers=devices,
                                 **({"stages": stages} if a == "pipeline"
                                    else {})),
                       config=a)
             for a in arms]
    dec = choose(cands, fallback_arm=fallback, platform=platform)
    return dec.arm, dec


def suggest_accum_steps(batch: float, param_bytes: float,
                        state_budget_bytes: Optional[float],
                        fallback: int = 1,
                        platform: Optional[str] = None
                        ) -> Tuple[int, Decision]:
    """Suggest gradient-accumulation steps.

    Analytic prior: accumulation trades per-step activation memory for more
    dispatches — runtime grows roughly linearly in the fixed per-microbatch
    overhead, so the model prefers the smallest ``accum_steps`` whose
    activation slice fits the state budget (when one is known).
    """
    divisors = [k for k in (1, 2, 4, 8) if batch % k == 0 and k <= batch]
    cands = []
    for k in divisors:
        feats = featurize(batch=batch, param_bytes=param_bytes, accum=k)
        # fixed dispatch overhead per microbatch dominates on small batches
        analytic = 1.0 + 0.05 * (k - 1)
        if state_budget_bytes and param_bytes / k > state_budget_bytes:
            analytic = None  # does not fit: never an analytic winner
        cands.append(Candidate("dl_accum_steps", f"a{k}", feats,
                               analytic_s=analytic, config=k))
    dec = choose(cands, fallback_arm=f"a{fallback}", platform=platform)
    return (int(dec.config) if dec.config is not None else fallback), dec


def suggest_pipeline_schedule(stages: float, microbatches: float,
                              fallback: str = "fill_drain",
                              platform: Optional[str] = None
                              ) -> Tuple[str, Decision]:
    """Suggest fill_drain vs overlap for MPMD pipelines.

    Analytic prior prices the bubble: fill_drain idles ``(S-1)/(M+S-1)`` of
    the schedule, overlap hides roughly half the bubble behind compute at
    some dispatch overhead.  Recorded rows from
    ``bench_dl_overlap_pipeline`` (kind ``dl_pipeline_schedule``) take over
    once captured on the target fabric.
    """
    S, M = max(1.0, stages), max(1.0, microbatches)
    total = M + S - 1.0
    cands = [
        Candidate("dl_pipeline_schedule", "fill_drain",
                  featurize(stages=S, microbatches=M),
                  analytic_s=total / M, config="fill_drain"),
        Candidate("dl_pipeline_schedule", "overlap",
                  featurize(stages=S, microbatches=M),
                  analytic_s=(M + 0.5 * (S - 1.0)) / M * 1.02,
                  config="overlap"),
    ]
    dec = choose(cands, fallback_arm=fallback, platform=platform)
    return dec.arm, dec


def suggest_seq_attention(seq_len: float, heads: float, seq_shards: float,
                          head_dim: float = 64.0, batch: float = 1.0,
                          link_bps: Optional[float] = None,
                          fallback: str = "ring",
                          platform: Optional[str] = None
                          ) -> Tuple[str, Decision]:
    """Suggest ring vs Ulysses for seq-sharded self-attention.

    Analytic prior prices per-layer wire bytes over the ``seq`` axis: ring
    rotates the local K/V blocks ``p-1`` times (each step moves
    ``2·B·(S/p)·H·D`` activation bytes point-to-point, overlapped with the
    block compute), while Ulysses re-shards with four all-to-alls (q/k/v in,
    output back), each moving ``(p-1)/p`` of the full ``B·S·H·D`` activation.
    Ring's ppermute overlaps with compute, so its wire time is discounted;
    Ulysses is only a candidate when heads divide by the shard count (the
    head-scatter all-to-all needs even splits).  Recorded rows from
    ``bench_dl_seq`` (kind ``seq_attention``) take over once captured on the
    target fabric.
    """
    p = max(1.0, seq_shards)
    S, H, D, B = (max(1.0, seq_len), max(1.0, heads), max(1.0, head_dim),
                  max(1.0, batch))
    elem_bytes = 4.0 * B * S * H * D
    # probed link bandwidth when the caller has one; a nominal constant
    # otherwise (the arm ordering is invariant to the constant)
    link = float(link_bps) if link_bps else 1e9
    feats = featurize(seq_len=S, heads=H, seq_shards=p, head_dim=D, batch=B)
    # ring: (p-1) rotations of local K+V, half hidden behind block compute
    ring_s = (p - 1.0) * 2.0 * (elem_bytes / p) / link * 0.5
    # ulysses: 4 unoverlapped all-to-alls of (p-1)/p of the activation
    uly_s = 4.0 * elem_bytes * (p - 1.0) / p / link
    cands = [Candidate("seq_attention", "ring", feats,
                       analytic_s=ring_s, config="ring")]
    if H % p == 0:
        cands.append(Candidate("seq_attention", "ulysses", feats,
                               analytic_s=uly_s, config="ulysses"))
    dec = choose(cands, fallback_arm=fallback, platform=platform)
    return dec.arm, dec


def suggest_stage_cuts(unit_costs: Sequence[float], num_stages: int
                       ) -> Tuple[List[int], Decision]:
    """Cost-balanced contiguous pipeline cuts (min-max stage cost by DP).

    Deterministic given costs; the "model" here is the per-unit cost vector
    (parameter bytes or measured per-unit step time).  Returns stage sizes
    summing to ``len(unit_costs)``.  Falls back to count-balanced cuts when
    costs are degenerate.
    """
    n, S = len(unit_costs), int(num_stages)
    base, rem = divmod(n, S)
    fallback_sizes = [base + (1 if s < rem else 0) for s in range(S)]
    costs = [max(0.0, float(c)) for c in unit_costs]
    if n < S or S < 1 or sum(costs) <= 0:
        dec = Decision("dl_stage_cuts", "count_balanced", fallback_sizes,
                       None, 0.0, True, "count_balanced", "fallback",
                       [], {"units": float(n), "stages": float(S)})
        return fallback_sizes, dec
    prefix = np.concatenate([[0.0], np.cumsum(costs)])
    # dp[s][i]: minimal max-stage-cost splitting units[:i] into s stages
    INF = math.inf
    dp = [[INF] * (n + 1) for _ in range(S + 1)]
    cut = [[0] * (n + 1) for _ in range(S + 1)]
    dp[0][0] = 0.0
    for s in range(1, S + 1):
        for i in range(s, n + 1):
            for j in range(s - 1, i):
                cost = max(dp[s - 1][j], prefix[i] - prefix[j])
                if cost < dp[s][i]:
                    dp[s][i], cut[s][i] = cost, j
    sizes: List[int] = []
    i = n
    for s in range(S, 0, -1):
        j = cut[s][i]
        sizes.append(i - j)
        i = j
    sizes.reverse()
    if min(sizes) < 1:  # degenerate costs: keep the count-balanced default
        sizes = fallback_sizes
    used_fallback = sizes == fallback_sizes
    dec = Decision("dl_stage_cuts", "cost_balanced", sizes,
                   float(dp[S][n]), 0.9, used_fallback, "count_balanced",
                   "analytic", [{"arm": "cost_balanced",
                                 "max_stage_cost": float(dp[S][n])}],
                   {"units": float(n), "stages": float(S)})
    return sizes, dec


def suggest_chunk_rows(row_bytes: float, depth: int,
                       fallback_rows: int,
                       h2d_bps: Optional[float] = None,
                       platform: Optional[str] = None
                       ) -> Tuple[int, Decision]:
    """Suggest streaming chunk rows for ``io/ingest``.

    Candidates are a power-of-two ladder around the probe-derived fallback;
    analytic prior per row: ``row_bytes / h2d_bw + dispatch_overhead /
    chunk_rows``.  Only a measured match (kind ``io_chunk_rows``) displaces
    the probe formula — the formula *is* the analytic optimum.
    """
    ladder = sorted({fallback_rows} |
                    {1 << p for p in range(13, 21)
                     if (1 << p) <= 4 * fallback_rows
                     and (1 << p) >= max(1024, fallback_rows // 4)})
    dispatch_s = 2e-4  # per-chunk dispatch + pump hand-off overhead
    cands = []
    for cr in ladder:
        analytic = None
        if h2d_bps:
            analytic = row_bytes / float(h2d_bps) + dispatch_s / float(cr)
        cands.append(Candidate(
            "io_chunk_rows", f"c{cr}",
            featurize(row_bytes=row_bytes, depth=depth, chunk_rows=cr),
            analytic_s=analytic, config=int(cr)))
    dec = choose(cands, fallback_arm=f"c{fallback_rows}", platform=platform)
    return (int(dec.config) if dec.config is not None else fallback_rows), dec


SECOND_PASS_BUDGET = 0.10  # exact re-sketch may cost this fraction of training


def suggest_sketch_second_pass(n_rows: float, nfeat: float,
                               rows_per_s: Optional[float],
                               train_s_estimate: Optional[float],
                               platform: Optional[str] = None
                               ) -> Tuple[bool, Decision]:
    """Decide whether an exact second sketch pass is worth it (ROADMAP 2d).

    When the streaming sketch fell back to reservoir sampling
    (``sketch_exact=False``), an extra full pass buys exact bin boundaries.
    This is not a runtime argmin — the pass is pure extra cost paid for
    sketch quality — so the rule is a budget: take the pass when its
    predicted cost (measured rows of kind ``gbdt_sketch_pass`` when
    available, else the analytic ``rows / sketch_rate`` prior) is under
    ``SECOND_PASS_BUDGET`` of the estimated training cost.  The fallback
    (skip) preserves today's behavior whenever the model cannot price it.
    """
    analytic = n_rows / float(rows_per_s) if rows_per_s else None
    cand = Candidate("gbdt_sketch_pass", "exact",
                     featurize(rows=n_rows, nfeat=nfeat),
                     analytic_s=analytic)
    p = predict(cand, platform=platform)
    take = bool(
        enabled() and train_s_estimate
        and not math.isinf(p.seconds)
        and p.confidence >= ANALYTIC_CONFIDENCE
        and p.seconds <= SECOND_PASS_BUDGET * float(train_s_estimate))
    dec = Decision(
        "gbdt_sketch_pass", "exact" if take else "skip", take,
        None if math.isinf(p.seconds) else float(p.seconds),
        float(p.confidence), not take, "skip",
        p.source if take else ("disabled" if not enabled() else p.source),
        [{"arm": "exact",
          "predicted_s": None if math.isinf(p.seconds) else float(p.seconds),
          "confidence": round(p.confidence, 4), "source": p.source,
          "budget_s": (SECOND_PASS_BUDGET * float(train_s_estimate)
                       if train_s_estimate else None)}],
        dict(cand.features))
    return take, dec


__all__ = [
    "Candidate", "Prediction", "Decision", "featurize", "enabled",
    "PerfModelDriftWarning", "record_audit", "drift_demoted", "reset_drift",
    "append_training_row", "training_rows", "backfill_training_rows",
    "predict_runtime", "predict", "choose",
    "link_bandwidth", "h2d_bandwidth",
    "suggest_wire_dtype", "suggest_bucket_growth",
    "suggest_param_sharding", "suggest_accum_steps",
    "suggest_pipeline_schedule", "suggest_seq_attention",
    "suggest_stage_cuts", "suggest_chunk_rows",
    "suggest_sketch_second_pass",
    "MEASUREMENTS_JSONL", "MEASUREMENTS_JSON",
]
