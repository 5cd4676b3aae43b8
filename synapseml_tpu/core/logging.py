"""Structured logging + the span record.

Analog of the reference's ``SynapseMLLogging`` trait (core/.../logging/
SynapseMLLogging.scala: every stage logs construction via logClass and wraps
fit/transform in timed, structured log records) and of the LightGBM phase
instrumentation (lightgbm/.../LightGBMPerformance.scala: InstrumentationMeasures /
TaskInstrumentationMeasures with mark*Start/Stop spans).

``InstrumentationMeasures`` is the one span mechanism of the package: a fit
(the booster's, the trainer's) owns one, opens named spans on it where the
work happens and closes each when that work is done, and the estimator logs
its ``report()`` as one ``trainingMeasures`` record. Each span keeps a record
(name, start, end, parent) in memory and is at the same time a
``jax.profiler.TraceAnnotation``, so in a profiler session the spans lie on
the device trace's clock (SURVEY §5.1). docs/observability.md names every
span and counter.
"""

from __future__ import annotations

import collections
import contextlib
import json
import logging
import re
import threading
import time
from typing import Any, Deque, Dict, NamedTuple, Optional

logger = logging.getLogger("synapseml_tpu")

PROTOCOL_VERSION = "1.0.0"

# --- secret scrubbing --------------------------------------------------------
# Every structured log line passes through scrub_payload + scrub_text before
# it reaches a handler, so a subscription key, SAS signature, bearer token or
# connection string in a param payload / error message can never land in logs.
# Analog (and superset) of the reference's SASScrubber
# (core/.../logging/common/Scrubber.scala: sig=... redaction only).

REDACTED = "####"

# key NAMES whose values are secret wherever they appear in a payload:
# either the whole key is a well-known secret word, or it contains a
# compound secret name (subscriptionKey, apiKey, accountKey, aadToken, ...)
_EXACT_SECRET_KEYS = re.compile(
    r"(?i)^(key|sig|sas|token|secret|password|pwd|auth|authorization|"
    r"bearer|credential|credentials)$")
_COMPOUND_SECRET_KEYS = re.compile(
    r"(?i)(subscription[_-]?key|api[_-]?key|account[_-]?key|shared[_-]?key|"
    r"access[_-]?token|aad[_-]?token|sas[_-]?token|refresh[_-]?token|"
    r"id[_-]?token|client[_-]?secret|connection[_-]?string|"
    r"ocp-apim-subscription-key)")

# value PATTERNS scrubbed out of any logged string (URLs in error messages,
# headers echoed by HTTP exceptions, ...)
_TEXT_PATTERNS = (
    # SAS / query-string signatures and credentials: sig=..., key=..., &c.
    (re.compile(r"(?i)\b(sig|signature|key|token|secret|password|pwd|"
                r"credential|sv|se|st|spr|sp)=([A-Za-z0-9%+/._~-]{8,}"
                r"(?:%3d|=){0,2})"), r"\1=" + REDACTED),
    # Authorization headers / bearer tokens
    (re.compile(r"(?i)\b(bearer|basic)[ :]+[A-Za-z0-9._+/=-]{8,}"),
     r"\1 " + REDACTED),
    # API-key-shaped literals (OpenAI-style)
    (re.compile(r"\bsk-[A-Za-z0-9]{16,}\b"), "sk-" + REDACTED),
    # explicit subscription-key headers serialized into text
    (re.compile(r"(?i)(ocp-apim-subscription-key[\"']?\s*[:=]\s*[\"']?)"
                r"[A-Za-z0-9-]{8,}"), r"\1" + REDACTED),
    # JWTs (three dot-separated base64url segments)
    (re.compile(r"\beyJ[A-Za-z0-9_-]{8,}\.[A-Za-z0-9_-]{8,}"
                r"\.[A-Za-z0-9_-]{8,}\b"), REDACTED),
)


def _is_secret_key(name: str) -> bool:
    return bool(_EXACT_SECRET_KEYS.match(name)
                or _COMPOUND_SECRET_KEYS.search(name))


def scrub_text(s: str) -> str:
    """Redact secret-shaped substrings from free text (error messages, URLs)."""
    for pat, repl in _TEXT_PATTERNS:
        s = pat.sub(repl, s)
    return s


def scrub_payload(obj: Any) -> Any:
    """Recursively redact secret-named fields and secret-shaped strings from
    a structured payload about to be logged."""
    if isinstance(obj, dict):
        return {k: (REDACTED if isinstance(k, str) and _is_secret_key(k)
                    else scrub_payload(v)) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        vals = [scrub_payload(v) for v in obj]
        if hasattr(obj, "_make"):          # NamedTuple
            return type(obj)._make(vals)
        try:
            return type(obj)(vals)
        except TypeError:                  # exotic sequence subclass: the
            return vals                    # scrubbed content matters, not type
    if isinstance(obj, str):
        return scrub_text(obj)
    return obj


def _framework_version() -> str:
    try:
        from .. import __version__

        return __version__
    except Exception:
        return "unknown"


class SynapseMLLogging:
    """Mixin: structured JSON log records for class creation and verbs."""

    def log_class(self) -> None:
        self._log_base("constructor")

    def _log_base(self, method: str, extra: Optional[Dict[str, Any]] = None, level=logging.DEBUG) -> None:
        if not logger.isEnabledFor(level):
            return   # skip payload build + scrub work for disabled levels
        payload = {
            "uid": getattr(self, "uid", None),
            "className": type(self).__name__,
            "method": method,
            "libraryVersion": _framework_version(),
            "protocolVersion": PROTOCOL_VERSION,
        }
        if extra:
            payload.update(extra)
        # scrub twice: structured (secret-named fields) then textual (secret-
        # shaped values that survive json.dumps, e.g. URLs inside messages)
        logger.log(level, scrub_text(json.dumps(scrub_payload(payload),
                                                default=str)))

    @contextlib.contextmanager
    def log_verb(self, verb: str, **info):
        """Time a fit/transform body, logging duration or typed error payloads
        (the logFit/logTransform/logVerb analog)."""
        t0 = time.perf_counter()
        try:
            with _maybe_jax_annotation(f"{type(self).__name__}.{verb}"):
                yield
        except Exception as e:
            self._log_base(verb, {"error": type(e).__name__, "message": str(e)[:500],
                                  **info}, level=logging.ERROR)
            raise
        else:
            ms = (time.perf_counter() - t0) * 1e3
            self._log_base(verb, {"durationMs": round(ms, 3), **info}, level=logging.INFO)


@contextlib.contextmanager
def _maybe_jax_annotation(name: str):
    # guard only annotation setup — never the yield itself (a guarded yield
    # would catch exceptions thrown into the body and yield a second time)
    try:
        import jax.profiler

        ctx = jax.profiler.TraceAnnotation(name)
    except Exception:
        ctx = contextlib.nullcontext()
    with ctx:
        yield


class SpanRecord(NamedTuple):
    """One closed span: times from ``time.perf_counter_ns()``; ``parent`` is
    the name of the span that was open on the same object when this one was
    opened (``None`` at the top level)."""

    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]


class _Span:
    """One open span of an :class:`InstrumentationMeasures`: entered, it
    starts the clock and the profiler annotation of the same name; left, it
    stops both and files the record. ``discard()`` inside the block leaves
    no record (a step that found its iterator empty)."""

    __slots__ = ("_owner", "name", "_ann", "_parent", "start_ns", "_child_ns",
                 "_keep")

    def __init__(self, owner, name, ann):
        self._owner, self.name, self._ann = owner, name, ann
        self._child_ns = 0
        self._keep = True

    def discard(self) -> None:
        self._keep = False

    def __enter__(self):
        owner = self._owner
        self._parent = owner._open
        owner._open = self
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        owner, parent = self._owner, self._parent
        if owner._open is self:
            # (not so when a watchdog gave up on the thread that opened it
            # and the caller has unwound past it: the record is still filed)
            owner._open = parent
        if self._keep:
            owner._close(self, end, parent)
        return False


class InstrumentationMeasures:
    """The span record of one fit — the LightGBMPerformance analog. Usage::

        m = InstrumentationMeasures()
        with m.span("dataPreparation"):
            with m.span("binning"): ...
        m.report()        # {"dataPreparation": s, "dataPreparation/binning": s}
        m.self_seconds()  # each key's seconds less what its children cover
        m.records         # SpanRecord(name, start_ns, end_ns, parent), newest 4,096

    A span closes when its ``with`` block is left, so the block has to end
    where the work is done (``jax.block_until_ready``), not where it is
    dispatched. Every span is also a ``jax.profiler.TraceAnnotation`` of the
    same name (``StepTraceAnnotation`` with ``step_num``): one context
    manager opens and closes both, so in a profiler session the spans lie on
    the device trace's clock. Nothing is written anywhere while a fit runs;
    sums and counts hold every occurrence, ``records`` the newest
    ``MAX_RECORDS``. One object belongs to one fit and one thread at a time.
    """

    MAX_RECORDS = 4096

    def __init__(self):
        self.spans: Dict[str, float] = {}      # key -> seconds, all occurrences
        self.occurrences: Dict[str, int] = {}  # key -> spans closed
        self.counters: Dict[str, int] = {}
        self.records: Deque[SpanRecord] = collections.deque(
            maxlen=self.MAX_RECORDS)
        self._self_s: Dict[str, float] = {}
        self._open: Optional[_Span] = None

    def span(self, name: str, step_num: Optional[int] = None) -> _Span:
        import jax.profiler     # here, so that importing this module stays light

        if step_num is None:
            ann = jax.profiler.TraceAnnotation(name)
        else:
            ann = jax.profiler.StepTraceAnnotation(name, step_num=step_num)
        return _Span(self, name, ann)

    def _close(self, span: _Span, end_ns: int, parent: Optional[_Span]):
        dur = end_ns - span.start_ns
        key = span.name if parent is None else f"{parent.name}/{span.name}"
        self.spans[key] = self.spans.get(key, 0.0) + dur / 1e9
        self.occurrences[key] = self.occurrences.get(key, 0) + 1
        self._self_s[key] = (self._self_s.get(key, 0.0)
                             + max(dur - span._child_ns, 0) / 1e9)
        if parent is not None:
            parent._child_ns += dur
        self.records.append(SpanRecord(
            span.name, span.start_ns, end_ns,
            None if parent is None else parent.name))

    def count(self, name: str, n: int = 1):
        self.counters[name] = self.counters.get(name, 0) + n

    def report(self) -> Dict[str, float]:
        """Seconds summed over the occurrences of every span, a span opened
        inside another under ``<parent's name>/<its name>``, and
        ``count:<name>`` for the counters."""
        out: Dict[str, Any] = dict(self.spans)
        out.update({f"count:{k}": v for k, v in self.counters.items()})
        return out

    def self_seconds(self) -> Dict[str, float]:
        """``report()``'s span keys with the time their child spans cover
        taken out: what a layer spent that no span inside it names."""
        return dict(self._self_s)


# --- structured failure counters --------------------------------------------
# Process-global counters for resilience events (load shedding, deadline
# breaches, breaker trips, retry-budget denials, ...). Counting is separated
# from logging so hot paths pay one dict increment; each event still emits a
# scrubbed structured record at DEBUG for correlation with request logs.
# The chaos suite (tests/test_chaos_serving.py) asserts against these, which
# is what makes failure behavior a CI property instead of folklore.

_FAILURE_LOCK = threading.Lock()
_FAILURE_COUNTS: Dict[str, int] = {}


def record_failure(kind: str, n: int = 1, **detail: Any) -> None:
    """Count one resilience event (dotted name, e.g. ``serving.shed``) and
    emit a structured DEBUG record carrying ``detail`` (scrubbed)."""
    with _FAILURE_LOCK:
        _FAILURE_COUNTS[kind] = _FAILURE_COUNTS.get(kind, 0) + n
    if logger.isEnabledFor(logging.DEBUG):
        payload = {"event": "failure", "kind": kind, "n": n,
                   "protocolVersion": PROTOCOL_VERSION}
        if detail:
            payload.update(detail)
        logger.debug(scrub_text(json.dumps(scrub_payload(payload),
                                           default=str)))


def failure_counts() -> Dict[str, int]:
    """Snapshot of all failure counters (copy — safe to mutate)."""
    with _FAILURE_LOCK:
        return dict(_FAILURE_COUNTS)


def reset_failure_counts() -> None:
    """Zero the counters (test isolation)."""
    with _FAILURE_LOCK:
        _FAILURE_COUNTS.clear()


def retry_with_timeout(fn, retries: int = 3, initial_delay_s: float = 1.0, timeout_s: Optional[float] = None):
    """Reference: core/.../core/utils/FaultToleranceUtils.scala:9-22 (retryWithTimeout)
    and NetworkManager.scala:195-218 (exponential backoff). Host-side only."""
    delay = initial_delay_s
    last_exc: Optional[Exception] = None
    deadline = time.monotonic() + timeout_s if timeout_s else None
    for attempt in range(retries):
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 — generic retry wrapper by design
            last_exc = e
            if deadline and time.monotonic() > deadline:
                break
            if attempt < retries - 1:
                time.sleep(delay)
                delay *= 2
    raise last_exc  # type: ignore[misc]
