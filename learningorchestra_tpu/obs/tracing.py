"""End-to-end job tracing: request IDs and named spans.

Answers "where did this job's 40 seconds go?": a request ID is minted
at the API layer (or taken from the client's ``X-Request-Id`` header,
and echoed on every response), propagated through the job engine into
the worker thread that runs the job, and every interesting interval on
the way — queue wait, chip-lease hold, program compile, per-epoch
steps — is recorded as a named span with start/end/attrs.  On job
completion the span list persists into the artifact's execution ledger
(store/artifacts.py), where ``GET /observability/jobs/<name>/trace``
serves it back as a span tree.

Propagation model: context variables carry (request id, active trace,
current span id) per thread.  The job engine explicitly re-activates
the submitting request's trace inside its worker thread — thread pools
do not inherit context — so spans recorded anywhere down the call
stack (leases, compile cache, the train loop) attach to the right job
with the right parent without any of those layers knowing about HTTP.

Span timestamps anchor to ONE (wall, monotonic) pair captured at trace
creation: durations are monotonic-accurate, wall times are readable.

The span bookkeeping is a no-op when the registry is disabled
(``LO_TPU_OBS_ENABLED=0``) or tracing is off (``LO_TPU_OBS_TRACE=0``);
the fast path out is a single context-variable read.

Every :func:`span` and every :class:`Phases` block is ALSO a
``jax.profiler.TraceAnnotation`` named ``lo:<name>``, whether or not a
job trace is active: a profiler capture (``POST
/observability/profile/start``, a benchmark's traced run) then shows
the program's own intervals in its host plane, on the device trace's
clock.  Outside a profiler session an annotation is a flag check.
"""

from __future__ import annotations

import contextlib
import contextvars
import time
import uuid

from learningorchestra_tpu.concurrency_rt import make_lock

__all__ = [
    "ANNOTATION_PREFIX",
    "JobTrace",
    "Phases",
    "annotation",
    "current_trace",
    "get_request_id",
    "new_request_id",
    "new_trace",
    "record_span",
    "set_request_id",
    "reset_request_id",
    "sampled",
    "set_span_attrs",
    "span",
    "span_tree",
    "activate",
]

_REQUEST_ID: contextvars.ContextVar = contextvars.ContextVar(
    "lo_request_id", default=None
)
_TRACE: contextvars.ContextVar = contextvars.ContextVar(
    "lo_trace", default=None
)
_SPAN: contextvars.ContextVar = contextvars.ContextVar(
    "lo_span", default=None
)


#: What every program interval is called in a profiler trace.
ANNOTATION_PREFIX = "lo:"

_trace_annotation = None  # jax.profiler.TraceAnnotation, on first use


def annotation(name: str, **metadata):
    """A ``jax.profiler.TraceAnnotation`` named ``lo:<name>`` (a context
    manager; ``metadata`` rides as the event's stats, and
    ``set_metadata`` adds more before the block ends)."""
    global _trace_annotation
    if _trace_annotation is None:
        # Not at import: the store-only processes never need jax.
        from jax.profiler import TraceAnnotation

        _trace_annotation = TraceAnnotation
    return _trace_annotation(ANNOTATION_PREFIX + name, **metadata)


# -- request ids --------------------------------------------------------------


def new_request_id() -> str:
    return uuid.uuid4().hex[:16]


def set_request_id(request_id: str | None):
    """Bind the calling thread's current request id; returns the token
    for :func:`reset_request_id`."""
    return _REQUEST_ID.set(request_id)


def reset_request_id(token) -> None:
    _REQUEST_ID.reset(token)


def get_request_id() -> str | None:
    return _REQUEST_ID.get()


# -- traces and spans ---------------------------------------------------------


class JobTrace:
    """Span accumulator for one job.  Thread-safe: the engine worker,
    the train loop and (via the compile cache) coalesced builders may
    all record into it."""

    def __init__(self, job: str, request_id: str | None = None,
                 max_spans: int = 512):
        self.job = job
        self.request_id = request_id
        self.max_spans = int(max_spans)
        self._lock = make_lock("JobTrace._lock")
        self._spans: dict[int, dict] = {}
        self._next_id = 1
        self.dropped = 0
        # One (wall, monotonic) anchor: every span's monotonic stamps
        # convert to wall time through it, so durations stay immune to
        # wall-clock jumps while start/end remain human-readable.
        self._wall0 = time.time()
        self._mono0 = time.monotonic()

    def _wall(self, mono: float) -> float:
        return self._wall0 + (mono - self._mono0)

    def begin(self, name: str, parent: int | None = None,
              attrs: dict | None = None) -> int:
        """Open a span; returns its id, or -1 past the span cap (the
        caller then skips the matching :meth:`end`)."""
        t0 = time.monotonic()
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self.dropped += 1
                return -1
            sid = self._next_id
            self._next_id += 1
            self._spans[sid] = {
                "id": sid,
                "parent": parent,
                "name": name,
                "start": round(self._wall(t0), 6),
                "end": None,
                "durationS": None,
                "attrs": dict(attrs or {}),
                "_t0": t0,
            }
            return sid

    def end(self, sid: int) -> None:
        if sid < 0:
            return
        t1 = time.monotonic()
        with self._lock:
            rec = self._spans.get(sid)
            if rec is None or rec["end"] is not None:
                return
            rec["end"] = round(self._wall(t1), 6)
            rec["durationS"] = round(t1 - rec["_t0"], 6)

    def add_span(self, name: str, t0: float, t1: float,
                 parent: int | None = None,
                 attrs: dict | None = None) -> int:
        """Record an already-elapsed interval (monotonic stamps)."""
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self.dropped += 1
                return -1
            sid = self._next_id
            self._next_id += 1
            self._spans[sid] = {
                "id": sid,
                "parent": parent,
                "name": name,
                "start": round(self._wall(t0), 6),
                "end": round(self._wall(t1), 6),
                "durationS": round(t1 - t0, 6),
                "attrs": dict(attrs or {}),
                "_t0": t0,
            }
            return sid

    def set_attrs(self, sid: int, attrs: dict) -> None:
        """Merge ``attrs`` into an open or ended span's (what a block
        only knows at its end: an epoch's measured cost)."""
        with self._lock:
            rec = self._spans.get(sid)
            if rec is not None:
                rec["attrs"].update(attrs)

    def to_doc(self) -> dict:
        """JSON-safe record for the execution ledger.  Unfinished
        spans (a crash mid-interval) keep ``end: None`` — visibly
        open, never fabricated."""
        with self._lock:
            spans = [
                {k: v for k, v in rec.items() if not k.startswith("_")}
                for _sid, rec in sorted(self._spans.items())
            ]
        return {
            "requestId": self.request_id,
            "job": self.job,
            "spans": spans,
            "droppedSpans": self.dropped,
        }


def sampled(basis: str, fraction: float) -> bool:
    """Deterministic sampling decision for ``basis`` (a request id, or
    the job name when the submission carried none): a retried request
    samples the SAME way, so a drill re-running one request id either
    always has its span tree or never does — no flaky traces."""
    if fraction >= 1.0:
        return True
    if fraction <= 0.0:
        return False
    import zlib

    return (zlib.crc32(basis.encode()) % 10_000) < fraction * 10_000


def new_trace(job: str, request_id: str | None = None) -> JobTrace | None:
    """A JobTrace sized from config, or None when tracing is off or
    the LO_TPU_OBS_TRACE_SAMPLE decision excluded this job — callers
    guard every later touch on that None (a sampled-out job keeps all
    its metrics; only the persisted span tree is skipped)."""
    from learningorchestra_tpu.obs.metrics import get_registry

    registry = get_registry()
    if not registry.trace_enabled:
        return None
    if not sampled(request_id or job,
                   getattr(registry, "trace_sample", 1.0)):
        return None
    return JobTrace(job, request_id, max_spans=registry.max_spans)


def current_trace() -> JobTrace | None:
    return _TRACE.get()


@contextlib.contextmanager
def activate(trace: JobTrace | None, root_span: int | None = None):
    """Bind ``trace`` (and optionally a current span) to the calling
    thread for the with-block — the engine's worker-thread handoff."""
    t_token = _TRACE.set(trace)
    s_token = _SPAN.set(root_span)
    r_token = (
        _REQUEST_ID.set(trace.request_id)
        if trace is not None and trace.request_id else None
    )
    try:
        yield trace
    finally:
        _TRACE.reset(t_token)
        _SPAN.reset(s_token)
        if r_token is not None:
            _REQUEST_ID.reset(r_token)


@contextlib.contextmanager
def span(name: str, **attrs):
    """Record the with-block as a named span on the current trace (no
    bookkeeping when none is active) and, trace or none, as the
    profiler annotation ``lo:<name>``.  Spans opened inside nest under
    it."""
    with annotation(name):
        trace = _TRACE.get()
        if trace is None:
            yield None
            return
        sid = trace.begin(name, parent=_SPAN.get(), attrs=attrs)
        token = _SPAN.set(sid) if sid >= 0 else None
        try:
            yield sid
        finally:
            if token is not None:
                _SPAN.reset(token)
            trace.end(sid)


def set_span_attrs(**attrs) -> None:
    """Add ``attrs`` to the innermost open :func:`span` of the calling
    thread (a no-op when no trace is active)."""
    trace, sid = _TRACE.get(), _SPAN.get()
    if trace is not None and sid is not None:
        trace.set_attrs(sid, attrs)


def record_span(name: str, duration_s: float, **attrs) -> None:
    """Record an interval that just ended (duration known, end = now)
    on the current trace — the cheap form for per-epoch loops that
    already time themselves."""
    trace = _TRACE.get()
    if trace is None:
        return
    t1 = time.monotonic()
    trace.add_span(
        name, t1 - max(0.0, float(duration_s)), t1,
        parent=_SPAN.get(), attrs=attrs,
    )


class _Phase:
    """One named phase of a :class:`Phases`: the reusable with-block."""

    __slots__ = ("_owner", "_name", "_label", "_ann", "_t0")

    def __init__(self, owner: "Phases", name: str):
        self._owner = owner
        self._name = name
        self._label = f"{owner.prefix}.{name}"
        self._ann = None
        self._t0 = 0.0

    def __enter__(self):
        self._ann = annotation(self._label)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        owner, name = self._owner, self._name
        owner.total[name] += dt
        if dt > owner.peak[name]:
            owner.peak[name] = dt
        self._ann.__exit__(*exc)
        return False


class Phases:
    """Where ONE thread's loop spends its time, for loops that have no
    job trace (the decode worker): ``with phases("sync"):`` is the
    profiler annotation ``lo:<prefix>.sync`` and adds the block's
    seconds to ``total["sync"]`` (and raises ``peak["sync"]``, the
    longest single block).  One writer, no lock, no registry call and
    no allocation beyond the annotation; readers on other threads see
    floats that are each whole.  A phase is not entered inside itself."""

    __slots__ = ("prefix", "total", "peak", "_blocks")

    def __init__(self, prefix: str, names: tuple):
        self.prefix = prefix
        self.total = dict.fromkeys(names, 0.0)
        self.peak = dict.fromkeys(names, 0.0)
        self._blocks = {name: _Phase(self, name) for name in names}

    def __call__(self, name: str) -> _Phase:
        return self._blocks[name]


def span_tree(spans: list[dict]) -> list[dict]:
    """Flat parent-linked span list → nested tree (children sorted by
    start time), the shape the trace endpoint serves."""
    nodes = {
        rec["id"]: {**rec, "children": []}
        for rec in spans
        if isinstance(rec.get("id"), int)
    }
    roots: list[dict] = []
    for rec in spans:
        node = nodes.get(rec.get("id"))
        if node is None:
            continue
        parent = nodes.get(rec.get("parent"))
        if parent is not None:
            parent["children"].append(node)
        else:
            roots.append(node)

    def sort_rec(items: list[dict]) -> None:
        items.sort(key=lambda n: (n.get("start") or 0, n["id"]))
        for item in items:
            sort_rec(item["children"])

    sort_rec(roots)
    return roots
