"""The async job engine.

Every reference service runs its pipeline body on a bare thread pool and
signals completion by flipping the ``finished`` boolean in the metadata doc,
recording exceptions as execution documents (reference:
binary_executor_image/binary_execution.py:155-186,
code_executor_image/code_execution.py:149-196 which also captures stdout).

This engine keeps that durable contract but adds what the reference lacks
(SURVEY §5.3):
- explicit job states (pending → running → finished | failed | cancelled)
  persisted in the metadata doc as ``jobState``;
- a process-local registry of live jobs so status/wait/cancel work without
  polling the store;
- structured retry for preemptible hardware: a job function may raise
  ``Preempted`` to request re-execution (TPU preemption is a first-class
  event, not a crash);
- weighted-fair scheduling across job CLASSES (classes = service types),
  the reference's Spark FAIR scheduler pools (reference:
  builder_image/fairscheduler.xml:1-7, projection_image/server.py:51-69
  assign each service a pool so one service's burst can't monopolise
  executors).  Submissions enqueue per class; freed workers are handed
  to classes by weighted round-robin, so a ``function`` flood cannot
  queue-starve a training submission.
"""

from __future__ import annotations

import contextlib
import contextvars
import io
import random
import threading
import time
import traceback
from collections import OrderedDict, deque
from concurrent.futures import Future, InvalidStateError
from typing import Any, Callable

from learningorchestra_tpu import faults
from learningorchestra_tpu.concurrency_rt import make_lock
from learningorchestra_tpu.jobs import cancel as jobs_cancel
from learningorchestra_tpu.jobs import journal as jobs_journal
from learningorchestra_tpu.jobs.cancel import CancelToken
from learningorchestra_tpu.log import capture_thread_stdout, get_logger, kv
from learningorchestra_tpu.obs import tracing
from learningorchestra_tpu.store import ArtifactStore

logger = get_logger("jobs")

#: Which retry attempt the calling job body is running as: 0 on the
#: first execution, N after N preemptions.  Job bodies read it through
#: :func:`current_attempt` to adapt — the executor service resumes a
#: retried train fit from its newest managed checkpoint instead of
#: epoch 0 (services/executor.py), without the engine knowing anything
#: about checkpoints.
_ATTEMPT: contextvars.ContextVar = contextvars.ContextVar(
    "lo_job_attempt", default=0
)


def current_attempt() -> int:
    """0 on a job's first execution, N inside its Nth preemption
    retry.  Valid anywhere down the job body's call stack (the engine
    binds it around each attempt)."""
    return _ATTEMPT.get()


def _flight():
    """Lazy flight-recorder handle (obs/flight.py): dispatch, retry,
    fence and terminal decisions land in the ``jobs`` ring."""
    from learningorchestra_tpu.obs import flight

    return flight


def _current_tenant():
    """The requesting tenant bound by the API tier, or None (lazy
    import keeps jobs.cluster out of the raw-engine import path)."""
    from learningorchestra_tpu.jobs.cluster import current_tenant

    return current_tenant()


def _bundle():
    """Lazy debug-bundle handle (obs/bundle.py): retries-exhausted and
    deadline terminals ask for an incident bundle (no-op unless a
    server wired the singleton)."""
    from learningorchestra_tpu.obs import bundle

    return bundle


def _job_metrics():
    """Engine instrumentation handles, resolved per use so a registry
    reset (tests) takes effect immediately."""
    from learningorchestra_tpu.obs.metrics import get_registry

    reg = get_registry()
    return (
        reg.histogram(
            "lo_jobs_queue_wait_seconds",
            "Queue wait from submit to dispatch, per fairness class.",
            labels=("job_class",),
            buckets=(0.001, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 15.0,
                     60.0, 300.0, 1800.0),
        ),
        reg.counter(
            "lo_jobs_total",
            "Job state transitions by class (finished/failed are "
            "terminal; preempted counts each retry attempt).",
            labels=("job_class", "state"),
        ),
    )


class JobState:
    PENDING = "pending"
    RUNNING = "running"
    FINISHED = "finished"
    FAILED = "failed"
    CANCELLED = "cancelled"


class Preempted(Exception):
    """Raised by a job body to request re-execution after preemption."""


class JobDeadlineExceeded(Exception):
    """A job body ran past its deadline; the watchdog failed the job
    and reclaimed its worker and leases (the body itself cannot be
    killed — it finishes as an abandoned zombie whose result is
    discarded, the same semantics as a gateway-timed-out handler)."""


class JobEngine:
    #: Watchdog poll cadence.  Deadlines are a coarse hang bound, not a
    #: scheduler — sub-100ms precision is not a goal.
    WATCHDOG_INTERVAL_S = 0.1

    def __init__(
        self,
        artifacts: ArtifactStore,
        max_workers: int = 8,
        max_preemption_retries: int = 3,
        class_weights: dict[str, int] | None = None,
        retry_backoff_s: float = 0.05,
        retry_backoff_max_s: float = 5.0,
        deadline_s: float = 0.0,
        shutdown_drain_s: float = 0.0,
    ):
        self.artifacts = artifacts
        self.max_workers = max_workers
        # One dedicated thread per DISPATCHED job, gated by _inflight
        # (< max_workers), not a ThreadPoolExecutor: a fixed pool's
        # own thread cap would silently double the concurrency gate —
        # when the deadline watchdog reclaims a hung job's worker
        # slot, the zombie body still pins its thread, and an
        # equal-sized pool would have no thread left for the very job
        # the reclaim freed a slot for.  Threads are trivial next to
        # job bodies (model fits, dataset loads).
        self._threads: set[threading.Thread] = set()
        self.max_preemption_retries = max_preemption_retries
        # Preemption-retry backoff: attempt N sleeps
        # min(max, base * 2**(N-1)) * jitter, jitter ~ U[0.5, 1.5).
        # Immediate zero-backoff retries would slam a preempting
        # device pool in lockstep with every other retrying job —
        # the thundering-herd the jitter decorrelates.
        self.retry_backoff_s = max(0.0, float(retry_backoff_s))
        self.retry_backoff_max_s = max(0.0, float(retry_backoff_max_s))
        # Default wall-clock bound per dispatched job run (preemption
        # retries included); <= 0 disables.  Per-submit deadline_s
        # overrides.
        self.default_deadline_s = float(deadline_s)
        # Graceful-shutdown drain budget: shutdown(wait=True) waits at
        # most this long for running/queued work, then flips every
        # outstanding body's cancel token and joins with a short grace
        # before abandoning stragglers.  <= 0 keeps the legacy
        # unbounded drain (cooperating bodies still exit early when
        # the watchdog cancels them).  Env: LO_TPU_JOB_DRAIN_S.
        self.shutdown_drain_s = float(shutdown_drain_s)
        # Chip-lease pool (set by the service context): the deadline
        # watchdog revokes an expired job's leases through it so the
        # zombie body cannot pin chips it no longer owns.
        self.leaser = None
        # name -> dispatch record for RUNNING jobs ({t0, deadline,
        # future, job_class, ctl}); the watchdog scans it.
        self._running_recs: dict[str, dict] = {}
        self._watchdog: threading.Thread | None = None
        self._watchdog_wake = threading.Event()
        self._futures: dict[str, Future] = {}
        self._last_tracebacks: dict[str, str] = {}
        self._lock = make_lock("JobEngine._lock")
        # Weighted-fair dispatch state: per-class FIFO queues served by
        # weighted round-robin as workers free up.  A class's weight is
        # how many consecutive dispatches it gets per turn (default 1 —
        # equal shares, the reference fairscheduler's FAIR default).
        self.class_weights = dict(class_weights or {})
        self._queues: dict[str, deque] = {}
        self._rr_order: list[str] = []
        self._rr_idx = 0
        self._credits: dict[str, int] = {}
        self._inflight = 0
        self._shutdown = False
        # Warm-start hints (train/compile_cache.py): services tag a
        # submission with a program key and report it warm once the
        # job's compiled programs are cached; within a class's WRR
        # turn the dispatcher prefers queued jobs whose programs are
        # already compiled, so a freed worker starts stepping instead
        # of tracing.  Bounded FIFO — a hint registry, not a ledger.
        self._warm_keys: "OrderedDict[str, None]" = OrderedDict()
        self._max_warm_keys = 512
        # Starvation bound: after this many CONSECUTIVE warm bypasses
        # of a class's FIFO head, the head dispatches regardless — a
        # sustained stream of warm submissions cannot pin a cold job
        # in the queue forever.
        self._warm_bypass: dict[str, int] = {}
        self._max_warm_bypass = 4
        # Optional push-notification sink (services/webhooks.py): set
        # by the service context; completion paths call _notify.
        self.notifier = None
        # Crash-durable job journal (jobs/journal.py): set by the
        # service context.  Every state transition is recorded ahead
        # of its in-memory commit (group-committed through the
        # store's WAL by the journal flusher), and terminal commits
        # are fenced against the store's current engine epoch.  None
        # (raw engines, tests) disables both.
        self.journal = None
        # Cluster coordinator (jobs/cluster.py): set by the service
        # context when multi-engine dispatch is on.  Every dispatch
        # must CLAIM its job in the store-backed claim table before
        # running (a lost claim means a peer engine owns it — the
        # body never starts here).  None keeps the single-engine hot
        # path at one attribute check.
        self.cluster = None
        # Per-tenant admission counters (jobs/cluster.py
        # TenantAdmission): set by the service context when tenant
        # quotas are configured; the engine maintains queued/running
        # counts at submit/dispatch/terminal.  None disables.
        self.admission = None
        # Nested tenant fairness state: per-class last-served tenant
        # for the round-robin inside _pop_queued_locked.  The scan is
        # gated on _tenant_seen so untenanted deployments keep the
        # byte-identical popleft path.
        self._tenant_rr: dict[str, str] = {}
        self._tenant_seen = False

    def _journal(self, name: str, event: str, **fields) -> None:
        """Append one transition record; never raises (a journaling
        failure is counted and logged inside the journal — it must
        not take down the engine)."""
        if self.journal is not None:
            self.journal.append(event, name, **fields)

    def _fence_refused(self, name: str, req: dict) -> bool:
        """True when the calling body's engine epoch is stale: a newer
        recovery owns the store's metadata now — every terminal write
        below the check must be skipped (no lost-updates, no
        double-published state)."""
        if self.journal is None:
            return False
        try:
            self.journal.fence_check()
        except jobs_journal.StaleEpochError as exc:
            logger.error(kv(job=name, state="fenced",
                            error=str(exc), **req))
            _flight().record(
                "jobs", "fence_refused", job=name, error=str(exc),
            )
            return True
        return False

    def _notify(self, name: str, event: str) -> None:
        """Fire artifact state-change webhooks; never raises, never
        blocks (delivery is a daemon thread inside the notifier)."""
        if self.notifier is None:
            return
        try:
            meta = self.artifacts.metadata.read(name) or {}
            self.notifier.notify(name, event, meta)
        except Exception:  # noqa: BLE001 — jobs must finish regardless
            pass

    # -- submission -----------------------------------------------------------

    def submit(
        self,
        name: str,
        fn: Callable[[], Any],
        *,
        description: str | None = None,
        method: str | None = None,
        parameters: Any = None,
        capture_stdout: bool = False,
        on_success: Callable[[Any], dict | None] | None = None,
        job_class: str = "default",
        warm_key: str | None = None,
        deadline_s: float | None = None,
    ) -> Future:
        """Run ``fn`` asynchronously as the job for artifact ``name``.

        The artifact's metadata document must already exist (services create
        it before submitting, exactly as the reference creates metadata then
        spawns the thread — the HTTP response returns before the work runs).

        ``on_success(result)`` may return extra metadata fields to merge into
        the finished metadata doc (e.g. result row counts, checkpoint paths).

        ``job_class`` is the fairness pool (services pass their service
        type): queued work is dispatched to freed workers by weighted
        round-robin across classes, not global FIFO.

        ``warm_key``, when given, is the job's compiled-program tag:
        once any job reports it warm (:meth:`note_warm`, fed from
        train/compile_cache.py), queued jobs carrying the same tag are
        preferred WITHIN their class's round-robin turn — cross-class
        fairness is untouched; the hint only reorders one class's
        queue so freed workers favor zero-trace starts.

        ``deadline_s`` bounds the job body's wall clock per dispatch
        (None inherits the engine default, ``<= 0`` disables): past
        it, the watchdog marks the job failed, reclaims its worker
        slot and chip leases, and resolves the future with
        :class:`JobDeadlineExceeded`; the unkillable body finishes as
        an abandoned zombie whose writes are discarded.
        """
        # Observability: the submitting request's id (minted/echoed at
        # the API layer) rides into the job's metadata, log lines and
        # trace; the trace collects queue-wait/lease/compile/epoch
        # spans and persists into the execution ledger on completion.
        request_id = tracing.get_request_id()
        trace = tracing.new_trace(name, request_id)
        t_submit = time.monotonic()
        # The requesting tenant (bound from the X-Tenant header at the
        # API tier) rides into the queue entry for nested fair-share
        # dispatch and into the metadata for attribution.
        tenant = _current_tenant()
        # Persist the request parameters NOW, not only in the terminal
        # ledger record: a job killed mid-run (process death, store
        # failover) otherwise leaves no parameters anywhere, and the
        # recovery story — "bare PATCH re-uses the last recorded
        # parameters" — would be unfulfillable for a first run.
        stamp = {}
        if parameters is not None:
            stamp["requestParameters"] = parameters
        if request_id:
            stamp["requestId"] = request_id
        if tenant:
            stamp["tenant"] = tenant
        if stamp:
            try:
                self.artifacts.metadata.update(name, stamp)
            except Exception:  # noqa: BLE001 — recording is best-effort
                pass

        # Deadline control block, shared with the watchdog: once it
        # flips ``expired`` the (unkillable) body becomes a zombie —
        # every terminal write below checks it and discards instead of
        # overwriting the watchdog's recorded failure.
        ctl = {"expired": False}
        # Cooperative-cancellation token, bound around the dispatch so
        # the body can poll jobs_cancel.cancel_requested() anywhere
        # down its stack.  The watchdog flips it on deadline expiry
        # (zombies exit early instead of running to completion
        # discarded) and the bounded shutdown drain flips it when its
        # budget runs out.
        token = CancelToken()

        def run() -> Any:
            # Epoch stamp: the body carries the engine epoch of ITS
            # dispatch; terminal commits and artifact publications
            # compare it against the store's durable epoch (fencing).
            epoch = (
                self.journal.epoch if self.journal is not None
                else None
            )
            # Cluster claim: in the multi-engine world a dispatch may
            # only execute after winning the store-backed claim CAS —
            # a lost claim means a peer engine owns this job (its own
            # dispatch or a steal) and this future resolves None.  Any
            # claim-path error (chaos, store wobble) is treated as
            # LOST, never as a crash: the peer's copy still runs.
            claim_ctx = contextlib.nullcontext()
            if self.cluster is not None:
                try:
                    owned = self.cluster.claim(
                        name, info.get("enqueued_at")
                    )
                except Exception:  # noqa: BLE001
                    owned = False
                if not owned:
                    if self.admission is not None:
                        self.admission.note_dequeued(tenant)
                    _flight().record(
                        "jobs", "claim_lost", job=name,
                        jobClass=job_class,
                    )
                    logger.info(kv(job=name, state="claim_lost"))
                    return None
                from learningorchestra_tpu.jobs.cluster import bind_claim

                claim_ctx = bind_claim(name)
            if self.admission is not None:
                self.admission.note_dispatch(tenant, job_class)
            try:
                with jobs_cancel.bind(token), \
                        jobs_journal.stamp(epoch), claim_ctx:
                    return _run_attempts()
            finally:
                if self.admission is not None:
                    self.admission.note_done(tenant, job_class)
                if self.cluster is not None:
                    try:
                        self.cluster.release(name)
                    except Exception:  # noqa: BLE001 — release is
                        pass  # best-effort; the lease TTL reclaims

        def _run_attempts() -> Any:
            meta = self.artifacts.metadata
            ledger = self.artifacts.ledger
            attempts = 0
            t_start = time.monotonic()
            queue_wait_hist, jobs_total = _job_metrics()
            queue_wait_hist.observe(
                t_start - t_submit, job_class=job_class
            )
            if trace is not None:
                trace.add_span(
                    "queue_wait", t_submit, t_start,
                    attrs={"class": job_class},
                )
            job_sid = None  # the CURRENT attempt's span

            def trace_doc():
                """Finalize + snapshot the trace for a TERMINAL ledger
                record (None when tracing is off).  Ends the attempt
                span first, so the recorded durations cover exactly
                what ran."""
                if trace is None:
                    return None
                if job_sid is not None:
                    # None before the first attempt span begins (a
                    # cancel landing at the loop top).
                    trace.end(job_sid)
                return trace.to_doc()

            # req=<id> on every engine log line for this job: the one
            # grep key tying logs, metadata and the span tree together.
            req = {"req": request_id} if request_id else {}

            def _commit_cancelled(detail: str | None = None):
                """Terminal bookkeeping for a RUNNING job cancelled
                via the REST surface: the body wound down
                cooperatively (or died doing so) — record CANCELLED,
                not finished/failed.  Fenced like every terminal
                commit: a stale-epoch straggler's cancel must not
                lost-update metadata a newer recovery owns."""
                if self._fence_refused(name, req):
                    return None
                reason = token.reason or "cancel requested"
                logger.warning(kv(job=name, state="cancelled",
                                  reason=reason, **req))
                self._journal(name, "cancelled", reason=reason)
                meta.update(name, {
                    "jobState": JobState.CANCELLED,
                    "finished": False,
                    "exception": f"cancelled: {reason}"
                    + (f" ({detail})" if detail else ""),
                })
                jobs_total.inc(
                    job_class=job_class, state="cancelled"
                )
                ledger.record(
                    name,
                    description=description,
                    method=method,
                    parameters=parameters,
                    state=JobState.CANCELLED,
                    exception=detail,
                    trace=trace_doc(),
                )
                self._notify(name, "cancelled")
                return None
            while True:
                if ctl["expired"]:
                    # The watchdog expired this job while it slept in
                    # retry backoff: its failure is already recorded
                    # and its worker/leases handed on.  Starting
                    # another attempt here would mark_running over the
                    # watchdog's failed state and re-contend for the
                    # just-revoked leases.
                    logger.warning(kv(job=name, state="abandoned",
                                      **req))
                    return None
                if token.cancelled():
                    if ctl.get("cancelled"):
                        # REST-cancelled while between attempts
                        # (retry backoff): same terminal contract as
                        # a mid-run cancel — CANCELLED, not failed.
                        return _commit_cancelled()
                    # Cancelled between attempts without a deadline
                    # expiry: the bounded shutdown drain.  Record the
                    # terminal state (no watchdog wrote one) and stop
                    # instead of starting an attempt the process
                    # won't outlive.
                    err = (
                        f"cancelled: "
                        f"{token.reason or 'engine shutdown'}"
                    )
                    logger.warning(kv(job=name, state="cancelled",
                                      **req))
                    self._journal(name, "cancelled",
                                  reason=token.reason or None)
                    try:
                        meta.mark_failed(name, err)
                    except Exception:  # noqa: BLE001
                        pass
                    return None
                # One span PER ATTEMPT (attrs attempt=1..N): retries
                # are separate intervals in the persisted trace, not
                # one opaque job span swallowing every re-execution.
                if trace is not None:
                    job_sid = trace.begin(
                        "job", attrs={"attempt": attempts + 1}
                    )
                with tracing.activate(trace, job_sid):
                    self._journal(name, "running",
                                  attempt=attempts + 1)
                    meta.mark_running(name)
                    logger.info(kv(job=name, state="running",
                                   method=method, attempt=attempts + 1,
                                   **req))
                    # Feed-only event (no webhook fires for "running" —
                    # registrations are finished/failed; the global event
                    # feed still records the transition).
                    self._notify(name, "running")
                    # Rebound by the capture context; the empty default
                    # keeps the except-path buf.getvalue() calls safe if
                    # capture setup itself ever raises.
                    buf = io.StringIO()
                    attempt_token = _ATTEMPT.set(attempts)
                    try:
                        faults.hit("engine.dispatch")
                        _flight().record(
                            "jobs", "dispatch",
                            job=name, method=method,
                            jobClass=job_class, attempt=attempts + 1,
                        )
                        if capture_stdout:
                            # Thread-scoped: redirect_stdout would capture
                            # every concurrent thread's prints, not this
                            # job's (log.capture_thread_stdout docstring).
                            with capture_thread_stdout() as buf:
                                result = fn()
                        else:
                            result = fn()
                    except Preempted:
                        if ctl["expired"]:
                            # The watchdog already failed this job and
                            # reclaimed its worker — no retry, no
                            # state writes.
                            logger.warning(kv(job=name,
                                              state="abandoned", **req))
                            return None
                        attempts += 1
                        exhausted = (
                            attempts > self.max_preemption_retries
                        )
                        logger.warning(
                            kv(job=name, state="preempted",
                               attempt=attempts, **req)
                        )
                        self._journal(name, "preempted",
                                      attempt=attempts)
                        _flight().record(
                            "jobs", "preempt_retry",
                            job=name, attempt=attempts,
                            exhausted=exhausted,
                        )
                        jobs_total.inc(
                            job_class=job_class, state="preempted"
                        )
                        ledger.record(
                            name,
                            description=description,
                            method=method,
                            parameters=parameters,
                            state="preempted",
                            stdout=buf.getvalue() if capture_stdout
                            else None,
                            # The exhausting attempt IS the terminal
                            # record (no failed-state record follows
                            # it): persist the trace here or the
                            # failed run's spans are lost.
                            trace=trace_doc() if exhausted else None,
                        )
                        if not exhausted:
                            # Preemption survivors observable from the
                            # ordinary GET/poll path.
                            try:
                                meta.update(
                                    name, {"preemptions": attempts}
                                )
                            except Exception:  # noqa: BLE001
                                pass
                            if trace is not None:
                                trace.end(job_sid)
                            self._backoff(name, attempts, trace, req)
                            continue
                        if self._fence_refused(name, req):
                            return None
                        self._journal(
                            name, "failed",
                            reason="preemption retries exhausted",
                        )
                        meta.mark_failed(
                            name, "Preempted (retries exhausted)"
                        )
                        jobs_total.inc(
                            job_class=job_class, state="failed"
                        )
                        # Retries exhausted IS the incident: freeze
                        # the flight rings into a debug bundle.
                        _bundle().trigger(
                            "job_retries_exhausted",
                            job=name, attempts=attempts,
                        )
                        self._notify(name, "failed")
                        return None
                    except BaseException as exc:  # never kill workers
                        err = repr(exc)
                        if ctl["expired"]:
                            logger.warning(
                                kv(job=name, state="abandoned",
                                   error=err, **req)
                            )
                            return None
                        if self._fence_refused(name, req):
                            # Stale-epoch straggler: the newer
                            # recovery owns this job's metadata — a
                            # late "failed" would lost-update it.
                            return None
                        if ctl.get("cancelled"):
                            # The body died winding down after a
                            # cooperative cancel: that is a CANCELLED
                            # job, not a failure of the work itself.
                            return _commit_cancelled(err)
                        logger.error(
                            kv(job=name, state="failed", error=err,
                               dt=f"{time.monotonic() - t_start:.2f}s",
                               **req)
                        )
                        self._journal(name, "failed", reason=err)
                        _flight().record(
                            "jobs", "failed",
                            job=name, error=err[:200],
                        )
                        meta.mark_failed(name, err)
                        jobs_total.inc(
                            job_class=job_class, state="failed"
                        )
                        ledger.record(
                            name,
                            description=description,
                            method=method,
                            parameters=parameters,
                            state=JobState.FAILED,
                            exception=err,
                            stdout=buf.getvalue() if capture_stdout
                            else None,
                            trace=trace_doc(),
                        )
                        # Keep the traceback reachable for debugging
                        # without crashing the pool thread.
                        self._last_tracebacks[name] = (
                            traceback.format_exc()
                        )
                        self._notify(name, "failed")
                        return None
                    finally:
                        _ATTEMPT.reset(attempt_token)

                    if ctl["expired"]:
                        # Finished after its deadline: the job is
                        # already failed and its worker/leases handed
                        # on — a late mark_finished would resurrect it.
                        logger.warning(
                            kv(job=name, state="abandoned",
                               dt=f"{time.monotonic() - t_start:.2f}s",
                               **req)
                        )
                        return None
                    if ctl.get("cancelled"):
                        # REST-cancelled mid-run: the body observed
                        # its token and wound down early — its partial
                        # result must not publish as "finished".
                        return _commit_cancelled()
                    if self._fence_refused(name, req):
                        # Stale-epoch straggler racing a newer
                        # recovery: its completion must not publish.
                        return None
                    # ``commit``: from the body's return to the writes
                    # that make the job ``finished`` for a poller.  The
                    # ledger record below persists the trace itself, so
                    # the span ends before it.
                    with tracing.span("commit"):
                        extra = on_success(result) if on_success \
                            else None
                        logger.info(
                            kv(job=name, state="finished",
                               dt=f"{time.monotonic() - t_start:.2f}s",
                               **req)
                        )
                        if self.journal is not None:
                            # Epoch stamp on metadata finalization:
                            # which engine life committed this artifact
                            # — readable from the ordinary GET/poll
                            # path.
                            extra = {
                                **(extra or {}),
                                "engineEpoch":
                                    jobs_journal.current_stamp(),
                            }
                        self._journal(name, "finished")
                        meta.mark_finished(name, extra or None)
                        jobs_total.inc(
                            job_class=job_class, state="finished"
                        )
                    ledger.record(
                        name,
                        description=description,
                        method=method,
                        parameters=parameters,
                        state=JobState.FINISHED,
                        stdout=buf.getvalue() if capture_stdout
                        else None,
                        trace=trace_doc(),
                    )
                    self._notify(name, "finished")
                    return result

        future: Future = Future()
        deadline = (
            self.default_deadline_s if deadline_s is None
            else float(deadline_s)
        )
        info = {
            "name": name,
            "job_class": job_class,
            "deadline": deadline,
            "ctl": ctl,
            "token": token,
            "tenant": tenant,
            # Submit wall-time: the claim table's supersede rule
            # compares it against a released claim's completion time
            # to refuse re-running work a peer already finished.
            "enqueued_at": time.time(),
        }
        # Queued-quota accounting BEFORE the enqueue (the dispatcher
        # may pop the entry the instant the lock drops; decrementing
        # before incrementing would clamp at 0 and leak).
        if self.admission is not None:
            self.admission.note_queued(tenant)
        # Journal ahead of the in-memory enqueue (and outside the
        # engine lock — a late-shutdown append drains inline through
        # the store's collection lock, and nesting that under _lock
        # would add a cross-module edge the dispatcher's hot path
        # doesn't need).
        if self.journal is not None:
            self.journal.record_submit(
                name, job_class=job_class, method=method,
                description=description, parameters=parameters,
                deadline_s=deadline if deadline else None,
                request_id=request_id,
            )
        with self._lock:
            refused = self._shutdown
            if not refused:
                if tenant:
                    self._tenant_seen = True
                queue = self._queues.get(job_class)
                if queue is None:
                    queue = self._queues[job_class] = deque()
                    self._rr_order.append(job_class)
                    self._credits[job_class] = self._weight(job_class)
                queue.append((run, future, warm_key, info))
                self._futures[name] = future
                self._prune_locked()
                self._dispatch_locked()
        if refused:
            if self.admission is not None:
                self.admission.note_dequeued(tenant)
            # Same contract as handing the job to a shut-down
            # executor (the pre-fairness behavior) — but the journal
            # already holds this job's submitted/queued pair, so
            # append the terminal (outside the lock: store writes)
            # or recovery would resurrect a submission the caller
            # was told failed.
            self._journal(
                name, "cancelled",
                reason="engine shut down before enqueue",
            )
            raise RuntimeError(
                "cannot submit jobs after engine shutdown"
            )
        return future

    def _backoff(self, name: str, attempt: int, trace, req: dict) -> None:
        """Sleep the jittered exponential backoff before retry
        ``attempt`` and record it as a ``retry_backoff`` span."""
        base = self.retry_backoff_s
        if base <= 0:
            return
        delay = min(
            self.retry_backoff_max_s,
            base * (2 ** max(0, attempt - 1)),
        ) * (0.5 + random.random())
        logger.info(kv(job=name, state="backoff",
                       delay=f"{delay:.3f}s", attempt=attempt, **req))
        t0 = time.monotonic()
        # Interruptible: a bounded shutdown drain (or the deadline
        # watchdog) flipping the token mid-backoff wakes the sleep —
        # otherwise a fully cooperative job could outsleep the drain's
        # grace window and be abandoned.
        token = jobs_cancel.current_cancel_token()
        if token is not None:
            token.wait(delay)
        else:
            time.sleep(delay)
        if trace is not None:
            trace.add_span(
                "retry_backoff", t0, time.monotonic(),
                attrs={"attempt": attempt, "delayS": round(delay, 4)},
            )

    # -- weighted-fair dispatch ----------------------------------------------

    def _weight(self, job_class: str) -> int:
        return max(1, int(self.class_weights.get(job_class, 1)))

    def note_warm(self, warm_key: str | None) -> None:
        """Record that programs for ``warm_key`` are compiled and
        cached — future queued jobs with this tag dispatch first
        within their class.  Bounded FIFO; never raises."""
        if not warm_key:
            return
        with self._lock:
            self._warm_keys.pop(warm_key, None)
            self._warm_keys[warm_key] = None
            while len(self._warm_keys) > self._max_warm_keys:
                self._warm_keys.popitem(last=False)

    def clear_warm_keys(self) -> None:
        """Drop every warm hint — wired to the compile cache's
        device-set invalidation (services/context.py): once the cache
        cleared, 'warm' jobs would trace like any other, so the
        preference is pure queue distortion."""
        with self._lock:
            self._warm_keys.clear()

    def _pop_queued_locked(self, queue: deque, job_class: str):
        """Pop the next job from one class's queue: the first queued
        job whose ``warm_key`` is known-warm if any (its compiled
        programs are cached — it starts stepping, not tracing), else
        strict FIFO.  Cancelled entries are skipped, never charged.
        At most ``_max_warm_bypass`` consecutive dispatches may jump
        the FIFO head; then the head runs (cold jobs are delayed, not
        starved)."""
        if (
            self._warm_keys
            and self._warm_bypass.get(job_class, 0) < self._max_warm_bypass
        ):
            for i, (runner, future, wk, info) in enumerate(queue):
                if future.cancelled():
                    continue
                if wk is not None and wk in self._warm_keys:
                    if i > 0:
                        self._warm_bypass[job_class] = (
                            self._warm_bypass.get(job_class, 0) + 1
                        )
                    else:
                        self._warm_bypass[job_class] = 0
                    del queue[i]
                    return runner, future, info
        self._warm_bypass[job_class] = 0
        if self._tenant_seen:
            picked = self._tenant_pick_locked(queue, job_class)
            if picked is not None:
                return picked
        runner, future, _wk, info = queue.popleft()
        return runner, future, info

    def _tenant_pick_locked(self, queue: deque, job_class: str):
        """Nested tenant round-robin INSIDE one class's WRR turn:
        when the queue holds work from more than one tenant, serve
        tenants in sorted cyclic order (per-class last-served
        pointer), popping the chosen tenant's oldest entry — so one
        tenant's flood delays, never starves, another tenant's jobs.
        Returns None with a single (or no) tenant present, keeping
        the plain-FIFO path byte-identical."""
        tenants: list[str] = []
        for _r, f, _wk, info in queue:
            if f.cancelled():
                continue
            t = info.get("tenant") or ""
            if t not in tenants:
                tenants.append(t)
        if len(tenants) <= 1:
            return None
        order = sorted(tenants)
        last = self._tenant_rr.get(job_class, "")
        pick = next((t for t in order if t > last), order[0])
        self._tenant_rr[job_class] = pick
        for i, (runner, future, _wk, info) in enumerate(queue):
            if future.cancelled():
                continue
            if (info.get("tenant") or "") == pick:
                del queue[i]
                return runner, future, info
        return None

    def _dispatch_locked(self) -> None:
        """Hand freed workers to queued jobs, class by class (WRR)."""
        while self._inflight < self.max_workers:
            item = self._pick_locked()
            if item is None:
                return
            runner, future, info = item
            if not future.set_running_or_notify_cancel():
                continue  # cancelled while queued — skip, pick again
            self._inflight += 1
            rec = self._register_running_locked(info, future)
            self._spawn_worker_locked(runner, future, rec)

    def _spawn_worker_locked(self, runner, future: Future,
                             rec: dict) -> None:
        thread = threading.Thread(
            target=self._run_dispatched, args=(runner, future, rec),
            name=f"lo-job-{rec['name']}", daemon=True,
        )
        self._threads.add(thread)
        thread.start()

    def _register_running_locked(self, info: dict, future: Future) -> dict:
        """Running-job record the deadline watchdog scans; caller
        holds the lock and has already charged ``_inflight``."""
        rec = {
            "name": info["name"],
            "future": future,
            "deadline": info["deadline"],
            "job_class": info["job_class"],
            "ctl": info["ctl"],
            "token": info["token"],
            "t0": time.monotonic(),
            "released": False,
        }
        self._running_recs[info["name"]] = rec
        if rec["deadline"] and rec["deadline"] > 0:
            self._ensure_watchdog_locked()
        return rec

    def _pick_locked(self):
        """Next queued job under weighted round-robin.

        The pointer stays on a class while it has queued work AND
        remaining credits (its weight's worth of consecutive
        dispatches), then refills that class's credits and advances —
        so over any contention window each class with work receives
        dispatches proportional to its weight.
        """
        # Jobs cancelled while queued are discarded without charging
        # their class's credits — a burst of cancellations must not
        # burn the class's turn.  cancel() runs under the same lock,
        # so cancelled() is stable here.
        for queue in self._queues.values():
            while queue and queue[0][1].cancelled():
                queue.popleft()
        if not any(self._queues.values()):
            return None
        # Two full passes bound the scan: the first may only refill
        # exhausted credits, the second must then land on a nonempty
        # class with fresh credits.
        for _ in range(2 * len(self._rr_order)):
            cls = self._rr_order[self._rr_idx % len(self._rr_order)]
            queue = self._queues[cls]
            while queue and queue[0][1].cancelled():
                queue.popleft()
            if queue and self._credits.get(cls, 0) > 0:
                self._credits[cls] -= 1
                return self._pop_queued_locked(queue, cls)
            self._credits[cls] = self._weight(cls)
            self._rr_idx += 1
        return None

    def _run_dispatched(self, runner, future: Future, rec: dict) -> None:
        try:
            result = runner()
        except BaseException as exc:  # pragma: no cover — run() is
            # exception-safe by construction; never leak a worker.
            try:
                future.set_exception(exc)
            except InvalidStateError:
                pass  # deadline watchdog resolved the future first
        else:
            try:
                future.set_result(result)
            except InvalidStateError:
                pass
        finally:
            with self._lock:
                if self._running_recs.get(rec["name"]) is rec:
                    del self._running_recs[rec["name"]]
                if not rec["released"]:
                    # An expired job's worker was already released by
                    # the watchdog — the zombie's return must not
                    # double-credit the pool.
                    rec["released"] = True
                    self._inflight -= 1
                    self._dispatch_locked()
                self._threads.discard(threading.current_thread())

    # -- deadline watchdog ----------------------------------------------------

    def _ensure_watchdog_locked(self) -> None:
        """Start the watchdog lazily — engines that never see a
        deadline'd job never grow the thread."""
        if self._shutdown:
            return  # nothing to enforce; don't unclear the wake event
        if self._watchdog is None or not self._watchdog.is_alive():
            self._watchdog_wake.clear()
            self._watchdog = threading.Thread(
                target=self._watchdog_loop,
                name="lo-job-watchdog", daemon=True,
            )
            self._watchdog.start()

    def _watchdog_loop(self) -> None:
        while True:
            self._watchdog_wake.wait(self.WATCHDOG_INTERVAL_S)
            expired: list[tuple[str, dict]] = []
            with self._lock:
                if self._shutdown:
                    return
                now = time.monotonic()
                armed = 0
                for name, rec in list(self._running_recs.items()):
                    deadline = rec["deadline"]
                    if (
                        not deadline or deadline <= 0
                        or rec["released"]
                    ):
                        continue
                    if now - rec["t0"] > deadline:
                        # Reclaim the worker NOW: the hung body keeps
                        # its thread (unkillable), but stops counting
                        # against max_workers so queued work
                        # dispatches.  Flipping the cancel token asks
                        # the zombie to exit early (fit loops poll it
                        # per epoch) instead of running to completion
                        # discarded.
                        rec["released"] = True
                        rec["ctl"]["expired"] = True
                        rec["token"].cancel(
                            f"deadline {deadline:g}s exceeded"
                        )
                        del self._running_recs[name]
                        self._inflight -= 1
                        expired.append((name, rec))
                    else:
                        armed += 1
                if expired:
                    self._dispatch_locked()
                if not armed and not expired:
                    # Nothing left to watch: exit rather than poll a
                    # long-lived idle process forever.  Cleared under
                    # the lock so _ensure_watchdog_locked restarts a
                    # fresh thread for the next deadline'd dispatch.
                    self._watchdog = None
                    return
            for name, rec in expired:
                self._expire_job(name, rec)

    def _expire_job(self, name: str, rec: dict) -> None:
        """Terminal bookkeeping for a job the watchdog timed out —
        runs OUTSIDE the engine lock (store writes, webhooks)."""
        deadline = rec["deadline"]
        err = (
            f"job exceeded its {deadline:g}s deadline; the watchdog "
            "failed it and reclaimed its worker and chip leases (the "
            "body finishes as an abandoned zombie)"
        )
        logger.error(kv(job=name, state="deadline",
                        deadlineS=deadline))
        self._journal(name, "deadline", reason=err)
        _flight().record(
            "jobs", "deadline", job=name, deadlineS=deadline,
        )
        # A watchdog-expired job is a crash-grade incident: snapshot
        # the rings before the evidence ages out.
        _bundle().trigger(
            "job_deadline", job=name, deadlineS=deadline,
        )
        _, jobs_total = _job_metrics()
        jobs_total.inc(job_class=rec["job_class"], state="deadline")
        try:
            self.artifacts.metadata.mark_failed(name, err)
        except Exception:  # noqa: BLE001 — the watchdog must survive
            pass
        try:
            self.artifacts.ledger.record(
                name, state="deadline", exception=err,
            )
        except Exception:  # noqa: BLE001
            pass
        if self.leaser is not None:
            try:
                freed = self.leaser.revoke(name)
                if freed:
                    logger.warning(kv(job=name, event="lease_revoked",
                                      devices=freed))
            except Exception:  # noqa: BLE001
                pass
        try:
            rec["future"].set_exception(JobDeadlineExceeded(err))
        except InvalidStateError:
            pass
        self._notify(name, "failed")

    # Cap retained completed futures/tracebacks so a long-lived API process
    # doesn't accumulate every past job's result object.
    _MAX_DONE_RETAINED = 128

    def _prune_locked(self) -> None:
        done = [n for n, f in self._futures.items() if f.done()]
        excess = len(done) - self._MAX_DONE_RETAINED
        for name in done[:max(excess, 0)]:
            self._futures.pop(name, None)
            self._last_tracebacks.pop(name, None)

    # -- status / control -----------------------------------------------------

    def state(self, name: str) -> str:
        meta = self.artifacts.metadata.read(name)
        if meta is None:
            raise KeyError(name)
        return meta.get(
            "jobState",
            JobState.FINISHED if meta.get("finished") else JobState.PENDING,
        )

    def wait(self, name: str, timeout: float | None = None) -> Any:
        """Block until the job for ``name`` completes; returns its result.

        (Clients normally poll GET instead — this is for in-process callers
        and tests.)
        """
        with self._lock:
            future = self._futures.get(name)
        if future is None:
            return None
        return future.result(timeout=timeout)

    def cancel(self, name: str):
        """Cancel a queued or RUNNING job.

        Queued: the future is cancelled before dispatch → ``True``
        (the job never runs).  Running: the body's CancelToken is
        flipped → ``"running"`` — the fit surfaces poll it per
        epoch/batch and wind down like an early stop, after which the
        engine records a journaled ``cancelled`` terminal state
        instead of ``finished``.  ``False`` when the job is neither
        (already terminal, or unknown).
        """
        running_rec = None
        with self._lock:
            # future.cancel() under the engine lock: the dispatcher's
            # cancelled() checks in _pick_locked run under the same
            # lock, so a cancellation can never land between a queue
            # pop and its dispatch — the no-credit-burn guarantee
            # depends on this.
            future = self._futures.get(name)
            cancelled = future is not None and future.cancel()
            if cancelled:
                cancelled_class = "unknown"
                cancelled_tenant = None
                for cls, queue in self._queues.items():
                    for _r, f, _wk, qinfo in queue:
                        if f is future:
                            cancelled_class = cls
                            cancelled_tenant = qinfo.get("tenant")
                            break
            if not cancelled:
                rec = self._running_recs.get(name)
                if rec is not None and not rec["released"]:
                    # Cooperative cancel of the RUNNING body: flag the
                    # control block so the terminal commit records
                    # CANCELLED, then flip the token (the order means
                    # a body that observes the token always finds the
                    # flag set).
                    rec["ctl"]["cancelled"] = True
                    rec["token"].cancel("cancel requested")
                    running_rec = rec
        # Store writes outside the engine lock.
        if cancelled:
            if self.admission is not None:
                # The entry left the queue without dispatching — the
                # tenant's queued count must not leak.
                self.admission.note_dequeued(cancelled_tenant)
            self._journal(name, "cancelled",
                          reason="cancelled while queued")
            self.artifacts.metadata.update(
                name, {"jobState": JobState.CANCELLED, "finished": False}
            )
            # Same observability as the running-cancel commit: ledger
            # row, cancelled counter, webhook/event-feed notify — a
            # watcher of the queued job must see the terminal
            # transition, not wait forever.
            _, jobs_total = _job_metrics()
            jobs_total.inc(
                job_class=cancelled_class, state="cancelled"
            )
            try:
                self.artifacts.ledger.record(
                    name, state=JobState.CANCELLED,
                    exception="cancelled while queued",
                )
            except Exception:  # noqa: BLE001 — cancel must succeed
                pass
            self._notify(name, "cancelled")
            return True
        if running_rec is not None:
            self._journal(name, "cancel_requested")
            return "running"
        return False

    def running_jobs(self) -> list[str]:
        with self._lock:
            return [n for n, f in self._futures.items() if not f.done()]

    def queue_depths(self, include_empty: bool = False) -> dict[str, int]:
        """Queued-but-undispatched jobs per class (the fairness pools) —
        the ops status page's contention gauge.  ``include_empty``
        keeps drained classes at 0 (the Prometheus collector needs the
        series to REPORT zero, not vanish and go stale)."""
        with self._lock:
            return {
                cls: len(q)
                for cls, q in self._queues.items()
                if q or include_empty
            }

    def queue_depths_by_tenant(self) -> dict[tuple, int]:
        """Queued-but-undispatched jobs per ``(class, tenant)`` — the
        per-tenant labels the metrics endpoint adds to
        ``lo_jobs_queue_depth`` once any tenanted submission arrived
        (empty dict otherwise, so untenanted deployments emit no
        extra series)."""
        with self._lock:
            if not self._tenant_seen:
                return {}
            out: dict[tuple, int] = {}
            for cls, q in self._queues.items():
                for _r, f, _wk, info in q:
                    if f.cancelled():
                        continue
                    key = (cls, info.get("tenant") or "")
                    out[key] = out.get(key, 0) + 1
            return out

    #: Post-cancel join grace inside a bounded shutdown drain: once
    #: the drain budget lapses and every outstanding token is flipped,
    #: cooperating bodies get this long to wind down before being
    #: abandoned (they poll the token per epoch/batch, so the grace
    #: only needs to cover one unit of work).
    SHUTDOWN_GRACE_S = 2.0

    def shutdown(self, wait: bool = True,
                 drain_timeout_s: float | None = None,
                 grace_s: float | None = None) -> None:
        """Stop accepting work; with ``wait``, drain what was accepted.

        The drain is BOUNDED when ``drain_timeout_s`` (default: the
        engine's ``shutdown_drain_s``) is positive: past the budget,
        every outstanding job's cancel token is flipped — cooperating
        bodies (the fit surfaces poll per epoch) exit early as if
        early-stopped — still-queued futures are cancelled, and after
        ``grace_s`` any thread still running is abandoned (logged)
        rather than joined forever.  A deadline-expired zombie can
        therefore no longer hang a graceful shutdown.  ``<= 0`` keeps
        the legacy unbounded drain.
        """
        with self._lock:
            self._shutdown = True
            self._watchdog_wake.set()
            # Still-queued jobs keep dispatching as workers free (each
            # completion re-enters _dispatch_locked), capped at
            # max_workers throughout — shutdown(wait=True) must run
            # every accepted job, exactly the pre-fairness contract.
            # Without the kick, jobs queued behind idle workers would
            # be orphaned with their metadata stuck at "pending".
            # (Deadlines stop being enforced here — the watchdog is
            # exiting; the drain budget below bounds the wait instead.)
            self._dispatch_locked()
        if not wait:
            return
        budget = (
            self.shutdown_drain_s if drain_timeout_s is None
            else float(drain_timeout_s)
        )
        deadline = (
            time.monotonic() + budget if budget > 0 else None
        )
        while True:
            with self._lock:
                thread = next(iter(self._threads), None)
                drained = (
                    thread is None
                    and not any(self._queues.values())
                    and self._inflight == 0
                )
            if drained:
                return
            if deadline is not None and time.monotonic() >= deadline:
                break  # budget spent — cooperative-cancel phase
            if thread is None:
                # Transient gap between a worker freeing and the next
                # queued job's thread appearing.
                time.sleep(0.005)
                continue
            if deadline is None:
                thread.join()
            else:
                thread.join(
                    min(0.2, max(0.0, deadline - time.monotonic()))
                )
        # Drain budget exhausted: cancel everything outstanding —
        # running bodies via their tokens (zombies were already
        # cancelled by the watchdog at expiry), queued-never-
        # dispatched jobs via their futures so waiters unblock — then
        # give cooperating threads one grace window and abandon the
        # rest (they are daemon threads; their writes race nothing:
        # the store outlives them only within this process).
        with self._lock:
            stragglers = list(self._threads)
            for rec in self._running_recs.values():
                rec["token"].cancel("engine shutdown drain deadline")
            dropped: list[tuple] = []
            for queue in self._queues.values():
                for _runner, queued_future, _wk, qinfo in queue:
                    if queued_future.cancel():
                        dropped.append(
                            (qinfo["name"], qinfo.get("tenant"))
                        )
                queue.clear()
        # Same terminal metadata the explicit cancel() path writes —
        # without it the pre-created doc would sit at "pending"
        # forever (phantom jobs after restart).  Outside the lock:
        # store writes.
        for name, drop_tenant in dropped:
            if self.admission is not None:
                self.admission.note_dequeued(drop_tenant)
            self._journal(name, "cancelled",
                          reason="shutdown drain deadline")
            try:
                self.artifacts.metadata.update(
                    name,
                    {"jobState": JobState.CANCELLED,
                     "finished": False},
                )
            except Exception:  # noqa: BLE001 — shutdown must finish
                pass
        grace = (
            self.SHUTDOWN_GRACE_S if grace_s is None
            else float(grace_s)
        )
        grace_deadline = time.monotonic() + max(0.0, grace)
        for thread in stragglers:
            thread.join(
                max(0.0, grace_deadline - time.monotonic())
            )
        leftover = [t.name for t in stragglers if t.is_alive()]
        if dropped or leftover:
            logger.error(kv(
                event="shutdown_drain_bounded",
                budgetS=budget, droppedQueued=len(dropped),
                abandoned=len(leftover),
                threads=",".join(leftover[:8]),
            ))
