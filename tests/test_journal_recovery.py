"""Crash-durable job journal, restart recovery, and epoch fencing
(jobs/journal.py + the engine/context integration).

Covers the PR's acceptance drills: journal replay goldens for every
transition type, queued-job re-enqueue order preservation, stale-epoch
publication refusal, the REST cancel surface, recovery under an armed
``store.ha.failover`` fault, and the subprocess kill-9 drill — the
orchestrator SIGKILLed mid-train-fit, restarted, and the job resumes
from its newest managed checkpoint (verified via epoch-span count)
and reaches ``finished``.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from learningorchestra_tpu import faults
from learningorchestra_tpu.config import Config
from learningorchestra_tpu.jobs import (
    JobEngine,
    JobJournal,
    StaleEpochError,
)
from learningorchestra_tpu.jobs import journal as journal_mod
from learningorchestra_tpu.jobs.journal import (
    JOURNAL_COLLECTION,
    read_engine_epoch,
    write_engine_epoch,
)
from learningorchestra_tpu.store import ArtifactStore, DocumentStore

PREFIX = "/api/learningOrchestra/v1"


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _engine_with_journal(tmp_path, **engine_kw):
    store = DocumentStore(tmp_path / "store")
    arts = ArtifactStore(store)
    journal = JobJournal(store, tmp_path / "store")
    eng = JobEngine(arts, **engine_kw)
    eng.journal = journal
    return store, arts, journal, eng


def _events(store, job=None):
    out = [
        (d["job"], d["event"])
        for d in store.find(JOURNAL_COLLECTION)
        if d.get("docType") == "journal"
    ]
    if job is not None:
        out = [e for j, e in out if j == job]
    return out


class TestJournalGoldens:
    """Each transition type appends its journal record — the replay
    goldens the recovery contract rests on."""

    def test_every_transition_type_is_journaled(self, tmp_path):
        from learningorchestra_tpu.jobs import Preempted
        from learningorchestra_tpu.jobs import cancel as jc

        store, arts, journal, eng = _engine_with_journal(
            tmp_path, max_workers=2, retry_backoff_s=0.01,
        )
        try:
            # finished
            arts.metadata.create("ok", "function/python")
            eng.submit("ok", lambda: 1, job_class="f").result(timeout=10)
            # failed (the engine resolves the future None; the error
            # lives in metadata/ledger — the reference's contract)
            arts.metadata.create("bad", "function/python")
            fut = eng.submit("bad", lambda: 1 / 0, job_class="f")
            assert fut.result(timeout=10) is None
            # preempted once, then finished
            state = {"n": 0}

            def pre():
                state["n"] += 1
                if state["n"] == 1:
                    raise Preempted("chip gone")
                return "done"

            arts.metadata.create("pre", "function/python")
            eng.submit("pre", pre, job_class="f").result(timeout=10)

            # running job cancelled cooperatively (the REST path)
            gate = threading.Event()

            def body():
                gate.set()
                while not jc.cancel_requested():
                    time.sleep(0.005)
                return "partial"

            arts.metadata.create("run", "function/python")
            frun = eng.submit("run", body, job_class="f")
            assert gate.wait(10)
            assert eng.cancel("run") == "running"
            assert frun.result(timeout=10) is None
            # deadline (the cooperative body exits the moment expiry
            # flips its token, racing the watchdog's set_exception —
            # either future outcome is fine; the journal/metadata
            # terminal state below is the contract under test)
            arts.metadata.create("late", "function/python")
            flate = eng.submit(
                "late",
                lambda: jc.current_cancel_token().wait(30),
                job_class="f", deadline_s=0.2,
            )
            try:
                assert flate.result(timeout=30) is None
            except Exception:
                pass  # JobDeadlineExceeded when the watchdog won
            deadline = time.time() + 10
            while time.time() < deadline:
                if arts.metadata.read("late")["jobState"] == "failed":
                    break
                time.sleep(0.05)
            assert arts.metadata.read("late")["jobState"] == "failed"
            eng.shutdown(wait=True)
            journal.flush()

            assert _events(store, "ok") == [
                "submitted", "queued", "running", "finished",
            ]
            assert _events(store, "bad") == [
                "submitted", "queued", "running", "failed",
            ]
            assert _events(store, "pre") == [
                "submitted", "queued", "running", "preempted",
                "running", "finished",
            ]
            assert _events(store, "run") == [
                "submitted", "queued", "running",
                "cancel_requested", "cancelled",
            ]
            assert "deadline" in _events(store, "late")
            # cancelled metadata, not a phantom finish
            assert arts.metadata.read("run")["jobState"] == "cancelled"
            ledger_states = [
                r["state"] for r in arts.ledger.history("run")
            ]
            assert "cancelled" in ledger_states
        finally:
            eng.shutdown(wait=False)
            journal.close()
            store.close()

    def test_cancel_during_retry_backoff_records_cancelled(
        self, tmp_path
    ):
        """A REST cancel landing while the body sleeps in preemption
        backoff must land jobState CANCELLED (the cancel contract),
        not the shutdown-drain path's 'failed'."""
        from learningorchestra_tpu.jobs import Preempted

        store, arts, journal, eng = _engine_with_journal(
            tmp_path, max_workers=1,
            retry_backoff_s=5.0, retry_backoff_max_s=5.0,
        )
        try:
            in_backoff = threading.Event()

            def body():
                if not in_backoff.is_set():
                    in_backoff.set()
                    raise Preempted("chip gone")
                return "done"

            arts.metadata.create("bk", "function/python")
            fut = eng.submit("bk", body, job_class="f")
            assert in_backoff.wait(10)
            time.sleep(0.1)  # into the (interruptible) backoff sleep
            assert eng.cancel("bk") == "running"
            assert fut.result(timeout=10) is None
            deadline = time.time() + 10
            while time.time() < deadline:
                if arts.metadata.read("bk")["jobState"] == "cancelled":
                    break
                time.sleep(0.05)
            assert arts.metadata.read("bk")["jobState"] == "cancelled"
            journal.flush()
            assert _events(store, "bk")[-1] == "cancelled"
        finally:
            eng.shutdown(wait=False)
            journal.close()
            store.close()

    def test_queued_cancel_is_journaled(self, tmp_path):
        store, arts, journal, eng = _engine_with_journal(
            tmp_path, max_workers=1,
        )
        try:
            gate = threading.Event()
            arts.metadata.create("blk", "function/python")
            eng.submit("blk", gate.wait, job_class="f")
            time.sleep(0.05)
            arts.metadata.create("victim", "function/python")
            eng.submit("victim", lambda: 1, job_class="f")
            assert eng.cancel("victim") is True
            gate.set()
            eng.shutdown(wait=True)
            journal.flush()
            assert _events(store, "victim") == [
                "submitted", "queued", "cancelled",
            ]
        finally:
            journal.close()
            store.close()

    def test_job_life_is_four_records_group_committed(self, tmp_path):
        """A job that runs once costs the journal four records (the
        submit pair, ``running``, the terminal), whatever the number
        of jobs in flight, and ``flush()`` leaves every one in the
        store."""
        store, arts, journal, eng = _engine_with_journal(
            tmp_path, max_workers=4,
        )
        try:
            n = 24
            futures = []
            for i in range(n):
                arts.metadata.create(f"j{i}", "function/python")
                futures.append(
                    eng.submit(f"j{i}", lambda i=i: i, job_class="f")
                )
            assert [f.result(timeout=30) for f in futures] == list(
                range(n)
            )
            eng.shutdown(wait=True)
            journal.flush()
            events = _events(store)
            assert len(events) == 4 * n
            for i in range(n):
                assert _events(store, f"j{i}") == [
                    "submitted", "queued", "running", "finished",
                ]
        finally:
            eng.shutdown(wait=False)
            journal.close()
            store.close()

    def test_replay_folds_states_and_order(self, tmp_path):
        store, arts, journal, eng = _engine_with_journal(
            tmp_path, max_workers=2,
        )
        try:
            for name in ("a1", "a2"):
                arts.metadata.create(name, "function/python")
                eng.submit(name, lambda: 1, job_class="f").result(
                    timeout=10
                )
            eng.shutdown(wait=True)
            # A job whose life stopped mid-run (as a crash leaves it).
            journal.record_submit("mid", job_class="f", method="fit")
            journal.append("running", "mid", attempt=1)
            rep = journal.replay()
            assert rep["a1"]["terminal"] and rep["a2"]["terminal"]
            assert rep["a1"]["state"] == "finished"
            assert rep["mid"]["state"] == "running"
            assert not rep["mid"]["terminal"]
            assert rep["mid"]["spec"]["method"] == "fit"
            # Queue admission order rides the queued seq numbers.
            assert rep["a1"]["seq"] < rep["a2"]["seq"] < rep["mid"]["seq"]
        finally:
            journal.close()
            store.close()

    def test_prune_keeps_live_jobs_and_bounds_terminal(self, tmp_path):
        store = DocumentStore(tmp_path / "store")
        journal = JobJournal(
            store, tmp_path / "store", max_records=5,
        )
        try:
            for i in range(6):
                journal.record_submit(f"t{i}", job_class="f")
                journal.append("running", f"t{i}", attempt=1)
                journal.append("finished", f"t{i}")
            journal.record_submit("live", job_class="f")
            journal.append("running", "live", attempt=1)
            journal.flush()
            dropped = journal.prune()
            assert dropped > 0
            rep = journal.replay()
            # Terminal jobs still replay terminal; the live one keeps
            # its full history (state + order survive pruning).
            assert all(
                rep[f"t{i}"]["terminal"] for i in range(6)
            )
            assert rep["live"]["state"] == "running"
            assert store.count(JOURNAL_COLLECTION) < 6 * 4
        finally:
            journal.close()
            store.close()


class TestEpochFencing:
    def test_epoch_mints_monotonically(self, tmp_path):
        store = DocumentStore(tmp_path / "store")
        try:
            j1 = JobJournal(store, tmp_path / "store")
            assert j1.epoch == 1
            j2 = JobJournal(store, tmp_path / "store")
            assert j2.epoch == 2
            assert read_engine_epoch(tmp_path / "store") == 2
            j1.close()
            j2.close()
        finally:
            store.close()

    def test_fence_check_refuses_stale_stamp(self, tmp_path):
        store = DocumentStore(tmp_path / "store")
        journal = JobJournal(store, tmp_path / "store")
        try:
            journal.fence_check()  # unstamped: passes
            with journal_mod.stamp(journal.epoch):
                journal.fence_check()  # current: passes
                write_engine_epoch(
                    tmp_path / "store", journal.epoch + 1
                )
                with pytest.raises(StaleEpochError):
                    journal.fence_check()
        finally:
            journal.close()
            store.close()

    def test_stale_worker_terminal_commit_refused(self, tmp_path):
        """A body from a stale engine epoch finishes — its commit is
        REFUSED: metadata stays untouched for the newer epoch's
        recovery, no ledger record, no journal terminal event."""
        store, arts, journal, eng = _engine_with_journal(
            tmp_path, max_workers=1,
        )
        try:
            release = threading.Event()
            started = threading.Event()

            def body():
                started.set()
                release.wait(30)
                return "stale result"

            arts.metadata.create("stale", "function/python")
            fut = eng.submit("stale", body, job_class="f")
            assert started.wait(10)
            # A newer recovery boots over the same store root.
            write_engine_epoch(tmp_path / "store", journal.epoch + 1)
            release.set()
            assert fut.result(timeout=10) is None
            time.sleep(0.1)
            meta = arts.metadata.read("stale")
            assert meta["jobState"] == "running"  # untouched
            assert not arts.ledger.history("stale")
            journal.flush()
            events = _events(store, "stale")
            assert "finished" not in events
        finally:
            eng.shutdown(wait=False)
            journal.close()
            store.close()

    def test_stale_worker_artifact_publication_refused(self, tmp_path):
        """The publication-time fence (ctx.require_current_epoch):
        a stale-epoch body raises before volumes.save_object runs."""
        from learningorchestra_tpu.services.context import (
            ServiceContext,
        )

        cfg = Config()
        cfg.store.root = str(tmp_path / "store")
        cfg.store.volume_root = str(tmp_path / "vol")
        cfg.store.backend = "python"
        ctx = ServiceContext(cfg)
        try:
            release = threading.Event()
            published = []

            def body():
                release.wait(30)
                ctx.require_current_epoch()  # raises: stale
                published.append(True)

            ctx.artifacts.metadata.create("pub", "function/python")
            fut = ctx.engine.submit("pub", body, job_class="f")
            write_engine_epoch(
                ctx.config.store.store_path(),
                ctx.journal.epoch + 1,
            )
            release.set()
            assert fut.result(timeout=10) is None
            assert not published
        finally:
            ctx.close()


class TestRecovery:
    def _cfg(self, tmp_path):
        cfg = Config()
        cfg.store.root = str(tmp_path / "store")
        cfg.store.volume_root = str(tmp_path / "vol")
        cfg.store.backend = "python"
        return cfg

    def test_reenqueue_preserves_queue_order(self, tmp_path,
                                             monkeypatch):
        """Jobs journaled as queued re-dispatch in their pre-crash
        queue admission order, not name order."""
        from learningorchestra_tpu.services.context import (
            ServiceContext,
        )
        from learningorchestra_tpu.services.executor import (
            ExecutorService,
        )
        from learningorchestra_tpu.store import Metadata

        cfg = self._cfg(tmp_path)
        store = DocumentStore(cfg.store.store_path())
        meta = Metadata(store)
        journal = JobJournal(store, cfg.store.store_path())
        for name in ("j_b", "j_a", "j_c"):  # admission order
            meta.create(
                name, "predict/tensorflow", parent_name="fit0",
                method="predict",
            )
            journal.record_submit(
                name, job_class="executor", method="predict",
            )
        journal.close()
        store.close()

        order = []

        def fake_update(self, name, **kw):
            order.append(name)
            return {}

        monkeypatch.setattr(ExecutorService, "update", fake_update)
        ctx = ServiceContext(cfg)
        try:
            assert order == ["j_b", "j_a", "j_c"]
        finally:
            ctx.close()

    def test_unresumable_job_orphan_fails_with_reason(self, tmp_path):
        """A journaled job whose body cannot be re-derived (function)
        is terminally failed `orphaned-by-restart` — never phantom
        running metadata — and the journal records the terminal."""
        from learningorchestra_tpu.services.context import (
            ServiceContext,
        )
        from learningorchestra_tpu.store import Metadata

        cfg = self._cfg(tmp_path)
        store = DocumentStore(cfg.store.store_path())
        meta = Metadata(store)
        meta.create("fn1", "function/python")
        meta.mark_running("fn1")
        journal = JobJournal(store, cfg.store.store_path())
        journal.record_submit("fn1", job_class="function")
        journal.append("running", "fn1", attempt=1)
        journal.close()
        store.close()

        ctx = ServiceContext(cfg)
        try:
            doc = ctx.artifacts.metadata.read("fn1")
            assert doc["jobState"] == "failed"
            assert "orphaned-by-restart" in doc["exception"]
            rep = ctx.journal.replay()
            assert rep["fn1"]["terminal"]
            assert rep["fn1"]["reason"] == "orphaned-by-restart"
        finally:
            ctx.close()

    def test_journal_less_job_keeps_legacy_reflag(self, tmp_path):
        """Stores predating the journal (or journal off): interrupted
        jobs still get the legacy interrupted-re-flag message."""
        from learningorchestra_tpu.services.context import (
            ServiceContext,
        )
        from learningorchestra_tpu.store import Metadata

        cfg = self._cfg(tmp_path)
        store = DocumentStore(cfg.store.store_path())
        Metadata(store).create("old", "function/python")
        Metadata(store).mark_running("old")
        store.close()
        ctx = ServiceContext(cfg)
        try:
            doc = ctx.artifacts.metadata.read("old")
            assert doc["jobState"] == "failed"
            assert "interrupted" in doc["exception"]
        finally:
            ctx.close()

    def test_recover_off_orphans_instead_of_redispatch(
        self, tmp_path, monkeypatch
    ):
        from learningorchestra_tpu.services.context import (
            ServiceContext,
        )
        from learningorchestra_tpu.services.executor import (
            ExecutorService,
        )
        from learningorchestra_tpu.store import Metadata

        cfg = self._cfg(tmp_path)
        cfg.jobs.journal_recover = False
        store = DocumentStore(cfg.store.store_path())
        meta = Metadata(store)
        meta.create(
            "fitx", "train/tensorflow", parent_name="m",
            method="fit",
        )
        meta.mark_running("fitx")
        journal = JobJournal(store, cfg.store.store_path())
        journal.record_submit("fitx", job_class="executor",
                              method="fit")
        journal.append("running", "fitx", attempt=1)
        journal.close()
        store.close()

        called = []
        monkeypatch.setattr(
            ExecutorService, "update",
            lambda self, name, **kw: called.append(name),
        )
        ctx = ServiceContext(cfg)
        try:
            assert not called
            doc = ctx.artifacts.metadata.read("fitx")
            assert doc["jobState"] == "failed"
            assert "orphaned-by-restart" in doc["exception"]
        finally:
            ctx.close()

    def test_recovery_under_armed_failover_fault(self, tmp_path):
        """The HA drill composition: the primary dies mid-job, the
        standby's promotion crashes once under an armed seeded
        ``store.ha.failover`` fault and succeeds on retry (the
        supervisor-restart analogue), and the recovered boot over the
        promoted directory resolves the inherited journal — no
        phantom running metadata survives the whole chain."""
        from learningorchestra_tpu.faults import FaultInjected
        from learningorchestra_tpu.services.context import (
            ServiceContext,
        )
        from learningorchestra_tpu.store import Metadata
        from learningorchestra_tpu.store.ha import StandbyMonitor

        primary = tmp_path / "primary"
        store = DocumentStore(primary)
        meta = Metadata(store)
        meta.create("wedged", "function/python")
        meta.mark_running("wedged")
        journal = JobJournal(store, primary)
        journal.record_submit("wedged", job_class="function")
        journal.append("running", "wedged", attempt=1)
        journal.close()
        store.close()

        monitor = StandbyMonitor(
            "127.0.0.1:1", primary, tmp_path / "replica",
            probe_timeout=0.2,
        )
        monitor.step()  # ships the WALs, journal included
        faults.arm("store.ha.failover", "error", max_triggers=1)
        with pytest.raises(FaultInjected):
            monitor.promote()
        promoted = monitor.promote()  # supervisor-restart retry
        assert faults.triggers("store.ha.failover") == 1
        faults.reset()

        cfg = Config()
        cfg.store.root = str(promoted)
        cfg.store.volume_root = str(tmp_path / "vol")
        cfg.store.backend = "python"
        ctx = ServiceContext(cfg)
        try:
            doc = ctx.artifacts.metadata.read("wedged")
            assert doc["jobState"] == "failed"
            assert "orphaned-by-restart" in doc["exception"]
        finally:
            ctx.close()


class TestRestCancel:
    def test_delete_jobs_route_cancels_running_job(self, tmp_path):
        from learningorchestra_tpu.api.server import APIServer
        from learningorchestra_tpu.jobs import cancel as jc

        cfg = Config()
        cfg.store.root = str(tmp_path / "store")
        cfg.store.volume_root = str(tmp_path / "vol")
        server = APIServer(cfg)
        try:
            ctx = server.ctx
            gate = threading.Event()

            def body():
                gate.set()
                while not jc.cancel_requested():
                    time.sleep(0.005)
                return "partial"

            ctx.artifacts.metadata.create("runjob", "function/python")
            fut = ctx.engine.submit("runjob", body, job_class="f")
            assert gate.wait(10)
            status, payload = server.handle(
                "DELETE", f"{PREFIX}/jobs/runjob", {}, {}
            )
            assert status == 202, payload
            assert payload["result"] == "cancelling"
            fut.result(timeout=10)
            deadline = time.time() + 10
            while time.time() < deadline:
                doc = ctx.artifacts.metadata.read("runjob")
                if doc["jobState"] == "cancelled":
                    break
                time.sleep(0.05)
            assert doc["jobState"] == "cancelled"
            # Terminal now → 409; unknown → 404.
            status, _ = server.handle(
                "DELETE", f"{PREFIX}/jobs/runjob", {}, {}
            )
            assert status == 409
            status, _ = server.handle(
                "DELETE", f"{PREFIX}/jobs/nope", {}, {}
            )
            assert status == 404
            ctx.journal.flush()
            events = [
                e for j, e in (
                    (d["job"], d["event"])
                    for d in ctx.documents.find(JOURNAL_COLLECTION)
                    if d.get("docType") == "journal"
                ) if j == "runjob"
            ]
            assert "cancel_requested" in events
            assert events[-1] == "cancelled"
        finally:
            server.shutdown()


# -- the kill-9 drill ---------------------------------------------------------

_CHILD_ORCHESTRATOR = r"""
import json, os, signal, sys, time
import numpy as np
from learningorchestra_tpu import faults
from learningorchestra_tpu.config import Config
from learningorchestra_tpu.services.context import ServiceContext
from learningorchestra_tpu.services.executor import ExecutorService
from learningorchestra_tpu.services.model import ModelService

cfg = Config.from_env()
cfg.store.backend = "python"
ctx = ServiceContext(cfg)
model = ModelService(ctx)
ex = ExecutorService(ctx)
rng = np.random.default_rng(0)
x = rng.standard_normal((32, 4)).astype("float32")
y = (x.sum(1) > 0).astype("int32")
model.create(
    "m", module_path="learningorchestra_tpu.models.mlp",
    class_name="MLPClassifier",
    class_parameters={"hidden_layer_sizes": [4], "num_classes": 2},
)
ctx.engine.wait("m", timeout=180)
# Deterministic mid-fit window: epochs 0-1 run free (and checkpoint),
# every later epoch's top delays 300 ms — the SIGKILL below lands
# while the fit is provably still running.
faults.arm("train.epoch", "delay", delay_ms=300, after=2)
ex.create(
    "fit1", parent_name="m", method="fit",
    method_parameters={
        "x": x.tolist(), "y": y.tolist(), "epochs": 6,
        "checkpoint_every": 1, "checkpoint_min_interval_s": 0,
        "checkpoint_async": False,
    },
    artifact_type="train/tensorflow",
)
marker = ctx.checkpoint_dir("fit1") / "latest.json"
deadline = time.time() + 240
while time.time() < deadline:
    try:
        if json.loads(marker.read_text()).get("step", 0) >= 2:
            break
    except (OSError, ValueError):
        pass
    time.sleep(0.02)
else:
    print("NO_CHECKPOINT", flush=True)
    sys.exit(3)
print("KILLING", flush=True)
os.kill(os.getpid(), signal.SIGKILL)
"""

_CHILD_RECOVERY = r"""
import json, sys, time
from learningorchestra_tpu.config import Config
from learningorchestra_tpu.services.context import ServiceContext

cfg = Config.from_env()
cfg.store.backend = "python"
ctx = ServiceContext(cfg)  # boot-time recovery re-dispatches fit1
deadline = time.time() + 240
meta = {}
while time.time() < deadline:
    meta = ctx.artifacts.metadata.read("fit1") or {}
    if meta.get("finished") or meta.get("jobState") == "failed":
        break
    time.sleep(0.1)
hist = ctx.artifacts.ledger.history("fit1")
trace = next(
    (r.get("trace") for r in reversed(hist) if r.get("trace")), None
)
epochs = sorted(
    s["attrs"]["epoch"]
    for s in (trace or {}).get("spans", [])
    if s.get("name") == "epoch"
)
print("RESULT " + json.dumps({
    "jobState": meta.get("jobState"),
    "engineEpoch": meta.get("engineEpoch"),
    "epochs": epochs,
}), flush=True)
ctx.close()
"""


def _run_child(source: str, env: dict, timeout: int):
    return subprocess.run(
        [sys.executable, "-c", source],
        env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_kill9_drill_resumes_from_newest_checkpoint(tmp_path):
    """The acceptance drill: orchestrator SIGKILLed mid-train-fit →
    restarted process replays the journal → the job resumes from its
    newest managed checkpoint (epoch-span count strictly below a
    from-scratch run, first resumed epoch >= the killed run's last
    checkpoint) and reaches ``finished`` stamped with the recovery
    boot's engine epoch."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "LO_TPU_STORE_ROOT": str(tmp_path / "store"),
        "LO_TPU_VOLUME_ROOT": str(tmp_path / "vol"),
    })
    env.pop("LO_TPU_WITNESS", None)

    first = _run_child(_CHILD_ORCHESTRATOR, env, timeout=420)
    assert first.returncode == -signal.SIGKILL, (
        first.returncode, first.stdout[-2000:], first.stderr[-2000:]
    )
    assert "KILLING" in first.stdout
    # The killed process left a journal with fit1 mid-run and a
    # checkpoint tree at step >= 2.
    marker = json.loads(
        (tmp_path / "vol" / "_checkpoints" / "fit1" /
         "latest.json").read_text()
    )
    assert marker["step"] >= 2

    second = _run_child(_CHILD_RECOVERY, env, timeout=420)
    assert second.returncode == 0, (
        second.stdout[-2000:], second.stderr[-2000:]
    )
    result = json.loads(
        second.stdout.split("RESULT ", 1)[1].splitlines()[0]
    )
    assert result["jobState"] == "finished", result
    assert result["engineEpoch"] == 2, result
    epochs = result["epochs"]
    # Resumed, not restarted: the recovery run trained only the tail.
    assert epochs, "recovered run recorded no epoch spans"
    assert min(epochs) >= 2, epochs
    assert max(epochs) == 5, epochs
    assert len(epochs) < 6, epochs
