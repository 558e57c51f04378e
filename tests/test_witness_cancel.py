"""Runtime lock witness ("losan", concurrency_rt.py) + cooperative
cancellation (jobs/cancel.py): the dynamic halves of the whole-program
concurrency PR.

Covers: witnessed acquisition-order edges / holders / waiters /
held-while-blocking events, the witness-vs-static cross-check on a
REAL short engine job (the tier-1 zero-unmatched-edges gate), the
cancel token's epoch-loop integration, and the bounded
``shutdown(wait=True)`` drain regression — a deadline-failed zombie
body no longer hangs graceful shutdown.
"""

import functools
import tempfile
import threading
import time

import numpy as np
import pytest

from learningorchestra_tpu import concurrency_rt as rt
from learningorchestra_tpu import faults
from learningorchestra_tpu.analysis.witness import cross_check
from learningorchestra_tpu.analysis.wholeprogram import global_graph
from learningorchestra_tpu.jobs.cancel import (
    CancelToken,
    bind,
    cancel_requested,
    current_cancel_token,
)
from learningorchestra_tpu.jobs.engine import (
    JobDeadlineExceeded,
    JobEngine,
)
from learningorchestra_tpu.store import (
    ArtifactStore,
    open_document_store,
)

PKG = __file__.rsplit("/tests/", 1)[0] + "/learningorchestra_tpu"


@functools.lru_cache(maxsize=1)
def _static_graph():
    """The composed whole-program lock graph (one parse per run —
    every witness cross-check in this module shares it)."""
    return global_graph(PKG)


@pytest.fixture
def witness():
    """Enable the witness for locks constructed inside the test, with
    clean edge/event state before and after.

    The metrics-registry singleton is rebuilt on both sides: witness
    enablement is construction-time, so a registry created by an
    EARLIER test would carry a plain (invisible) lock into this test's
    cross-module chains — and a witnessed one left behind would keep
    recording after the test."""
    from learningorchestra_tpu.obs import metrics as obs_metrics

    rt.set_witness(True)
    rt.reset()
    obs_metrics.reset_registry()
    yield rt
    rt.set_witness(False)
    rt.reset()
    obs_metrics.reset_registry()


@pytest.fixture
def artifacts(tmp_path):
    store = open_document_store(tmp_path / "store", backend="python")
    return ArtifactStore(store)


# -- witness primitives ------------------------------------------------------


class TestWitnessRuntime:
    def test_disabled_factories_return_plain_primitives(self):
        rt.set_witness(False)
        lock = rt.make_lock("X.y")
        assert type(lock) is type(threading.Lock())
        rlock = rt.make_rlock("X.z")
        assert type(rlock) is type(threading.RLock())

    def test_acquisition_order_edges_recorded(self, witness):
        a = rt.make_lock("Wa.x")
        b = rt.make_lock("Wb.y")
        with a:
            with b:
                pass
        edges = {
            (e["from"], e["to"]) for e in rt.snapshot()["edges"]
        }
        assert ("Wa.x", "Wb.y") in edges
        assert ("Wb.y", "Wa.x") not in edges

    def test_rlock_reacquire_records_no_self_edge(self, witness):
        r = rt.make_rlock("Wr.r")
        with r:
            with r:
                pass
        assert rt.snapshot()["edges"] == []

    def test_holders_waiters_and_contention_events(self, witness):
        a = rt.make_lock("Wc.a")
        c = rt.make_lock("Wc.c")
        entered = threading.Event()

        def contender():
            with c:          # holds c...
                entered.set()
                with a:      # ...while blocking on a: an event
                    pass

        with a:
            thread = threading.Thread(target=contender)
            thread.start()
            entered.wait(5)
            deadline = time.monotonic() + 5
            snap = rt.snapshot(include_stacks=True)
            while time.monotonic() < deadline:
                locks = {e["name"]: e for e in snap["locks"]}
                if locks.get("Wc.a", {}).get("waiters"):
                    break
                time.sleep(0.01)
                snap = rt.snapshot(include_stacks=True)
            locks = {e["name"]: e for e in snap["locks"]}
            assert locks["Wc.a"]["owner"] == (
                threading.current_thread().name
            )
            assert locks["Wc.a"]["waiters"], "contender not seen"
            # Held-while-blocking event: the contender stalls on a
            # WHILE holding c — the inversion-deadlock shape.
            assert any(
                e["wanted"] == "Wc.a" and "Wc.c" in e["held"]
                for e in snap["events"]
            )
            # The dump ships live stacks for holder + waiter threads.
            assert snap.get("stacks")
        thread.join(5)
        assert not thread.is_alive()

    def test_reset_clears_edges_and_events(self, witness):
        a = rt.make_lock("Wd.a")
        b = rt.make_lock("Wd.b")
        with a, b:
            pass
        assert rt.snapshot()["edges"]
        rt.reset()
        assert rt.snapshot()["edges"] == []


# -- witness vs static: the tier-1 gate --------------------------------------


class TestWitnessCrossCheck:
    def test_short_job_has_zero_unmatched_edges(
        self, witness, artifacts
    ):
        """The acceptance gate: a witness-enabled engine job whose
        store writes cross the armed fault plane (collection lock →
        plane lock → metrics lock, the real cross-module chain)
        witnesses edges, and EVERY one exists in the static
        whole-program graph."""
        artifacts.metadata.create("wit_job", {"name": "wit_job"})
        faults.arm("store.wal_write", "delay", delay_ms=0.0)
        try:
            engine = JobEngine(artifacts, max_workers=2)
            assert engine.submit("wit_job", lambda: 7).result(30) == 7
            engine.shutdown(wait=True)
        finally:
            faults.disarm_all()
        snap = rt.snapshot()
        assert snap["enabled"]
        assert snap["edges"], (
            "the drill should witness at least one ordering edge"
        )
        findings = cross_check(snap, _static_graph())
        assert findings == [], "\n".join(
            f.render() for f in findings
        )

    def test_unmatched_edge_fails_the_gate(self):
        """A witnessed edge the static graph lacks IS a finding — the
        false-negative detector actually detects."""
        graph = _static_graph()
        snap = {"edges": [{
            "from": "JobEngine._lock", "to": "_Collection.lock",
            "count": 3, "site": "somefile.py:12",
        }]}
        assert ("JobEngine._lock", "_Collection.lock") not in (
            graph.edge_pairs
        )
        findings = cross_check(snap, graph)
        assert len(findings) == 1
        assert findings[0].rule == "witness-unmatched-edge"
        assert findings[0].file == "somefile.py"
        assert findings[0].line == 12

    def test_self_edge_exempt_and_matched_edge_clean(self):
        graph = _static_graph()
        matched = next(iter(sorted(graph.edge_pairs)))
        snap = {"edges": [
            {"from": matched[0], "to": matched[1], "count": 1,
             "site": "x.py:1"},
            {"from": "MicroBatcher._cond", "to": "MicroBatcher._cond",
             "count": 2, "site": "x.py:2"},  # per-instance self-edge
        ]}
        assert cross_check(snap, graph) == []


# -- cooperative cancellation ------------------------------------------------


class TestCancelToken:
    def test_token_binding_and_idempotent_reason(self):
        token = CancelToken()
        assert current_cancel_token() is None
        assert not cancel_requested()
        with bind(token):
            assert current_cancel_token() is token
            assert not cancel_requested()
            token.cancel("first")
            token.cancel("second")
            assert cancel_requested()
            assert token.reason == "first"
        assert current_cancel_token() is None

    def test_cancelled_token_stops_fit_loop(self):
        """The epoch loops poll the bound token: a cancelled token
        winds a fit down like an early stop, before epoch work."""
        from learningorchestra_tpu.models.mlp import MLPClassifier

        rng = np.random.default_rng(0)
        x = rng.standard_normal((32, 4)).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int32)
        est = MLPClassifier(hidden_layer_sizes=[4], num_classes=2)
        token = CancelToken()
        token.cancel("test")
        with bind(token):
            est.fit(x, y, epochs=5, batch_size=16)
        assert est.stop_training
        assert len(est.history.get("loss", [])) == 0

    def test_watchdog_expiry_flips_token_so_zombie_exits_early(
        self, witness, artifacts
    ):
        """The ROADMAP regression: a deadline-failed body that POLLS
        the token exits the moment the watchdog expires it — and
        graceful shutdown(wait=True) returns immediately instead of
        joining a runaway zombie.  Runs witness-enabled (acceptance
        criterion): the engine/watchdog/shutdown interleaving happens
        on instrumented locks."""
        artifacts.metadata.create("coop", {"name": "coop"})
        engine = JobEngine(artifacts, max_workers=1, deadline_s=0.3)
        saw_token = threading.Event()
        failed = threading.Event()
        exited = threading.Event()

        def body():
            while not cancel_requested():
                time.sleep(0.01)
            saw_token.set()
            # The watchdog flips the token BEFORE it fails the future
            # (store writes in between): a body that returned here
            # could resolve the future first, with None.
            failed.wait(30)
            exited.set()

        future = engine.submit("coop", body)
        with pytest.raises(JobDeadlineExceeded):
            future.result(30)
        assert saw_token.wait(5), "body never saw the cancel token"
        failed.set()
        assert exited.wait(5)
        t0 = time.monotonic()
        engine.shutdown(wait=True)  # legacy unbounded drain is fine:
        # the zombie already exited cooperatively.
        assert time.monotonic() - t0 < 5.0
        assert engine.state("coop") == "failed"

    def test_bounded_drain_abandons_noncooperative_zombie(
        self, witness, artifacts
    ):
        """A body that ignores the token cannot hang a BOUNDED
        shutdown: past the drain budget its token flips, and past the
        grace it is abandoned (logged), not joined forever."""
        artifacts.metadata.create("stubborn", {"name": "stubborn"})
        engine = JobEngine(artifacts, max_workers=1, deadline_s=0.2)
        release = threading.Event()
        future = engine.submit(
            "stubborn", lambda: release.wait(60)
        )
        with pytest.raises(JobDeadlineExceeded):
            future.result(30)
        t0 = time.monotonic()
        engine.shutdown(
            wait=True, drain_timeout_s=0.3, grace_s=0.2
        )
        assert time.monotonic() - t0 < 3.0, (
            "bounded shutdown must not hang on a zombie"
        )
        release.set()  # unpin the abandoned daemon thread

    def test_bounded_drain_cancels_queued_jobs(self, artifacts):
        """Queued-never-dispatched work is cancelled (futures resolve)
        when the drain budget lapses, so shutdown waiters unblock."""
        for name in ("running", "queued"):
            artifacts.metadata.create(name, {"name": name})
        engine = JobEngine(artifacts, max_workers=1)
        release = threading.Event()
        running = engine.submit("running", lambda: release.wait(60))
        queued = engine.submit("queued", lambda: 1)
        t0 = time.monotonic()
        engine.shutdown(
            wait=True, drain_timeout_s=0.2, grace_s=0.1
        )
        assert time.monotonic() - t0 < 3.0
        assert queued.cancelled()
        release.set()
        assert running.cancelled() is False


class TestContextClose:
    def test_close_waits_bounded_when_drain_configured(
        self, tmp_path
    ):
        """LO_TPU_JOB_DRAIN_S reaches the deployed shutdown path:
        ServiceContext.close() WAITS (bounded) when a drain budget is
        configured — cancelling outstanding bodies past the budget —
        instead of the legacy fire-and-forget wait=False."""
        from learningorchestra_tpu.config import Config
        from learningorchestra_tpu.services.context import (
            ServiceContext,
        )

        cfg = Config()
        cfg.store.root = str(tmp_path / "store")
        cfg.store.volume_root = str(tmp_path / "volumes")
        cfg.jobs.shutdown_drain_s = 0.3
        ctx = ServiceContext(cfg)
        ctx.artifacts.metadata.create("slow", {"name": "slow"})
        release = threading.Event()
        ctx.engine.submit("slow", lambda: release.wait(60))
        t0 = time.monotonic()
        ctx.close()
        dt = time.monotonic() - t0
        assert 0.2 < dt < 5.0, (
            f"close() should drain ~budget+grace, took {dt:.2f}s"
        )
        release.set()


class TestWitnessDumpCLI:
    def test_env_dump_cross_checks_clean_via_cli(self, tmp_path):
        """The operator loop end-to-end: LO_TPU_WITNESS=1 +
        LO_TPU_WITNESS_DUMP in a fresh process (so MODULE-LEVEL locks
        are witnessed too), a store+faults workload, the atexit dump,
        then ``lo_check.py --witness <dump>`` exits 0."""
        import json
        import os
        import subprocess
        import sys

        root = PKG.rsplit("/", 1)[0]
        dump = tmp_path / "witness.json"
        script = (
            "import tempfile\n"
            "from learningorchestra_tpu.store import (\n"
            "    ArtifactStore, open_document_store)\n"
            "from learningorchestra_tpu.jobs.engine import JobEngine\n"
            "from learningorchestra_tpu import faults\n"
            "tmp = tempfile.mkdtemp()\n"
            "arts = ArtifactStore(open_document_store(\n"
            "    tmp + '/s', backend='python'))\n"
            "arts.metadata.create('j', {'name': 'j'})\n"
            "faults.arm('store.wal_write', 'delay', delay_ms=0.0)\n"
            "eng = JobEngine(arts, max_workers=1)\n"
            "assert eng.submit('j', lambda: 1).result(30) == 1\n"
            "eng.shutdown(wait=True)\n"
        )
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "LO_TPU_WITNESS": "1",
            "LO_TPU_WITNESS_DUMP": str(dump),
            "PYTHONPATH": root,
        })
        proc = subprocess.run(
            [sys.executable, "-c", script], env=env, cwd=root,
            capture_output=True, text=True, timeout=180,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(dump.read_text())
        assert doc["enabled"] and doc["edges"], (
            "module-level locks should witness edges in a fresh "
            "process"
        )
        check = subprocess.run(
            [sys.executable, root + "/scripts/lo_check.py",
             "learningorchestra_tpu", "--repo-root", root,
             "--witness", str(dump)],
            cwd=root, capture_output=True, text=True, timeout=180,
        )
        assert check.returncode == 0, check.stdout + check.stderr
        assert "0 error(s)" in check.stdout


class TestObservabilityLocks:
    def test_locks_endpoint_and_client_binding(
        self, witness, tmp_path
    ):
        """GET /observability/locks serves the witness dump; the
        client binding round-trips it."""
        from learningorchestra_tpu.api import APIServer
        from learningorchestra_tpu.client import Context
        from learningorchestra_tpu.config import Config

        cfg = Config()
        cfg.store.root = str(tmp_path / "store")
        cfg.store.volume_root = str(tmp_path / "volumes")
        server = APIServer(cfg)
        port = server.start_background()
        try:
            ctx = Context(f"http://127.0.0.1:{port}")
            doc = ctx.observability.locks()
            assert doc["enabled"] is True
            assert "edges" in doc and "locks" in doc
            assert doc["registeredLocks"] > 0
        finally:
            server.shutdown()
