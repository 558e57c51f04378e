"""Chaos-path coverage: the fault-injection plane (faults/plane.py)
and the self-healing machinery it exists to prove — seeded schedules
driven through the REAL call sites (train epochs, serve dispatch, WAL
appends, lease acquisition, engine dispatch, HTTP handling), asserting
jobs finish, retries resume from checkpoints, deadlines reclaim
workers and leases, and nothing leaks.

The autouse fixture tallies each test's observed triggers per point;
the gate test at the bottom fails any registered fault point the suite
never exercised (mirroring test_obs.py's every-route-metered gate) —
new fault points can't land untested.
"""

import threading
import time

import numpy as np
import pytest
import requests

from learningorchestra_tpu import faults
from learningorchestra_tpu.faults import FaultInjected, FaultSchedule

PREFIX = "/api/learningOrchestra/v1"

#: point -> triggers observed across the whole module, through real
#: call sites (accumulated by the autouse fixture before each reset).
_TALLY: dict = {}


@pytest.fixture(autouse=True)
def clean_plane():
    """Every test starts with the plane disarmed and zeroed, and its
    observed triggers feed the every-point-exercised gate."""
    faults.reset()
    yield
    st = faults.status()
    for point, doc in st["points"].items():
        _TALLY[point] = _TALLY.get(point, 0) + doc["triggers"]
    faults.reset()


@pytest.fixture(scope="module")
def chaos_api(tmp_path_factory):
    from learningorchestra_tpu.api import APIServer
    from learningorchestra_tpu.config import Config

    tmp = tmp_path_factory.mktemp("chaos_api")
    cfg = Config()
    cfg.store.root = str(tmp / "store")
    cfg.store.volume_root = str(tmp / "volumes")
    server = APIServer(cfg)
    port = server.start_background()
    base = f"http://127.0.0.1:{port}{PREFIX}"
    yield server, base, tmp
    server.shutdown()


def _install_trained_model(server, name):
    """Fabricate a finished train artifact holding a fitted estimator
    (bypasses the async pipeline — chaos on the serve path is what's
    under test; same shape as tests/test_serve.py)."""
    from learningorchestra_tpu.models.mlp import MLPClassifier

    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)
    est = MLPClassifier(hidden_layer_sizes=[8], num_classes=2, seed=0)
    est.compute_dtype = "float32"
    est.fit(x, y, epochs=1, batch_size=32)
    server.ctx.volumes.save_object("train/tensorflow", name, est)
    server.ctx.artifacts.metadata.create(name, "train/tensorflow")
    server.ctx.artifacts.metadata.mark_finished(name)
    return est, x


# -- schedule semantics ------------------------------------------------------


class TestSchedule:
    def test_seeded_rate_is_deterministic(self):
        """Same (seed, rate, point) → the same trigger pattern on
        every run; a different seed → a different pattern.  This is
        what makes chaos tests reproducible instead of flaky."""
        a = FaultSchedule("engine.dispatch", "error", rate=0.3, seed=42)
        b = FaultSchedule("engine.dispatch", "error", rate=0.3, seed=42)
        pattern = [a.should_fire() for _ in range(300)]
        assert pattern == [b.should_fire() for _ in range(300)]
        assert any(pattern) and not all(pattern)
        c = FaultSchedule("engine.dispatch", "error", rate=0.3, seed=7)
        assert pattern != [c.should_fire() for _ in range(300)]
        # ...and per-point streams differ under one seed (the point
        # name is mixed into the stream, not just the seed).
        d = FaultSchedule("lease.acquire", "error", rate=0.3, seed=42)
        assert pattern != [d.should_fire() for _ in range(300)]

    def test_after_skips_and_max_triggers_bounds(self):
        s = FaultSchedule(
            "engine.dispatch", "error", after=3, max_triggers=2
        )
        assert [s.should_fire() for _ in range(10)] == (
            [False] * 3 + [True] * 2 + [False] * 5
        )

    def test_parse_spec_grammar(self):
        kw = faults.parse_spec("preempt:rate=0.5,seed=7,max=2")
        assert kw == {"mode": "preempt", "rate": 0.5, "seed": 7,
                      "max_triggers": 2}
        assert faults.parse_spec("delay:ms=50") == {
            "mode": "delay", "delay_ms": 50.0,
        }
        # Typo'd chaos knobs reject LOUDLY — silently doing nothing
        # would fake a green drill.
        for bad in ("bogus", "error:typo=1", "delay:ms"):
            with pytest.raises(ValueError):
                faults.parse_spec(bad)
        with pytest.raises(ValueError):
            faults.arm("engine.dispatch", "error", rate=2.0)

    def test_unknown_point_rejected_env_spelling_resolves(self):
        with pytest.raises(ValueError):
            faults.arm("no.such_point", "error")
        # The env-var spelling (STORE_WAL_WRITE) resolves to the
        # canonical point even though the name itself contains "_".
        faults.arm("STORE_WAL_WRITE", "error")
        st = faults.status()
        assert st["points"]["store.wal_write"]["armed"]["mode"] == "error"

    def test_disabled_plane_is_inert(self):
        assert not faults.status()["enabled"]
        # No schedule armed: hit() is a no-op, never raises.
        for point in faults.points():
            faults.hit(point)
        assert all(
            doc["hits"] == 0 for doc in faults.status()["points"].values()
        )


# -- engine.dispatch: preemption retries with backoff ------------------------


class TestEngineChaos:
    def test_injected_preemptions_retry_and_finish(self, artifacts):
        from learningorchestra_tpu.jobs import JobEngine

        eng = JobEngine(artifacts, max_workers=2,
                        retry_backoff_s=0.01, retry_backoff_max_s=0.05)
        try:
            artifacts.metadata.create("chaos_eng", "train/x")
            faults.arm("engine.dispatch", "preempt", max_triggers=2)
            eng.submit("chaos_eng", lambda: "ok")
            assert eng.wait("chaos_eng", timeout=30) == "ok"
            meta = artifacts.metadata.read("chaos_eng")
            assert meta["jobState"] == "finished"
            assert meta["preemptions"] == 2
            states = [
                h["state"] for h in artifacts.ledger.history("chaos_eng")
            ]
            assert states.count("preempted") == 2
            assert states[-1] == "finished"
            assert faults.triggers("engine.dispatch") == 2
            # Per-attempt spans + backoff spans in the persisted trace.
            trace = next(
                rec["trace"]
                for rec in reversed(artifacts.ledger.history("chaos_eng"))
                if rec.get("trace")
            )
            job_spans = [
                s for s in trace["spans"] if s["name"] == "job"
            ]
            assert [s["attrs"]["attempt"] for s in job_spans] == [1, 2, 3]
            backoffs = [
                s for s in trace["spans"] if s["name"] == "retry_backoff"
            ]
            assert [s["attrs"]["attempt"] for s in backoffs] == [1, 2]
            assert all(s["durationS"] > 0 for s in backoffs)
        finally:
            eng.shutdown()

    def test_retry_budget_exhausts_to_failed(self, artifacts):
        from learningorchestra_tpu.jobs import JobEngine

        eng = JobEngine(artifacts, max_workers=1,
                        max_preemption_retries=2, retry_backoff_s=0.005)
        try:
            artifacts.metadata.create("chaos_exh", "train/x")
            faults.arm("engine.dispatch", "preempt")  # every attempt
            eng.submit("chaos_exh", lambda: "never")
            assert eng.wait("chaos_exh", timeout=30) is None
            meta = artifacts.metadata.read("chaos_exh")
            assert meta["jobState"] == "failed"
            assert "retries exhausted" in meta["exception"]
            assert faults.triggers("engine.dispatch") == 3  # 1 + 2 retries
        finally:
            eng.shutdown()


# -- deadlines: the watchdog ------------------------------------------------


class TestDeadline:
    def test_hung_job_fails_and_worker_is_reclaimed(self, artifacts):
        from learningorchestra_tpu.jobs import (
            JobDeadlineExceeded,
            JobEngine,
        )

        eng = JobEngine(artifacts, max_workers=1)
        release = threading.Event()
        try:
            artifacts.metadata.create("hung", "train/x")
            artifacts.metadata.create("after_hung", "train/x")
            fut = eng.submit(
                "hung", lambda: release.wait(30), deadline_s=0.3
            )
            # Queued behind the hung job on the ONLY worker: it can
            # run iff the watchdog reclaims the hung job's slot.
            eng.submit("after_hung", lambda: "ran")
            assert eng.wait("after_hung", timeout=15) == "ran"
            with pytest.raises(JobDeadlineExceeded):
                fut.result(timeout=15)
            meta = artifacts.metadata.read("hung")
            assert meta["jobState"] == "failed"
            assert "deadline" in meta["exception"]
            hist = artifacts.ledger.history("hung")
            assert hist[-1]["state"] == "deadline"
            # The zombie body finishing must NOT resurrect the job.
            release.set()
            time.sleep(0.3)
            assert artifacts.metadata.read("hung")["jobState"] == "failed"
        finally:
            release.set()
            eng.shutdown()

    def test_deadline_revokes_chip_leases(self, artifacts):
        from learningorchestra_tpu.jobs import (
            JobDeadlineExceeded,
            JobEngine,
        )
        from learningorchestra_tpu.jobs.leases import DeviceLeaser

        eng = JobEngine(artifacts, max_workers=2)
        leaser = DeviceLeaser(device_ids=["tpu:0"])
        eng.leaser = leaser
        release = threading.Event()
        entered = threading.Event()

        def pin_chip():
            with leaser.lease(1, label="pinner"):
                entered.set()
                release.wait(30)

        try:
            artifacts.metadata.create("pinner", "train/x")
            fut = eng.submit("pinner", pin_chip, deadline_s=0.25)
            assert entered.wait(15)
            # The zombie still sits in its with-block, but the
            # watchdog's revoke returned the chip to the pool: a new
            # lease acquires it instead of waiting out the zombie.
            with leaser.lease(1, label="taker", timeout=15) as devs:
                assert devs == ["tpu:0"]
            with pytest.raises(JobDeadlineExceeded):
                fut.result(timeout=15)
            # Now let the zombie exit its lease: the revoked device
            # must not be double-freed into the pool.
            release.set()
            time.sleep(0.3)
            with leaser._cv:
                assert sorted(leaser._free) == ["tpu:0"]
                assert leaser._active == []
        finally:
            release.set()
            eng.shutdown()

    def test_deadline_during_backoff_does_not_resurrect(self, artifacts):
        """The watchdog fires while the job sleeps in preemption
        backoff: the woken body must abandon — not mark_running over
        the watchdog's recorded failure and burn another attempt on
        leases the reclaim just freed."""
        from learningorchestra_tpu.jobs import (
            JobDeadlineExceeded,
            JobEngine,
            Preempted,
        )

        # Backoff (0.5-1.5s jittered) far outlives the 0.2s deadline,
        # so the watchdog always fires mid-sleep.
        eng = JobEngine(artifacts, max_workers=1,
                        retry_backoff_s=1.0, retry_backoff_max_s=1.0)
        attempts = []

        def body():
            attempts.append(time.monotonic())
            raise Preempted("chaos")

        try:
            artifacts.metadata.create("bkoff", "train/x")
            fut = eng.submit("bkoff", body, deadline_s=0.2)
            with pytest.raises(JobDeadlineExceeded):
                fut.result(timeout=15)
            # Outlive the backoff sleep: the woken body must not have
            # re-entered the loop (one attempt total, state still the
            # watchdog's).
            time.sleep(2.0)
            assert len(attempts) == 1
            meta = artifacts.metadata.read("bkoff")
            assert meta["jobState"] == "failed"
            assert "deadline" in meta["exception"]
        finally:
            eng.shutdown()

    def test_engine_default_applies_and_zero_disables(self, artifacts):
        from learningorchestra_tpu.jobs import (
            JobDeadlineExceeded,
            JobEngine,
        )

        eng = JobEngine(artifacts, max_workers=2, deadline_s=0.2)
        try:
            # Inherits the engine default (no per-submit override).
            artifacts.metadata.create("dflt", "train/x")
            fut = eng.submit("dflt", lambda: time.sleep(2.0))
            with pytest.raises(JobDeadlineExceeded):
                fut.result(timeout=15)
            # Per-submit 0 disables the default for this job.
            artifacts.metadata.create("nodl", "train/x")
            fut2 = eng.submit(
                "nodl", lambda: (time.sleep(0.4), "ok")[1], deadline_s=0
            )
            assert fut2.result(timeout=15) == "ok"
            assert artifacts.metadata.read("nodl")["jobState"] == "finished"
        finally:
            eng.shutdown()


# -- lease.acquire -----------------------------------------------------------


class TestLeaseChaos:
    def test_injected_lease_failure_then_clean_recovery(self):
        from learningorchestra_tpu.jobs.leases import DeviceLeaser

        leaser = DeviceLeaser(device_ids=["tpu:0"])
        faults.arm("lease.acquire", "error", max_triggers=1)
        with pytest.raises(FaultInjected):
            with leaser.lease(1, label="victim"):
                pass
        # The failed acquisition took nothing: the next lease gets the
        # chip immediately and the pool is whole afterwards.
        with leaser.lease(1, label="survivor", timeout=5) as devs:
            assert devs == ["tpu:0"]
        with leaser._cv:
            assert sorted(leaser._free) == ["tpu:0"]
            assert leaser._active == []
        assert faults.triggers("lease.acquire") == 1

    def test_injected_lease_delay_is_latency_not_failure(self):
        from learningorchestra_tpu.jobs.leases import DeviceLeaser

        leaser = DeviceLeaser(device_ids=["tpu:0"])
        faults.arm("lease.acquire", "delay", delay_ms=60, max_triggers=1)
        t0 = time.monotonic()
        with leaser.lease(1, label="slow", timeout=5) as devs:
            assert devs == ["tpu:0"]
        assert time.monotonic() - t0 >= 0.055


# -- compile.build -----------------------------------------------------------


class TestCompileChaos:
    def test_injected_compile_failure_is_not_cached(self):
        from learningorchestra_tpu.train.compile_cache import (
            CompiledProgramCache,
        )

        cache = CompiledProgramCache()
        built = []
        faults.arm("compile.build", "error", max_triggers=1)

        def builder():
            built.append(1)
            return "program"

        with pytest.raises(FaultInjected):
            cache.get_or_build("k1", builder)
        # The injected failure fired BEFORE the builder (modeling a
        # tracing/XLA crash) and poisoned nothing: the retry builds
        # and caches normally.
        assert cache.get_or_build("k1", builder) == "program"
        assert built == [1]
        assert cache.contains("k1")
        assert cache.get_or_build("k1", builder) == "program"  # hit
        assert built == [1]
        assert faults.triggers("compile.build") == 1


# -- cache.aot_load / cache.aot_store ----------------------------------------


@pytest.fixture()
def aot_round_trip(tmp_path):
    """A durable store holding one REAL serialized executable, plus
    the key/apply/args to restore it — installed as the process
    singleton for the test, always uninstalled after."""
    import jax
    import jax.numpy as jnp

    from learningorchestra_tpu.train import aot_store
    from learningorchestra_tpu.train import compile_cache as cc

    store = aot_store.reset_store(
        root=str(tmp_path / "aot"), max_entries=8, max_bytes=1 << 30
    )
    fn = jax.jit(lambda a: a * 2.0)
    compiled = fn.lower(
        jax.ShapeDtypeStruct((4,), jnp.float32)
    ).compile()
    key = cc.fingerprint("chaos", "aot")
    store.offer(key, aot_store.serialize(compiled), label="chaos")
    yield store, key
    aot_store.reset_store()


class TestAOTChaos:
    def test_injected_load_error_degrades_to_live_retrace(
        self, aot_round_trip
    ):
        """A corrupt/failed AOT deserialize must never fail the
        request: the load-error counter bumps, the blob survives
        (injected chaos is transient, not corruption), and the
        program builds live."""
        import jax
        import numpy as np

        from learningorchestra_tpu.train import compile_cache as cc

        store, key = aot_round_trip
        faults.arm("cache.aot_load", "error", max_triggers=1)
        cache = cc.CompiledProgramCache(max_entries=8)
        built = []

        def builder():
            built.append(1)
            return jax.jit(lambda a: a * 2.0)

        apply = cache.get_or_build(key, builder, label="chaos")
        out = np.asarray(apply(np.ones(4, dtype=np.float32)))
        assert out.tolist() == [2.0, 2.0, 2.0, 2.0]
        # Degraded to the live build — and the blob is still there
        # for the next boot (an injected error is not corruption).
        assert built == [1]
        assert store.load_errors == 1
        assert store.contains(key)
        assert faults.triggers("cache.aot_load") == 1
        # Disarmed: a fresh cache restores from disk, no rebuild.
        cache2 = cc.CompiledProgramCache(max_entries=8)
        restored = cache2.get_or_build(key, builder, label="chaos")
        assert built == [1]
        out2 = np.asarray(restored(np.ones(4, dtype=np.float32)))
        assert out2.tolist() == [2.0, 2.0, 2.0, 2.0]
        assert store.hits == 1

    def test_injected_store_error_counts_and_build_proceeds(
        self, tmp_path
    ):
        """An injected persist failure costs only the durability —
        ``offer`` returns False, the error counter bumps, and a
        disarmed re-offer lands the blob."""
        from learningorchestra_tpu.train import aot_store
        from learningorchestra_tpu.train import compile_cache as cc

        store = aot_store.AOTExecutableStore(
            str(tmp_path / "aot2"), max_entries=8, max_bytes=1 << 30
        )
        key = cc.fingerprint("chaos", "aot_store")
        faults.arm("cache.aot_store", "error", max_triggers=1)
        assert store.offer(key, ("payload",), label="chaos") is False
        assert store.store_errors == 1
        assert not store.contains(key)
        assert faults.triggers("cache.aot_store") == 1
        # Disarmed: the same offer persists.
        assert store.offer(key, ("payload",), label="chaos") is True
        assert store.contains(key)


# -- store.wal_write ---------------------------------------------------------


class TestStoreChaos:
    def test_wal_faults_fail_writes_replay_recovers(self, tmp_path):
        from learningorchestra_tpu.store import DocumentStore

        store = DocumentStore(tmp_path / "chaos_store")
        ok = []
        faults.arm("store.wal_write", "error", after=5, max_triggers=3)
        for i in range(20):
            try:
                store.insert_one("events", {"i": i})
                ok.append(i)
            except FaultInjected:
                pass
        faults.disarm("store.wal_write")
        assert len(ok) == 17
        assert faults.triggers("store.wal_write") == 3
        store.close()
        # Replay-on-reopen: exactly the successfully logged writes
        # survive — a failed WAL append may leave the in-memory map
        # ahead of the log (a real fsync failure's shape), but never
        # corrupts what was committed.
        store2 = DocumentStore(tmp_path / "chaos_store")
        assert {d["i"] for d in store2.find("events")} == set(ok)
        store2.close()

    def test_native_backend_carries_the_same_probe(self, tmp_path):
        """The default (native C++) backend must fire armed
        ``store.wal_write`` schedules too — a probe existing on only
        one backend would fake a green drill on the other."""
        from learningorchestra_tpu import native

        if not native.native_available():
            pytest.skip("native library not built")
        store = native.NativeDocumentStore(tmp_path / "native_chaos")
        try:
            store.insert_one("events", {"i": 0})
            faults.arm("store.wal_write", "error", max_triggers=1)
            with pytest.raises(FaultInjected):
                store.insert_one("events", {"i": 1})
            # One-shot schedule spent: writes recover, nothing leaked.
            store.insert_one("events", {"i": 2})
            assert faults.triggers("store.wal_write") == 1
            assert {d["i"] for d in store.find("events")} == {0, 2}
        finally:
            store.close()

    def test_seeded_rate_schedule_is_reproducible_on_store(self, tmp_path):
        from learningorchestra_tpu.store import DocumentStore

        outcomes = []
        for run in range(2):
            faults.reset()
            store = DocumentStore(tmp_path / f"rep_{run}")
            faults.arm("store.wal_write", "error", rate=0.3, seed=11)
            pattern = []
            for i in range(30):
                try:
                    store.insert_one("docs", {"i": i})
                    pattern.append(True)
                except FaultInjected:
                    pattern.append(False)
            outcomes.append(pattern)
            faults.disarm("store.wal_write")
            store.close()
        assert outcomes[0] == outcomes[1]
        assert any(outcomes[0]) and not all(outcomes[0])


# -- serve.apply + http.handler + the REST surface ---------------------------


class TestServeChaos:
    def test_injected_apply_fault_fails_batch_not_worker(self, chaos_api):
        server, base, _ = chaos_api
        _, x = _install_trained_model(server, "chaos_srv")
        resp = requests.post(f"{base}/serve/chaos_srv/load", json={})
        assert resp.status_code == 200, resp.text

        faults.arm("serve.apply", "error", max_triggers=1)
        resp = requests.post(
            f"{base}/serve/chaos_srv/predict",
            json={"instances": x[:2].tolist()},
        )
        assert resp.status_code == 500
        assert "injected fault" in resp.json()["error"]
        # The batcher worker survived the poisoned dispatch: the very
        # next predict serves normally.
        resp = requests.post(
            f"{base}/serve/chaos_srv/predict",
            json={"instances": x[:2].tolist()},
        )
        assert resp.status_code == 200, resp.text
        assert len(resp.json()["predictions"]) == 2
        assert faults.triggers("serve.apply") == 1


class TestServeRouteChaos:
    """The fleet's routing-decision fault point (serve.route), driven
    through its real call site — ``P2CRouter.choose``, the function in
    front of every fleet predict."""

    def test_injected_route_delay_then_error(self):
        from learningorchestra_tpu.serve.fleet import P2CRouter

        router = P2CRouter(seed=3)
        faults.arm("serve.route", "delay", delay_ms=40, max_triggers=1)
        t0 = time.monotonic()
        order = router.choose([3, 0])
        assert 0.03 <= time.monotonic() - t0 < 5.0
        assert order == [1, 0]  # delayed, not rerouted
        faults.disarm("serve.route")

        faults.arm("serve.route", "error", max_triggers=1)
        with pytest.raises(faults.FaultInjected):
            router.choose([1, 1, 2])
        # Routing recovers on the very next decision.
        assert sorted(router.choose([1, 1, 2])) == [0, 1, 2]
        assert faults.triggers("serve.route") == 2


def _install_trained_lm(server, name):
    """Finished train artifact holding a fitted tiny DecoderLM —
    chaos on the decode step is what's under test, not training."""
    from learningorchestra_tpu.models.text import DecoderLM

    rng = np.random.default_rng(1)
    x = rng.integers(1, 8, size=(8, 12)).astype(np.int32)
    y = np.concatenate([x[:, 1:], np.zeros((8, 1), np.int32)], axis=1)
    est = DecoderLM(vocab_size=8, hidden_dim=16, num_layers=1,
                    num_heads=2, max_len=16, seed=0)
    est.compute_dtype = "float32"
    est.fit(x, y, epochs=1, batch_size=8)
    server.ctx.volumes.save_object("train/tensorflow", name, est)
    server.ctx.artifacts.metadata.create(name, "train/tensorflow")
    server.ctx.artifacts.metadata.mark_finished(name)
    return est


class TestDecodeChaos:
    """The decode engine's step fault point (serve.decode_step),
    fired in the worker immediately before each pool step."""

    def test_injected_decode_step_fails_streams_not_worker(
        self, chaos_api
    ):
        server, base, _ = chaos_api
        _install_trained_lm(server, "chaos_lm")
        faults.arm("serve.decode_step", "error", max_triggers=1)
        resp = requests.post(
            f"{base}/serve/chaos_lm/generate",
            json={"prompts": [[5, 1, 2]], "maxNewTokens": 4},
        )
        # Blast radius = that pool's streams: the request fails with
        # the injected fault surfaced, 406 (ServeError), not a 500.
        assert resp.status_code == 406, resp.text
        assert "injected fault" in resp.json()["error"]
        # The decode worker survived the poisoned step: the very next
        # generate serves normally.
        resp = requests.post(
            f"{base}/serve/chaos_lm/generate",
            json={"prompts": [[5, 1, 2]], "maxNewTokens": 4},
        )
        assert resp.status_code == 200, resp.text
        assert len(resp.json()["newTokens"][0]) == 4
        assert faults.triggers("serve.decode_step") == 1


class TestHttpChaos:
    def test_injected_handler_error_then_recovery(self, chaos_api):
        _, base, _ = chaos_api
        faults.arm("http.handler", "error", max_triggers=1)
        assert requests.get(f"{base}/health").status_code == 500
        assert requests.get(f"{base}/health").status_code == 200

    def test_injected_handler_delay_is_latency(self, chaos_api):
        _, base, _ = chaos_api
        faults.arm("http.handler", "delay", delay_ms=80, max_triggers=1)
        t0 = time.monotonic()
        assert requests.get(f"{base}/health").status_code == 200
        assert time.monotonic() - t0 >= 0.075

    def test_rest_surface_arm_status_disarm(self, chaos_api):
        _, base, _ = chaos_api
        resp = requests.post(
            f"{base}/faults/http.handler",
            json={"mode": "delay", "delayMs": 5, "maxTriggers": 1},
        )
        assert resp.status_code == 201, resp.text
        assert resp.json()["armed"]["mode"] == "delay"
        st = requests.get(f"{base}/faults").json()
        assert st["enabled"]
        assert st["points"]["http.handler"]["armed"]["delayMs"] == 5
        requests.get(f"{base}/health")  # trigger it
        st = requests.get(f"{base}/faults").json()
        assert st["points"]["http.handler"]["triggers"] >= 1
        assert requests.delete(
            f"{base}/faults/http.handler"
        ).status_code == 200
        assert requests.delete(
            f"{base}/faults/http.handler"
        ).status_code == 404  # already disarmed
        # Bad requests reject loudly.
        assert requests.post(
            f"{base}/faults/engine.dispatch", json={}
        ).status_code == 406  # missing mode
        assert requests.post(
            f"{base}/faults/no.such", json={"mode": "error"}
        ).status_code == 406  # unknown point
        assert requests.post(
            f"{base}/faults/engine.dispatch",
            json={"mode": "error", "rate": 2},
        ).status_code == 406  # rate out of range
        # Disarm-all sweeps whatever is left.
        requests.post(
            f"{base}/faults/engine.dispatch", json={"mode": "error"}
        )
        assert requests.delete(f"{base}/faults").status_code == 200
        assert not requests.get(f"{base}/faults").json()["enabled"]

    def test_profile_start_under_injected_error_leaks_no_lock(
            self, chaos_api):
        """Profile-capture chaos drill: an injected http.handler error
        on POST /observability/profile/start fires BEFORE the handler
        claims the single-capture lock, so the failed request must
        not leave a phantom active capture behind — the retry starts
        cleanly, and stop round-trips."""
        _, base, _ = chaos_api
        faults.arm("http.handler", "error", max_triggers=1)
        resp = requests.post(
            f"{base}/observability/profile/start",
            json={"name": "chaos_prof"},
        )
        assert resp.status_code == 500
        assert "injected fault" in resp.json()["error"]
        # No leaked lock: the capture never started.
        status = requests.get(
            f"{base}/observability/profile"
        ).json()
        assert status["active"] is None
        # The very next start succeeds and the round-trip completes.
        resp = requests.post(
            f"{base}/observability/profile/start",
            json={"name": "chaos_prof"},
        )
        assert resp.status_code == 201, resp.text
        resp = requests.post(
            f"{base}/observability/profile/stop", json={}
        )
        assert resp.status_code == 200, resp.text
        assert resp.json()["capture"]["name"] == "chaos_prof"

    def test_trigger_counters_export_to_prometheus(self, chaos_api):
        _, base, _ = chaos_api
        faults.arm("http.handler", "delay", delay_ms=1, max_triggers=1)
        requests.get(f"{base}/health")
        text = requests.get(f"{base}/metrics.prom").text
        assert "lo_fault_triggers_total" in text
        assert 'point="http.handler"' in text


class TestBootArming:
    def test_env_specs_arm_at_server_construction(self, tmp_path,
                                                  monkeypatch):
        from learningorchestra_tpu.api import APIServer
        from learningorchestra_tpu.config import Config

        monkeypatch.setenv(
            "LO_TPU_FAULT_ENGINE_DISPATCH", "preempt:rate=0.5,seed=7"
        )
        cfg = Config.from_env()
        assert cfg.faults.specs["ENGINE_DISPATCH"] == \
            "preempt:rate=0.5,seed=7"
        cfg.store.root = str(tmp_path / "store")
        cfg.store.volume_root = str(tmp_path / "volumes")
        server = APIServer(cfg)
        try:
            armed = faults.status()["points"]["engine.dispatch"]["armed"]
            assert armed["mode"] == "preempt"
            assert armed["rate"] == 0.5
            assert armed["seed"] == 7
        finally:
            server.shutdown()

    def test_bad_boot_spec_raises_at_construction(self, tmp_path):
        from learningorchestra_tpu.api import APIServer
        from learningorchestra_tpu.config import Config

        cfg = Config()
        cfg.store.root = str(tmp_path / "store")
        cfg.store.volume_root = str(tmp_path / "volumes")
        cfg.faults.specs["ENGINE_DISPATCH"] = "bogus"
        with pytest.raises(ValueError):
            APIServer(cfg)


# -- LeaseTimeout → 503 + Retry-After ----------------------------------------


class TestLeaseTimeout503:
    def test_lease_timeout_maps_to_503_with_retry_after(self, chaos_api):
        from learningorchestra_tpu.jobs.leases import LeaseTimeout

        server, base, _ = chaos_api

        def saturated(m, body, query):
            raise LeaseTimeout("no chip lease within placement budget")

        server.router.add("GET", r"/_chaos/saturated", saturated)
        resp = requests.get(f"{base}/_chaos/saturated")
        assert resp.status_code == 503
        retry_after = server.config.serve.retry_after_s
        assert float(resp.headers["Retry-After"]) == retry_after
        assert resp.json()["retryAfter"] == retry_after
        assert "no chip lease" in resp.json()["error"]


# -- train.epoch: the acceptance-criteria chaos drill ------------------------


class TestTrainChaos:
    def test_preempted_fit_resumes_from_checkpoint(self, tmp_path):
        """A seeded schedule preempts a 6-epoch fit at the top of
        epoch 3; the ENGINE's automatic retry (no manual PATCH)
        resumes from the managed checkpoint — attempt 2 trains epochs
        3..5, never epoch 0 — with backoff applied and one span per
        attempt in the persisted trace.

        Runs under the RUNTIME LOCK WITNESS (LO_TPU_WITNESS
        semantics via set_witness): the preemption/retry error path
        exercises lock nestings the happy path never touches, and
        every witnessed acquisition-order edge must exist in the
        static whole-program graph (the losan cross-check gate on an
        ERROR path, not just a clean run)."""
        from learningorchestra_tpu import concurrency_rt as rt
        from learningorchestra_tpu.config import Config
        from learningorchestra_tpu.obs import metrics as obs_metrics
        from learningorchestra_tpu.services.context import ServiceContext
        from learningorchestra_tpu.services.executor import ExecutorService
        from learningorchestra_tpu.services.model import ModelService

        rt.set_witness(True)
        rt.reset()
        # Rebuilt under the witness (enablement is construction-time):
        # an earlier test's registry would carry a plain, invisible
        # lock into the drill's WAL-append → trigger-counter chain.
        obs_metrics.reset_registry()
        cfg = Config()
        # Python store backend: the witness instruments Python-level
        # locks, and the WAL-append-under-collection-lock nesting is
        # the cross-module chain this drill is meant to capture (the
        # native C++ store synchronizes internally, invisibly).
        cfg.store.backend = "python"
        cfg.store.root = str(tmp_path / "store")
        cfg.store.volume_root = str(tmp_path / "volumes")
        cfg.jobs.retry_backoff_s = 0.01
        cfg.jobs.retry_backoff_max_s = 0.05
        ctx = ServiceContext(cfg)
        try:
            model = ModelService(ctx)
            executor = ExecutorService(ctx)
            rng = np.random.default_rng(0)
            x = rng.standard_normal((32, 4)).astype(np.float32)
            y = (x.sum(1) > 0).astype(np.int32)

            model.create(
                "chaos_mlp",
                module_path="learningorchestra_tpu.models.mlp",
                class_name="MLPClassifier",
                class_parameters={
                    "hidden_layer_sizes": [4], "num_classes": 2,
                },
            )
            ctx.engine.wait("chaos_mlp", timeout=60)

            # 4th epoch-start hit preempts, exactly once: attempt 1
            # runs epochs 0-2 (each checkpointed), dies entering 3.
            faults.arm(
                "train.epoch", "preempt", after=3, max_triggers=1
            )
            # Zero-cost schedule on the WAL boundary so the drill's
            # store writes traverse the trigger-counter path UNDER the
            # collection lock — the witnessed cross-module chain the
            # losan gate below cross-checks on this error path.
            # Bounded triggers: one is enough for the edge; unbounded
            # would log a warning per WAL append.
            faults.arm(
                "store.wal_write", "delay", delay_ms=0.0,
                max_triggers=5,
            )
            executor.create(
                "chaos_fit",
                parent_name="chaos_mlp",
                method="fit",
                method_parameters={
                    "x": x.tolist(), "y": y.tolist(), "epochs": 6,
                    "checkpoint_every": 1,
                    "checkpoint_min_interval_s": 0,
                    "checkpoint_async": False,
                },
                artifact_type="train/tensorflow",
            )
            ctx.engine.wait("chaos_fit", timeout=300)

            meta = ctx.artifacts.metadata.read("chaos_fit")
            assert meta["jobState"] == "finished", meta.get("exception")
            assert meta["preemptions"] == 1
            assert faults.triggers("train.epoch") == 1

            hist = ctx.artifacts.ledger.history("chaos_fit")
            states = [h["state"] for h in hist]
            assert states.count("preempted") == 1
            assert states[-1] == "finished"

            trace = next(
                rec["trace"] for rec in reversed(hist)
                if rec.get("trace")
            )
            spans = trace["spans"]
            by_id = {s["id"]: s for s in spans}

            def attempt_of(span):
                cur = span
                while cur is not None:
                    if cur["name"] == "job":
                        return cur["attrs"]["attempt"]
                    cur = by_id.get(cur.get("parent"))
                return None

            job_spans = [s for s in spans if s["name"] == "job"]
            assert [s["attrs"]["attempt"] for s in job_spans] == [1, 2]
            backoffs = [
                s for s in spans if s["name"] == "retry_backoff"
            ]
            assert len(backoffs) == 1
            assert backoffs[0]["durationS"] > 0

            epochs = {}
            for s in spans:
                if s["name"] == "epoch":
                    epochs.setdefault(attempt_of(s), []).append(
                        s["attrs"]["epoch"]
                    )
            # Attempt 1 trained 0-2; the retry RESUMED at 3 — a
            # restart-from-scratch would re-log epoch 0 here.
            assert sorted(epochs[1]) == [0, 1, 2]
            assert sorted(epochs[2]) == [3, 4, 5]

            # losan gate on the ERROR path: the drill's witnessed
            # lock orders (store WAL under collection locks, compile
            # cache, leases, retry bookkeeping) must all exist in the
            # static whole-program graph.
            from test_witness_cancel import _static_graph

            from learningorchestra_tpu.analysis.witness import (
                cross_check,
            )

            snap = rt.snapshot()
            assert snap["edges"], (
                "a preempted fit should witness ordering edges"
            )
            unmatched = cross_check(snap, _static_graph())
            assert unmatched == [], "\n".join(
                f.render() for f in unmatched
            )
        finally:
            rt.set_witness(False)
            rt.reset()
            obs_metrics.reset_registry()
            ctx.close()


# -- replica.wal_ship / store.ha.failover: the HA-tier points -----------------


class TestReplicationChaos:
    """The HA/replication tier's fault points (PR-7 carried
    follow-up): WAL shipping and promotion run under seeded schedules
    so the kill-9 recovery drills can chaos the failover path too."""

    def test_injected_wal_ship_error_then_clean_resync(self, tmp_path):
        """An injected error at the shipping boundary models the
        standby crashing mid-ship: shipped offsets are durable, so
        the next sync resumes and the replica converges."""
        from learningorchestra_tpu.store.document_store import (
            DocumentStore,
        )
        from learningorchestra_tpu.store.replica import WalReplica

        primary = tmp_path / "primary"
        store = DocumentStore(primary)
        for i in range(5):
            store.insert_one("rows", {"n": i})
        replica = WalReplica(str(primary), tmp_path / "replica")
        faults.arm("replica.wal_ship", "error", max_triggers=1)
        with pytest.raises(FaultInjected):
            replica.sync()
        shipped = replica.sync()  # supervisor-restart analogue
        assert sum(shipped.values()) > 0
        assert faults.triggers("replica.wal_ship") == 1
        assert len(replica.find("rows")) == 5
        store.close()

    def test_injected_wal_ship_delay_is_lag_not_failure(self, tmp_path):
        from learningorchestra_tpu.store.document_store import (
            DocumentStore,
        )
        from learningorchestra_tpu.store.replica import WalReplica

        primary = tmp_path / "primary"
        store = DocumentStore(primary)
        store.insert_one("rows", {"n": 1})
        replica = WalReplica(str(primary), tmp_path / "replica")
        faults.arm("replica.wal_ship", "delay", delay_ms=30,
                   max_triggers=1)
        t0 = time.monotonic()
        replica.sync()
        assert time.monotonic() - t0 >= 0.03
        assert len(replica.find("rows")) == 1
        store.close()

    def test_injected_failover_fault_promotion_retries(self, tmp_path):
        """Promotion dies at the election moment under a seeded
        schedule; the retry (a supervisor restart) promotes cleanly —
        epoch bumped, old primary fenced."""
        from learningorchestra_tpu.store.document_store import (
            DocumentStore,
        )
        from learningorchestra_tpu.store.ha import StandbyMonitor
        from learningorchestra_tpu.store.replica import read_epoch

        primary = tmp_path / "primary"
        store = DocumentStore(primary)
        store.insert_one("rows", {"n": 1})
        store.close()
        monitor = StandbyMonitor(
            "127.0.0.1:1", primary, tmp_path / "replica",
            probe_timeout=0.2, new_primary_addr="127.0.0.1:9",
        )
        monitor.step()
        faults.arm("store.ha.failover", "error", max_triggers=1)
        with pytest.raises(FaultInjected):
            monitor.promote()
        # Nothing half-promoted: no epoch bump, no fence landed.
        assert read_epoch(tmp_path / "replica") == 0
        assert not (primary / ".fenced").exists()
        promoted = monitor.promote()
        assert read_epoch(promoted) == 1
        assert (primary / ".fenced").exists()
        assert faults.triggers("store.ha.failover") == 1


# -- cluster control plane: claim / heartbeat / steal ------------------------


class TestClusterChaos:
    """Chaos on the scale-out control plane (jobs/cluster.py): claim
    failures must resolve to LOST (the peer's copy runs), heartbeat
    and steal wobbles must heal on the next tick — never crash an
    engine."""

    def _coordinator(self, store, **kw):
        from learningorchestra_tpu.jobs.cluster import ClusterCoordinator

        kw.setdefault("heartbeat_s", 30.0)
        kw.setdefault("ttl_s", 60.0)
        kw.setdefault("sweep_s", 30.0)
        # No join(): tests drive claim/heartbeat/sweep directly so the
        # seeded schedules hit deterministic call counts.
        return ClusterCoordinator(store, store.root, **kw)

    def test_injected_claim_error_resolves_to_lost(self, artifacts):
        """An armed cluster.claim error rides a REAL engine dispatch:
        the job's future resolves None (claim lost — in production the
        peer that owns the claim runs the body) and the engine worker
        survives to run the next, unfaulted dispatch."""
        from learningorchestra_tpu.jobs import JobEngine

        eng = JobEngine(artifacts, max_workers=1)
        eng.cluster = self._coordinator(
            artifacts.documents, engine_id="chaos-a"
        )
        try:
            faults.arm("cluster.claim", "error", max_triggers=1)
            artifacts.metadata.create("chaos_claim1", "train/x")
            eng.submit("chaos_claim1", lambda: "never")
            assert eng.wait("chaos_claim1", timeout=30) is None
            assert faults.triggers("cluster.claim") == 1
            # Same engine, fault exhausted: claim lands, body runs.
            artifacts.metadata.create("chaos_claim2", "train/x")
            eng.submit("chaos_claim2", lambda: "ok")
            assert eng.wait("chaos_claim2", timeout=30) == "ok"
            assert eng.cluster.verify("chaos_claim1") is False
        finally:
            eng.shutdown()
            eng.cluster.close()

    def test_injected_heartbeat_error_next_tick_renews(self, tmp_store):
        """A heartbeat-tick fault is one missed renewal, absorbed by
        the lease TTL margin — the next tick renews every live claim
        (the daemon loop catches per-tick exceptions the same way)."""
        from learningorchestra_tpu.faults import FaultInjected

        coord = self._coordinator(tmp_store, engine_id="chaos-hb")
        try:
            assert coord.claim("chaos_hb_job")
            faults.arm("cluster.heartbeat", "error", max_triggers=1)
            with pytest.raises(FaultInjected):
                coord.heartbeat()
            assert coord.heartbeat() == 1  # renewed the live claim
            assert faults.triggers("cluster.heartbeat") == 1
        finally:
            coord.close()

    def test_injected_steal_error_next_sweep_finishes(self, tmp_store):
        """A sweeper crashing mid-steal leaves the claim with its
        (dead) owner; the NEXT sweep completes the takeover in the
        same claim order — no claim is ever half-stolen."""
        from learningorchestra_tpu.faults import FaultInjected

        dead = self._coordinator(tmp_store, engine_id="chaos-dead")
        thief = self._coordinator(
            tmp_store, engine_id="chaos-thief", ttl_s=0.05
        )
        try:
            assert dead.claim("chaos_steal_job")
            time.sleep(0.12)  # lease idles past the thief's TTL
            faults.arm("cluster.steal", "error", max_triggers=1)
            with pytest.raises(FaultInjected):
                thief.sweep()
            # Interrupted steal: ownership unchanged.
            assert dead.verify("chaos_steal_job") is True
            stolen = thief.sweep()  # fault exhausted
            assert ("chaos_steal_job", "chaos-dead") in stolen
            assert thief.verify("chaos_steal_job") is True
            assert dead.verify("chaos_steal_job") is False
            assert faults.triggers("cluster.steal") == 1
        finally:
            dead.close()
            thief.close()


# -- the gate: every fault point exercised -----------------------------------


def test_every_fault_point_exercised():
    """Mirrors test_obs.py's every-route-metered gate: a fault point
    registered in the plane but never TRIGGERED through its real call
    site by this suite fails here — new fault points can't land
    untested.  (Runs last: pytest executes this file in definition
    order; the autouse fixture feeds _TALLY.)"""
    missing = sorted(
        p for p in faults.points() if _TALLY.get(p, 0) == 0
    )
    assert not missing, (
        f"fault points with no chaos coverage: {missing} — add a "
        "seeded-schedule test driving each through its real call site"
    )
