"""Fleet serving tests: P2C routing skew, replica lifecycle with chip-
lease accounting, autoscaler drills under seeded fault-plane schedules
(scale-up on sustained queue depth, scale-down with lease release,
drain-before-unload), and the REST surface end-to-end — the ISSUE-10
acceptance drill runs through real HTTP against an injected device
pool.
"""

import threading
import time

import numpy as np
import pytest
import requests

from learningorchestra_tpu import faults
from learningorchestra_tpu.config import FleetConfig, ServeConfig
from learningorchestra_tpu.jobs.leases import DeviceLeaser
from learningorchestra_tpu.serve.batcher import QueueFull
from learningorchestra_tpu.serve.fleet import (
    Autoscaler,
    P2CRouter,
    ReplicaSet,
)

PREFIX = "/api/learningOrchestra/v1"


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _stub_set(
    n_devices=3,
    dispatch=None,
    *,
    min_replicas=1,
    max_replicas=3,
    max_batch=8,
    max_queue=64,
    flush_ms=1.0,
):
    """ReplicaSet over an injected device pool with a stub dispatch:
    real routing/scaling/leasing, no model."""
    leaser = DeviceLeaser([f"tpu:{i}" for i in range(n_devices)])
    cfg = ServeConfig(
        max_batch=max_batch, max_queue=max_queue, flush_ms=flush_ms
    )
    fn = dispatch or (lambda padded: padded)
    rs = ReplicaSet(
        "m", cfg, leaser, lambda replica: fn,
        min_replicas=min_replicas, max_replicas=max_replicas,
    )
    rs.scale_to(min_replicas, reason="ensure")  # what ensure() does
    return rs, leaser


class _StubManager:
    """The slice of FleetManager the Autoscaler consumes."""

    def __init__(self, rs):
        self.rs = rs

    def sets_snapshot(self):
        return [(self.rs.name, self.rs)]

    def scale(self, name, n, *, reason):
        return self.rs.scale_to(n, reason=reason)


# -- router ------------------------------------------------------------------


class TestP2CRouter:
    def test_single_replica_shortcut(self):
        assert P2CRouter(seed=0).choose([7]) == [0]
        assert P2CRouter(seed=0).choose([]) == []

    def test_pair_picks_shallower_queue(self):
        # n == 2 needs no sampling: the pair IS both replicas, and the
        # winner must be the shallower queue.
        router = P2CRouter(seed=0)
        assert router.choose([5, 0]) == [1, 0]
        assert router.choose([0, 5]) == [0, 1]

    def test_candidate_order_covers_every_replica(self):
        router = P2CRouter(seed=1)
        for depths in ([3, 1, 4, 1, 5], [0, 0, 0]):
            order = router.choose(depths)
            assert sorted(order) == list(range(len(depths)))

    def test_skew_bound_under_uniform_load(self):
        """Seeded P2C over idle (equal-depth) replicas must spread
        near-uniformly: with 3 replicas and 600 requests, every
        replica takes at least 20% of the traffic (exactly
        reproducible — the router RNG is seeded)."""
        rs, _ = _stub_set(flush_ms=0.0)
        try:
            rs.scale_to(3)
            row = np.ones((1, 2), np.float32)
            for _ in range(600):
                rs.submit(row)
            counts = [
                r["requests"] for r in rs.status()["replicas"]
            ]
            assert sum(counts) == 600
            assert min(counts) >= 120, counts  # >= 20% each
        finally:
            rs.close()


# -- replica lifecycle + lease accounting ------------------------------------


class TestReplicaLifecycle:
    def test_scale_up_down_moves_chip_leases(self):
        rs, leaser = _stub_set()
        try:
            assert rs.scale_to(1) == 1
            snap = leaser.snapshot()
            assert len(snap["free"]) == 2
            assert rs.scale_to(3) == 3
            assert leaser.snapshot()["free"] == []
            # Scale-down drains newest-first and returns the chips.
            assert rs.scale_to(1, reason="test") == 1
            assert len(leaser.snapshot()["free"]) == 2
            assert rs.status()["replicas"][0]["replica"] == 0
        finally:
            rs.close()
        # close() releases the last lease too.
        assert len(leaser.snapshot()["free"]) == 3

    def test_scale_clamps_to_bounds(self):
        rs, _ = _stub_set(min_replicas=1, max_replicas=2)
        try:
            assert rs.scale_to(5) == 2
            assert rs.scale_to(0) == 1
        finally:
            rs.close()

    def test_replica_devices_recorded_in_status(self):
        rs, _ = _stub_set()
        try:
            rs.scale_to(2)
            devices = {
                r["device"] for r in rs.status()["replicas"]
            }
            assert len(devices) == 2
            assert all(d.startswith("tpu:") for d in devices)
            assert set(rs.placements()) == {0, 1}
        finally:
            rs.close()

    def test_drain_before_unload_drops_no_inflight_predicts(self):
        """Scale-down mid-traffic: every already-submitted predict
        completes (flush-on-close) or re-routes (BatcherClosed →
        next candidate); none surfaces an error."""
        def dispatch(padded):
            time.sleep(0.002 * padded.shape[0])
            return padded * 3.0

        rs, leaser = _stub_set(dispatch=dispatch, max_batch=4)
        errors: list = []
        oks: list = []
        try:
            rs.scale_to(2)

            def client(i):
                row = np.full((1, 2), float(i), np.float32)
                try:
                    out, _replica = rs.submit(row)
                    np.testing.assert_array_equal(out, row * 3.0)
                    oks.append(i)
                except Exception as exc:  # noqa: BLE001 — the assert
                    errors.append(exc)

            threads = [
                threading.Thread(target=client, args=(i,))
                for i in range(24)
            ]
            for t in threads:
                t.start()
            rs.scale_to(1, reason="drain-test")
            for t in threads:
                t.join(20)
            assert not errors
            assert len(oks) == 24
            assert len(leaser.snapshot()["free"]) == 2
        finally:
            rs.close()

    def test_429_only_when_every_replica_saturated(self):
        release = threading.Event()

        def dispatch(padded):
            release.wait(15)
            return padded

        rs, _ = _stub_set(
            dispatch=dispatch, max_batch=1, max_queue=1, flush_ms=0.0
        )
        threads = []
        try:
            rs.scale_to(2)
            row = np.zeros((1, 1), np.float32)
            errors: list = []

            def submit():
                try:
                    rs.submit(row)
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)

            # Two waves: first pair lands in the (blocked) workers,
            # second pair fills both 1-row queues.
            for _ in range(2):
                pair = [
                    threading.Thread(target=submit, daemon=True)
                    for _ in range(2)
                ]
                threads += pair
                for t in pair:
                    t.start()
                time.sleep(0.3)
            # Every replica saturated now — THIS one must shed.
            with pytest.raises(QueueFull):
                rs.submit(row)
        finally:
            release.set()
            for t in threads:
                t.join(10)
            rs.close()
        assert not errors  # the queued/blocked requests all completed


# -- autoscaler --------------------------------------------------------------


def _fleet_cfg(**kw):
    kw.setdefault("interval_s", 0.0)  # manual tick()
    kw.setdefault("up_queue_frac", 0.1)
    kw.setdefault("up_ticks", 2)
    kw.setdefault("down_ticks", 2)
    return FleetConfig(**kw)


class TestAutoscaler:
    def test_scale_up_on_sustained_queue_depth_under_fault_delay(self):
        """The ISSUE drill, unit-sized: a fault-plane delay holds the
        replica's dispatch busy, sustained load builds queue depth,
        and the sustain-count controller scales 1→2 — at exactly the
        configured tick, because every signal is deterministic."""
        def dispatch(padded):
            faults.hit("serve.apply")  # the real dispatch's probe
            return padded

        rs, leaser = _stub_set(
            dispatch=dispatch, max_batch=2, max_queue=32, flush_ms=0.5
        )
        scaler = Autoscaler(_StubManager(rs), _fleet_cfg())
        stop = threading.Event()
        threads = []
        try:
            faults.arm("serve.apply", "delay", delay_ms=40)
            row = np.zeros((1, 1), np.float32)

            def load():
                while not stop.is_set():
                    try:
                        rs.submit(row)
                    except QueueFull:
                        time.sleep(0.01)

            threads = [
                threading.Thread(target=load, daemon=True)
                for _ in range(8)
            ]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 15
            while rs.size < 2 and time.monotonic() < deadline:
                scaler.tick()
                time.sleep(0.05)
            assert rs.size >= 2
            decisions = scaler.status()["decisions"]
            assert decisions and decisions[0]["signal"] in (
                "queue", "shed"
            )
            assert len(leaser.snapshot()["free"]) <= 1
            assert faults.triggers("serve.apply") > 0

            # Load subsides (and the delay disarms): empty-queue ticks
            # scale back down to min and the chip lease is RELEASED.
            stop.set()
            for t in threads:
                t.join(10)
            faults.disarm("serve.apply")
            deadline = time.monotonic() + 15
            while rs.size > 1 and time.monotonic() < deadline:
                scaler.tick()
                time.sleep(0.02)
            assert rs.size == 1
            assert len(leaser.snapshot()["free"]) == 2
        finally:
            stop.set()
            for t in threads:
                t.join(10)
            rs.close()

    def test_shed_requests_count_as_up_signal(self):
        release = threading.Event()

        def dispatch(padded):
            release.wait(10)
            return padded

        rs, _ = _stub_set(
            dispatch=dispatch, max_batch=1, max_queue=1, flush_ms=0.0
        )
        scaler = Autoscaler(_StubManager(rs), _fleet_cfg())
        threads = []
        try:
            row = np.zeros((1, 1), np.float32)
            for _ in range(2):
                t = threading.Thread(
                    target=lambda: rs.submit(row), daemon=True
                )
                t.start()
                threads.append(t)
                time.sleep(0.2)
            scaler.tick()  # baseline: records current overflow count
            with pytest.raises(QueueFull):
                rs.submit(row)  # the shed 429
            for _ in range(2):
                scaler.tick()
            assert rs.size == 2
            assert scaler.status()["decisions"][0]["signal"] == "shed"
        finally:
            release.set()
            for t in threads:
                t.join(10)
            rs.close()

    def test_steady_load_does_not_scale_down(self):
        """Regression: 'idle' means NO traffic since the last tick,
        not an instantaneously empty queue — a loaded fleet whose
        batchers are flushed at sample time must hold its size, then
        drain only after genuinely traffic-free ticks."""
        rs, leaser = _stub_set(flush_ms=0.0)
        scaler = Autoscaler(_StubManager(rs), _fleet_cfg())
        try:
            rs.scale_to(2)
            row = np.zeros((1, 1), np.float32)
            # Traffic on every tick; queue samples 0 throughout (the
            # zero-deadline batcher flushes synchronously).
            for _ in range(3 * scaler.cfg.down_ticks):
                rs.submit(row)
                assert rs.signals()["queue_depth"] == 0
                scaler.tick()
            assert rs.size == 2  # never scaled down under load
            # Genuinely idle ticks DO drain it.
            for _ in range(scaler.cfg.down_ticks):
                scaler.tick()
            assert rs.size == 1
            assert len(leaser.snapshot()["free"]) == 2
        finally:
            rs.close()

    def test_lease_timeout_skips_scale_up_and_survives(self):
        """A saturated chip pool must not kill the control loop: the
        scale-up is skipped and the streak re-armed for next tick."""
        release = threading.Event()

        def dispatch(padded):
            release.wait(10)
            return padded

        rs, leaser = _stub_set(
            n_devices=1, dispatch=dispatch,
            max_batch=1, max_queue=1, flush_ms=0.0,
        )
        rs.lease_timeout_s = 0.05
        scaler = Autoscaler(_StubManager(rs), _fleet_cfg())
        threads = []
        try:
            row = np.zeros((1, 1), np.float32)
            for _ in range(2):
                t = threading.Thread(
                    target=lambda: rs.submit(row), daemon=True
                )
                t.start()
                threads.append(t)
                time.sleep(0.2)
            for _ in range(4):
                scaler.tick()
            assert rs.size == 1  # no second chip to scale onto
            assert scaler.status()["decisions"] == []
            # The streak stays armed so recovery is immediate.
            assert scaler.status()["streaks"]["m"]["up"] >= 2
        finally:
            release.set()
            for t in threads:
                t.join(10)
            rs.close()


class TestManagerLeaseExhaustion:
    def _manager(self, leaser, fleet_cfg):
        """FleetManager over a stub service — real manager/replica
        code, no model registry."""
        import types

        from learningorchestra_tpu.serve.fleet import FleetManager

        service = types.SimpleNamespace(
            ctx=types.SimpleNamespace(
                leaser=leaser,
                config=types.SimpleNamespace(fleet=fleet_cfg),
            ),
            cfg=ServeConfig(max_batch=4, max_queue=16, flush_ms=0.5),
            registry=types.SimpleNamespace(peek=lambda name: None),
            replica_dispatch_factory=lambda name: (
                lambda replica: (lambda padded: padded)
            ),
            pop_single_path=lambda name: None,
            _drop_batcher=lambda name: None,
        )
        return FleetManager(service)

    def test_failed_ensure_does_not_register_a_dead_set(self):
        """Regression: a LeaseTimeout during ensure()'s initial scale
        must NOT leave a zero-replica set registered (every later
        predict would shed 429 forever with nothing retrying the
        lease) — the next request re-attempts and succeeds once a
        chip frees up."""
        from learningorchestra_tpu.jobs.leases import LeaseTimeout

        leaser = DeviceLeaser(["tpu:0"])
        cfg = _fleet_cfg(max_replicas=3, lease_timeout_s=0.05)
        mgr = self._manager(leaser, cfg)
        mgr._bounds["m"] = (1, 3)
        hog = leaser.acquire(1, label="training-hog")
        try:
            with pytest.raises(LeaseTimeout):
                mgr.routing_set("m")
            assert mgr.sets_snapshot() == []  # nothing dead registered
        finally:
            hog.release()
        # Placement-failure cooldown: routed predicts go single-path
        # (None) instead of each paying a fresh lease wait...
        assert mgr.routing_set("m") is None
        time.sleep(cfg.lease_timeout_s + 0.05)
        # ...and after it expires the next request re-attempts.
        rs = mgr.routing_set("m")
        assert rs is not None and rs.size == 1
        out, _replica = rs.submit(np.ones((1, 2), np.float32))
        assert out.shape == (1, 2)
        mgr.close()

    def test_autoscaler_heals_below_min_without_sustain_window(self):
        rs, _ = _stub_set(min_replicas=1, max_replicas=3)
        rs.min_replicas = 2  # simulate a partially-placed ensure
        scaler = Autoscaler(_StubManager(rs), _fleet_cfg())
        try:
            decisions = scaler.tick()
            assert rs.size == 2
            assert decisions and decisions[0]["signal"] == "min"
            # Ticks count control-loop PASSES, not per-model visits.
            scaler.tick()
            assert scaler.status()["ticks"] == 2
        finally:
            rs.close()


class TestCounterContinuity:
    def test_cumulative_counters_survive_scale_down(self):
        """Regression: a drained replica's lifetime counters fold into
        the set's retired totals — cumulative requests must stay
        monotonic across scale cycles (negative per-tick deltas would
        corrupt the autoscaler's served/shed signals and move
        counter-typed Prometheus series backwards)."""
        rs, _ = _stub_set(flush_ms=0.0)
        try:
            rs.scale_to(3)
            row = np.ones((1, 2), np.float32)
            for _ in range(60):
                rs.submit(row)
            assert rs.signals()["requests"] == 60
            rs.scale_to(1)
            assert rs.signals()["requests"] == 60  # not regressed
            merged = rs.merged_stats()
            assert merged["requests"] == 60
            assert merged["rows"] == 60
        finally:
            rs.close()


class TestFleetEnvValidation:
    def test_bad_fleet_bounds_fail_at_boot(self, monkeypatch):
        from learningorchestra_tpu.config import Config

        monkeypatch.setenv("LO_TPU_FLEET_MIN", "0")
        monkeypatch.setenv("LO_TPU_FLEET_MAX", "2")
        with pytest.raises(ValueError, match="LO_TPU_FLEET_MIN"):
            Config.from_env()
        monkeypatch.setenv("LO_TPU_FLEET_MIN", "3")
        with pytest.raises(ValueError, match="LO_TPU_FLEET_MIN"):
            Config.from_env()
        monkeypatch.setenv("LO_TPU_FLEET_MIN", "1")
        assert Config.from_env().fleet.max_replicas == 2


class TestScaleBoundsShrink:
    def test_scale_re_clamps_against_live_bounds(self):
        """Regression: scale_to re-reads the bounds every iteration, so
        a shrink between clamp and add converges instead of spinning
        the lease pool under the scale lock."""
        rs, leaser = _stub_set(min_replicas=1, max_replicas=3)
        try:
            assert rs.scale_to(3) == 3
            rs.set_bounds(1, 2)
            # Asking for MORE than the (new) max settles at max.
            assert rs.scale_to(3) == 2
            assert len(leaser.snapshot()["free"]) == 1
        finally:
            rs.close()


# -- REST surface (the acceptance drill) -------------------------------------


def _install_trained_model(server, name):
    """Fabricate a finished train artifact holding a fitted estimator
    (same helper as test_serve.py — serving is what's under test)."""
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


@pytest.fixture(scope="module")
def fleet_api(tmp_path_factory):
    from learningorchestra_tpu.api import APIServer
    from learningorchestra_tpu.config import Config

    tmp = tmp_path_factory.mktemp("fleet_api")
    cfg = Config()
    cfg.store.root = str(tmp / "store")
    cfg.store.volume_root = str(tmp / "volumes")
    cfg.serve.max_batch = 2
    cfg.serve.max_queue = 16
    cfg.serve.flush_ms = 1.0
    cfg.fleet.interval_s = 0.05
    cfg.fleet.up_queue_frac = 0.1
    cfg.fleet.up_ticks = 2
    cfg.fleet.down_ticks = 3
    cfg.fleet.lease_timeout_s = 2.0
    server = APIServer(cfg)
    # Inject a 3-chip pool BEFORE any fleet op: replica placement and
    # the release assertions run against exactly these devices.
    server.ctx.leaser = DeviceLeaser(["tpu:0", "tpu:1", "tpu:2"])
    port = server.start_background()
    base = f"http://127.0.0.1:{port}{PREFIX}"
    yield server, base
    server.shutdown()


class TestFleetRest:
    def test_replicas_404_without_a_set(self, fleet_api):
        _, base = fleet_api
        resp = requests.get(f"{base}/serve/none_such/replicas")
        assert resp.status_code == 404

    def test_configure_unknown_model_404(self, fleet_api):
        _, base = fleet_api
        resp = requests.post(
            f"{base}/serve/ghost/replicas", json={"count": 2}
        )
        assert resp.status_code == 404

    def test_bad_bounds_406(self, fleet_api):
        server, base = fleet_api
        _install_trained_model(server, "flt_bounds")
        resp = requests.post(
            f"{base}/serve/flt_bounds/replicas",
            json={"min": 3, "max": 1},
        )
        assert resp.status_code == 406
        resp = requests.post(
            f"{base}/serve/flt_bounds/replicas", json={}
        )
        assert resp.status_code == 406

    def test_manual_scale_roundtrip(self, fleet_api):
        server, base = fleet_api
        _, x = _install_trained_model(server, "flt_manual")
        # One classic-path predict first: its counters must CARRY into
        # the fleet (per-model serving counters stay monotonic across
        # the plane migration).
        resp = requests.post(
            f"{base}/serve/flt_manual/predict",
            json={"instances": x[:1].tolist()},
        )
        assert resp.status_code == 200 and "replica" not in resp.json()
        # min=2 so the (running) autoscaler can't drain the set while
        # the assertions below are still reading it.
        resp = requests.post(
            f"{base}/serve/flt_manual/replicas",
            json={"min": 2, "max": 3},
        )
        assert resp.status_code == 200, resp.text
        body = resp.json()
        assert body["size"] == 2
        assert {r["device"] for r in body["replicas"]} <= {
            "tpu:0", "tpu:1", "tpu:2"
        }
        # Predict routes through the fleet and attributes its replica.
        resp = requests.post(
            f"{base}/serve/flt_manual/predict",
            json={"instances": x[:3].tolist()},
        )
        assert resp.status_code == 200, resp.text
        assert resp.json()["replica"] in (0, 1)
        assert resp.json()["device"].startswith("tpu:")
        # 1 classic + 1 fleet predict: the migration carried the
        # classic batcher's counters into the set.
        stats = server.serving.stats()["models"]["flt_manual"]
        assert stats["requests"] >= 2, stats
        # Residency listing carries the placement map.
        listed = requests.get(f"{base}/serve").json()
        entry = next(
            m for m in listed["models"] if m["name"] == "flt_manual"
        )
        assert len(entry["replicaDevices"]) == 2
        # Per-replica series on the Prometheus exposition.
        prom = requests.get(f"{base}/metrics.prom", timeout=30).text
        assert "lo_serving_replicas{" in prom
        assert 'lo_serving_replica_queue_depth{' in prom
        assert 'replica="0"' in prom
        # While fleet-engaged, the single-path batcher cannot be
        # resurrected by a racing predict — it refuses retriably.
        from learningorchestra_tpu.serve.batcher import BatcherClosed

        with pytest.raises(BatcherClosed, match="fleet"):
            server.serving._batcher_for("flt_manual")
        # Back down to one replica; the extra chip returns to the pool.
        resp = requests.post(
            f"{base}/serve/flt_manual/replicas",
            json={"min": 1, "max": 3, "count": 1},
        )
        assert resp.json()["size"] == 1
        requests.post(f"{base}/serve/flt_manual/unload", json={})
        # Unload forgets the model: classic path usable again.
        assert not server.serving.fleet.engaged("flt_manual")

    def test_autoscale_drill_end_to_end(self, fleet_api):
        """The acceptance drill: min=1,max=3; a fault-plane delay pins
        dispatch; sustained REST load scales the model to >= 2
        replicas; new traffic reaches the fresh replica; load stops,
        the fleet drains back to 1 and its chip leases are released —
        all observed through the REST surface."""
        server, base = fleet_api
        _, x = _install_trained_model(server, "flt_drill")
        resp = requests.post(
            f"{base}/serve/flt_drill/replicas",
            json={"min": 1, "max": 3},
        )
        assert resp.status_code == 200, resp.text
        assert resp.json()["size"] == 1
        held0 = 3 - len(server.ctx.leaser.snapshot()["free"])
        assert held0 == 1

        # Seeded chaos: every coalesced dispatch sleeps 60 ms — the
        # "replica 0 is busy" pin (deterministic: rate 1).
        resp = requests.post(
            f"{base}/faults/serve.apply",
            json={"mode": "delay", "delayMs": 60},
        )
        assert resp.status_code in (200, 201), resp.text

        stop = threading.Event()
        errors: list = []

        def load():
            while not stop.is_set():
                try:
                    r = requests.post(
                        f"{base}/serve/flt_drill/predict",
                        json={"instances": x[:1].tolist()},
                        timeout=30,
                    )
                    if r.status_code not in (200, 429):
                        errors.append((r.status_code, r.text))
                except requests.RequestException as exc:
                    errors.append(exc)

        threads = [
            threading.Thread(target=load, daemon=True)
            for _ in range(8)
        ]
        try:
            for t in threads:
                t.start()
            deadline = time.monotonic() + 20
            size = 1
            while size < 2 and time.monotonic() < deadline:
                time.sleep(0.1)
                size = requests.get(
                    f"{base}/serve/flt_drill/replicas"
                ).json()["size"]
            assert size >= 2, "fleet never scaled up under load"

            # Fresh replica takes NEW traffic (replica 0 stays pinned
            # behind its queue).
            deadline = time.monotonic() + 15
            fresh_served = False
            while not fresh_served and time.monotonic() < deadline:
                time.sleep(0.1)
                status = requests.get(
                    f"{base}/serve/flt_drill/replicas"
                ).json()
                fresh_served = any(
                    r["requests"] > 0 for r in status["replicas"]
                    if r["replica"] != 0
                )
            assert fresh_served, "no traffic reached the new replica"
        finally:
            stop.set()
            for t in threads:
                t.join(15)
        assert not errors, errors[:3]

        # Chaos off, load gone: the autoscaler drains back to min and
        # returns the extra chips to the pool.
        requests.delete(f"{base}/faults")
        deadline = time.monotonic() + 25
        size = 99
        while size > 1 and time.monotonic() < deadline:
            time.sleep(0.1)
            size = requests.get(
                f"{base}/serve/flt_drill/replicas"
            ).json()["size"]
        assert size == 1, "fleet never scaled back down"
        assert len(server.ctx.leaser.snapshot()["free"]) == 2

        # The whole story is on the autoscaler status surface.
        fleet = requests.get(f"{base}/serve/fleet").json()
        directions = {
            (d["model"], d["to"] > d["from"])
            for d in fleet["autoscaler"]["decisions"]
        }
        assert ("flt_drill", True) in directions
        assert ("flt_drill", False) in directions
        requests.post(f"{base}/serve/flt_drill/unload", json={})

    def test_dissolve_returns_model_to_single_path(self, fleet_api):
        """DELETE /serve/<m>/replicas: drain + release chips + back to
        classic serving WITHOUT unloading — the 'want my chips back'
        remediation."""
        server, base = fleet_api
        _, x = _install_trained_model(server, "flt_dissolve")
        free_before = len(server.ctx.leaser.snapshot()["free"])
        resp = requests.post(
            f"{base}/serve/flt_dissolve/replicas",
            json={"min": 2, "max": 3},
        )
        assert resp.status_code == 200 and resp.json()["size"] == 2
        assert len(
            server.ctx.leaser.snapshot()["free"]
        ) == free_before - 2

        resp = requests.delete(f"{base}/serve/flt_dissolve/replicas")
        assert resp.status_code == 200, resp.text
        assert resp.json()["dissolved"] is True
        assert len(
            server.ctx.leaser.snapshot()["free"]
        ) == free_before
        # Model still loaded; predict serves on the classic path.
        resp = requests.post(
            f"{base}/serve/flt_dissolve/predict",
            json={"instances": x[:1].tolist()},
        )
        assert resp.status_code == 200, resp.text
        assert "replica" not in resp.json()
        assert requests.get(
            f"{base}/serve/flt_dissolve/replicas"
        ).status_code == 404
        # Idempotent.
        assert requests.delete(
            f"{base}/serve/flt_dissolve/replicas"
        ).json()["dissolved"] is False

    def test_failed_cutover_keeps_single_path_serving(self, fleet_api):
        """Regression: a fleet cutover that can't place its first
        replica (chip pool exhausted → 503) must NOT retire the
        model's working single-path batcher — predicts degrade to it
        instead of going dark, and once chips free up the cutover
        carries the accumulated counters into the set."""
        server, base = fleet_api
        _, x = _install_trained_model(server, "flt_degrade")
        resp = requests.post(
            f"{base}/serve/flt_degrade/predict",
            json={"instances": x[:1].tolist()},
        )
        assert resp.status_code == 200 and "replica" not in resp.json()

        leaser = server.ctx.leaser
        hogs = [
            leaser.acquire(1, label=f"hog{i}", timeout=1)
            for i in range(len(leaser.snapshot()["free"]))
        ]
        try:
            resp = requests.post(
                f"{base}/serve/flt_degrade/replicas",
                json={"min": 1, "max": 2},
            )
            assert resp.status_code == 503, resp.text  # LeaseTimeout
            # Still serving — on the un-retired single-path batcher.
            resp = requests.post(
                f"{base}/serve/flt_degrade/predict",
                json={"instances": x[:1].tolist()},
            )
            assert resp.status_code == 200, resp.text
            assert "replica" not in resp.json()
        finally:
            for hog in hogs:
                hog.release()
        # Chips free: the cutover completes and the counters carried.
        resp = requests.post(
            f"{base}/serve/flt_degrade/replicas", json={"count": 1}
        )
        assert resp.status_code == 200, resp.text
        stats = server.serving.stats()["models"]["flt_degrade"]
        assert stats["requests"] >= 2, stats
        requests.delete(f"{base}/serve/flt_degrade/replicas")

    def test_single_replica_path_unchanged(self, fleet_api):
        """A model WITHOUT fleet bounds stays on the classic
        single-batcher path: no replica key in the response, no
        replica set, no leases held."""
        server, base = fleet_api
        _, x = _install_trained_model(server, "flt_classic")
        resp = requests.post(
            f"{base}/serve/flt_classic/predict",
            json={"instances": x[:2].tolist()},
        )
        assert resp.status_code == 200, resp.text
        assert "replica" not in resp.json()
        assert requests.get(
            f"{base}/serve/flt_classic/replicas"
        ).status_code == 404
