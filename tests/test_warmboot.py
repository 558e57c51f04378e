"""Durable warm start (train/aot_store.py + the boot/replica pre-warm
paths): AOT round-trip through a fresh compile cache, paranoid blob
validation (checksum/version/device-signature mismatches degrade to a
live re-trace, never a crash), manifest prune bounds, the subprocess
restart drill (a fresh process with LO_TPU_AOT_PREWARM=1 serves its
first dispatch with ZERO compile spans), replica warm-before-routable,
and the program-fingerprint warm-start hints.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from learningorchestra_tpu.train import aot_store
from learningorchestra_tpu.train import compile_cache as cc


@pytest.fixture(autouse=True)
def _clean_store():
    """Never leak an installed singleton store across tests."""
    yield
    aot_store.reset_store()


def _seed_store(tmp_path, key="warmboot-test", label="wb"):
    """A store holding one REAL serialized executable for ``a * 2``."""
    import jax
    import jax.numpy as jnp

    store = aot_store.reset_store(
        root=str(tmp_path / "aot"), max_entries=8, max_bytes=1 << 30
    )
    fp = cc.fingerprint("warmboot", key)
    compiled = jax.jit(lambda a: a * 2.0).lower(
        jax.ShapeDtypeStruct((4,), jnp.float32)
    ).compile()
    store.offer(fp, aot_store.serialize(compiled), label=label)
    return store, fp


class TestRoundTrip:
    def test_restore_dispatches_without_rebuild_or_compile_span(
        self, tmp_path
    ):
        """The tentpole contract: a fresh cache resolves a persisted
        program from disk — builder never called, no compile span, no
        traceTimeS — and the restored executable computes."""
        import jax

        from learningorchestra_tpu.obs import tracing

        store, fp = _seed_store(tmp_path)
        cache = cc.CompiledProgramCache(max_entries=8)
        built = []

        def builder():
            built.append(1)
            return jax.jit(lambda a: a * 2.0)

        trace = tracing.new_trace("warmboot-round-trip")
        assert trace is not None  # tracing defaults on
        with tracing.activate(trace):
            apply = cache.get_or_build(fp, builder, label="wb")
            out = np.asarray(apply(np.ones(4, dtype=np.float32)))
        assert out.tolist() == [2.0, 2.0, 2.0, 2.0]
        assert built == []
        assert store.hits == 1
        compile_spans = [
            s for s in trace.to_doc()["spans"] if s["name"] == "compile"
        ]
        assert compile_spans == []
        stats = cache.stats()
        # An AOT restore is a cache MISS (the entry wasn't resident)
        # but costs zero trace time — the number the probe banks.
        assert stats["misses"] == 1
        assert stats["traceTimeS"] == 0.0
        # Bytes come MEASURED from the manifest, not the flat estimate.
        assert stats["measuredEntries"] == 1
        # Second lookup is a plain hit on the restored entry.
        assert cache.get_or_build(fp, builder, label="wb") is apply
        assert built == []

    def test_call_time_failure_rebuilds_live_once(self, tmp_path):
        """A restored executable pins its traced shapes: an argument
        it never saw fails at CALL time — the guard rebuilds through
        the builder once, swaps it in, and the request succeeds."""
        import jax

        store, fp = _seed_store(tmp_path)
        cache = cc.CompiledProgramCache(max_entries=8)
        built = []

        def builder():
            built.append(1)
            return jax.jit(lambda a: a * 2.0)

        apply = cache.get_or_build(fp, builder, label="wb")
        # (8,) was never traced — the restored Compiled rejects it.
        out = np.asarray(apply(np.ones(8, dtype=np.float32)))
        assert out.tolist() == [2.0] * 8
        assert built == [1]
        assert store.call_fallbacks == 1
        # Permanently swapped: the next odd shape re-traces through
        # the live jit wrapper, no second fallback dance.
        out2 = np.asarray(apply(np.ones(2, dtype=np.float32)))
        assert out2.tolist() == [2.0, 2.0]
        assert built == [1]


class TestBlobValidation:
    def _tamper(self, store, fp, mutate):
        """Rewrite the blob file through ``mutate(header, blob)``."""
        path = store._blob_path(fp)
        with open(path, "rb") as fh:
            magic = fh.read(7)
            header = json.loads(fh.readline().decode("utf-8"))
            blob = fh.read()
        magic, header, blob = mutate(magic, header, blob)
        with open(path, "wb") as fh:
            fh.write(magic)
            fh.write(json.dumps(header).encode("utf-8"))
            fh.write(b"\n")
            fh.write(blob)

    @pytest.mark.parametrize("mutate,what", [
        (lambda m, h, b: (m, h, b + b"corrupt"), "checksum"),
        (lambda m, h, b: (m, {**h, "version": 99}, b), "version"),
        (lambda m, h, b: (m, {**h, "deviceSig": [["gone", 0]]}, b),
         "device signature"),
        (lambda m, h, b: (b"NOTAOT\n", h, b), "magic"),
        (lambda m, h, b: (m, {**h, "key": "other"}, b), "key"),
    ])
    def test_mismatch_falls_back_cleanly(self, tmp_path, mutate, what):
        """Every validation failure returns None (live re-trace),
        counts a loadError, and deletes the bad blob so the error
        pays once — never an exception out of load()."""
        store, fp = _seed_store(tmp_path)
        self._tamper(store, fp, mutate)
        assert store.load(fp) is None, what
        assert store.load_errors == 1
        assert not os.path.exists(store._blob_path(fp))
        # And the compile-cache path degrades to the live build.
        import jax

        cache = cc.CompiledProgramCache(max_entries=8)
        built = []

        def builder():
            built.append(1)
            return jax.jit(lambda a: a * 2.0)

        apply = cache.get_or_build(fp, builder, label="wb")
        assert built == [1]
        out = np.asarray(apply(np.ones(4, dtype=np.float32)))
        assert out.tolist() == [2.0] * 4

    def test_vanished_blob_is_miss_and_drops_manifest_row(
        self, tmp_path
    ):
        store, fp = _seed_store(tmp_path)
        os.unlink(store._blob_path(fp))
        assert store.load(fp) is None
        assert store.misses == 1
        assert store.load_errors == 0
        assert not store.contains(fp)


class TestManifestPrune:
    def _store(self, tmp_path, **kw):
        return aot_store.AOTExecutableStore(
            str(tmp_path / "aot"), **kw
        )

    def test_entry_cap_evicts_coldest_never_just_stored(self, tmp_path):
        store = self._store(tmp_path, max_entries=2, max_bytes=1 << 30)
        store.offer("k1", ("p1",))
        store.offer("k2", ("p2",))
        store.offer("k2", ("p2",))  # heat k2
        store.offer("k3", ("p3",))  # over cap: k1 (coldest) evicts
        assert store.evictions == 1
        assert not store.contains("k1")
        assert store.contains("k2") and store.contains("k3")
        assert not os.path.exists(store._blob_path("k1"))

    def test_byte_cap_bounds_the_store(self, tmp_path):
        store = self._store(tmp_path, max_entries=64, max_bytes=2048)
        for i in range(4):
            store.offer(f"k{i}", ("x" * 800,))
        stats = store.stats()
        assert stats["persistedBytes"] <= 2048
        assert stats["persistedEntries"] < 4
        assert store.evictions > 0

    def test_manifest_survives_reopen(self, tmp_path):
        store = self._store(tmp_path, max_entries=8, max_bytes=1 << 30)
        store.offer("k1", ("p1",), label="L1")
        reopened = self._store(
            tmp_path, max_entries=8, max_bytes=1 << 30
        )
        entries = reopened.manifest_entries()
        assert [e["key"] for e in entries] == ["k1"]
        assert entries[0]["label"] == "L1"


# Shared spec for both halves of the restart drill: the program
# fingerprint must be identical across the two processes.
_DRILL_COMMON = """
import numpy as np, jax, jax.numpy as jnp
from learningorchestra_tpu.train import compile_cache as cc
from learningorchestra_tpu.models.mlp import MLPClassifier

est = MLPClassifier(hidden_layer_sizes=[8], num_classes=2, seed=0)
est.compute_dtype = "float32"
est._init_params(jnp.asarray(np.ones((1, 4), np.float32)))
x = np.ones((8, 4), np.float32)
key = cc.apply_program_key(est.module, rows=8)
"""

_DRILL_PHASE1 = _DRILL_COMMON + """
from learningorchestra_tpu.train import aot_store
from learningorchestra_tpu.train.neural import _probe_program_cost

def builder():
    jitted = jax.jit(est.module.apply)
    _probe_program_cost(
        key, "drill:b8", jitted, lambda: (est.params, x)
    )
    return jitted

apply = cc.get_cache().get_or_build(key, builder, label="drill:b8")
jax.block_until_ready(apply(est.params, jnp.asarray(x)))
store = aot_store.get_store()
assert store is not None, "store not enabled from env"
assert store.contains(key), "deep cost probe did not persist"
print("PHASE1_OK")
"""

_DRILL_PHASE2 = _DRILL_COMMON + """
from learningorchestra_tpu.obs import tracing
from learningorchestra_tpu.services.context import ServiceContext
from learningorchestra_tpu.train import aot_store

ctx = ServiceContext()
thread = ctx._aot_prewarm_thread
assert thread is not None, "boot pre-warm did not start"
thread.join(60)
assert not thread.is_alive(), "pre-warm wedged"
cache = cc.get_cache()
# EVERY manifest key must be resident before any dispatch.
for rec in aot_store.get_store().manifest_entries():
    assert cache.contains(rec["key"]), rec
assert cache.contains(key), "drill key not pre-warmed"

def builder():
    raise AssertionError("builder called: pre-warm did not stick")

trace = tracing.new_trace("restart-drill")
assert trace is not None
with tracing.activate(trace):
    apply = cache.get_or_build(key, builder, label="drill:b8")
    out = jax.block_until_ready(apply(est.params, jnp.asarray(x)))
compile_spans = [
    s for s in trace.to_doc()["spans"] if s["name"] == "compile"
]
assert compile_spans == [], compile_spans
assert aot_store.get_store().hits >= 1
ctx.close()
print("PHASE2_OK")
"""


class TestRestartDrill:
    def test_fresh_process_prewarms_with_zero_compile_spans(
        self, tmp_path
    ):
        """The acceptance drill: process 1 trains (the deep cost probe
        persists the executable); process 2 — a genuinely fresh
        interpreter — boot-pre-warms from the manifest and serves its
        first dispatch for every manifest key with ZERO compile
        spans."""
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "LO_TPU_AOT_ENABLED": "1",
            "LO_TPU_AOT_DIR": str(tmp_path / "aot"),
            "LO_TPU_AOT_PREWARM": "1",
            "LO_TPU_STORE_ROOT": str(tmp_path / "store"),
            "LO_TPU_VOLUME_ROOT": str(tmp_path / "volumes"),
        }
        for phase, script in (
            ("PHASE1_OK", _DRILL_PHASE1),
            ("PHASE2_OK", _DRILL_PHASE2),
        ):
            proc = subprocess.run(
                [sys.executable, "-c", script],
                env=env, capture_output=True, text=True, timeout=240,
            )
            assert proc.returncode == 0, (
                f"{phase} half failed:\n{proc.stdout}\n{proc.stderr}"
            )
            assert phase in proc.stdout


class TestReplicaWarmup:
    def _set(self, warmup, max_replicas=2):
        from learningorchestra_tpu.config import ServeConfig
        from learningorchestra_tpu.jobs.leases import DeviceLeaser
        from learningorchestra_tpu.serve.fleet import ReplicaSet

        leaser = DeviceLeaser(["tpu:0", "tpu:1"])
        cfg = ServeConfig(max_batch=8, max_queue=64, flush_ms=1.0)
        return ReplicaSet(
            "m", cfg, leaser, lambda replica: (lambda padded: padded),
            min_replicas=1, max_replicas=max_replicas, warmup=warmup,
        )

    def test_not_routable_until_warmed(self):
        """The warm-up callback runs BEFORE the replica joins the
        routable list — observed sizes prove the router can never
        pick a cold replica."""
        sizes_at_warmup = []

        def warmup(replica):
            sizes_at_warmup.append((replica.idx, None))

        rs = self._set(warmup)
        # Capture the routable size as seen from inside the warm-up.
        sizes_at_warmup.clear()

        def warmup2(replica):
            sizes_at_warmup.append((replica.idx, rs.size))

        rs._warmup = warmup2
        rs.scale_to(1, reason="test")
        assert sizes_at_warmup == [(0, 0)]  # warmed while unroutable
        assert rs.size == 1
        status = rs.status()
        assert status["replicas"][0]["warmed"] is True
        rs.scale_to(2, reason="test")
        assert sizes_at_warmup == [(0, 0), (1, 1)]
        assert all(r["warmed"] for r in rs.status()["replicas"])
        rs.close()

    def test_failed_warmup_serves_cold_not_stranded(self):
        """Availability beats warmth: a warm-up crash logs, the
        replica joins the routable list with warmed=False, and
        requests still serve."""
        def warmup(replica):
            raise RuntimeError("device hiccup")

        rs = self._set(warmup)
        rs.scale_to(1, reason="test")
        assert rs.size == 1
        assert rs.status()["replicas"][0]["warmed"] is False
        out, replica = rs.submit(np.ones((1, 4), dtype=np.float32))
        assert out.shape == (1, 4)
        rs.close()

    def test_no_warmup_configured_stays_cold_flagged(self):
        rs = self._set(None)
        rs.scale_to(1, reason="test")
        assert rs.status()["replicas"][0]["warmed"] is False
        rs.close()


class TestWarmFingerprint:
    def test_excludes_non_trace_knobs_and_key_order(self):
        base = cc.warm_fingerprint(
            "models.mlp", "MLPClassifier", "fit",
            {"lr": 0.1, "epochs": 2},
        )
        assert base == cc.warm_fingerprint(
            "models.mlp", "MLPClassifier", "fit",
            {"epochs": 2, "lr": 0.1, "verbose": True,
             "description": "x", "monitoring_path": "/tmp/m"},
        )

    def test_trace_shaping_params_separate(self):
        a = cc.warm_fingerprint(
            "models.mlp", "MLPClassifier", "fit", {"lr": 0.1}
        )
        b = cc.warm_fingerprint(
            "models.mlp", "MLPClassifier", "fit", {"lr": 0.2}
        )
        c = cc.warm_fingerprint(
            "models.mlp", "MLPClassifier", "predict", {"lr": 0.1}
        )
        assert len({a, b, c}) == 3

    def test_executor_warm_key_is_the_fingerprint(self):
        from learningorchestra_tpu.services.executor import _warm_key

        meta = {"modulePath": "models.mlp", "class": "MLPClassifier"}
        params = {"epochs": 3, "verbose": True}
        assert _warm_key(meta, "fit", params) == cc.warm_fingerprint(
            "models.mlp", "MLPClassifier", "fit", params
        )
        # Coarse legacy tags are gone: distinct params, distinct hints.
        assert _warm_key(meta, "fit", {"epochs": 4}) != _warm_key(
            meta, "fit", {"epochs": 3}
        )
        assert _warm_key({}, "fit", params) is None
