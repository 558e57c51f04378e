"""MPMD pipeline dispatch tests (parallel/mpmd.py) on the 8-virtual-
device CPU platform.

The load-bearing properties, in the order the ISSUE pins them:

- **Loss parity.**  The host-dispatched 1F1B schedule over per-stage
  programs computes EXACTLY the training trajectory of the sequential
  layer stack (``sequential_loss`` — the repo's stated correctness
  oracle) driven by the same adam updates: MPMD is a dispatch strategy,
  not a model change.
- **Per-stage compile-cache goldens.**  One fit populates one cache
  entry per stage program (N stages → N independent ``stage:*:sN``
  entries); a FRESH same-architecture model re-fits with zero misses —
  the cross-job sharing the per-stage fingerprints exist for.
- **Stage-partitioned checkpoints.**  One orbax directory per
  partition + one top-level marker; an interrupted fit resumes every
  stage from the newest common step and continues on the uninterrupted
  trajectory.  The kill-9 drill runs the same contract through the
  journal's crash-recovery path in real subprocesses.
- **restoreBestWeights on pipeline fits** rolls the partitioned state
  back leaf-by-leaf (the old refusal is gone) and training continues.
- **Sharded fleet replicas.**  A replica holding a multi-chip lease
  places params GSPMD-sharded across its device list and serves
  through the normal fleet REST surface.
"""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # compile-heavy: full tier only

import jax
import jax.numpy as jnp
import optax
import requests

from learningorchestra_tpu.parallel import MeshSpec, build_mesh
from learningorchestra_tpu.parallel.mpmd import partition_names
from learningorchestra_tpu.parallel.pipeline import (
    PipelinedTransformer,
    sequential_loss,
)
from learningorchestra_tpu.train import compile_cache as cc

PREFIX = "/api/learningOrchestra/v1"


def _toy(n=32, t=8, vocab=64, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(1, vocab, (n, t), dtype=np.int32)
    y = (x.sum(axis=1) % 2).astype(np.int32)
    return x, y


def _mpmd(**kw):
    """A small MPMD transformer on a dp=4,pp=2 mesh; float32 so the
    trajectory comparisons below are bit-honest on CPU."""
    kwargs = dict(
        vocab_size=64, hidden_dim=16, num_layers=4, num_heads=2,
        mlp_dim=16, max_len=8, num_classes=2, seed=1,
        n_microbatches=4, compute_dtype="float32", schedule="mpmd",
        mesh=build_mesh(MeshSpec(dp=4, pp=2)),
    )
    kwargs.update(kw)
    return PipelinedTransformer(**kwargs)


# -- loss parity vs the sequential oracle -------------------------------------


class TestLossParity:
    def test_fit_matches_sequential_adam_trajectory(self):
        """3 epochs of MPMD fit == 3 epochs of sequential-stack fit:
        same init (shared recipe), same adam, one full batch per epoch
        so the reference loop is the oracle verbatim.  The recorded
        history loss is the PRE-update loss each epoch — compare
        epoch-for-epoch."""
        x, y = _toy(n=32)
        model = _mpmd()
        model.fit(x, y, epochs=3, batch_size=32, shuffle=False)
        assert len(model.history["loss"]) == 3

        # Reference: a gpipe-schedule instance shares the init recipe
        # (same seed → identical stacked params) but never builds its
        # pipeline — we drive sequential_loss + adam by hand.
        ref = _mpmd(schedule="gpipe")
        ref._init_params(jnp.asarray(x[:1]))
        seq = sequential_loss(
            ref._embed.apply, ref._stage.apply, ref._head.apply,
            ref._loss_fn, n_stages=ref.pp,
        )
        opt = optax.adam(ref.learning_rate)

        @jax.jit
        def step(ps, os_, xb, yb, mb):
            (loss, _metrics), grads = jax.value_and_grad(
                lambda p: seq(*p, xb, yb, mb), has_aux=True
            )(ps)
            updates, os_ = opt.update(grads, os_, ps)
            return optax.apply_updates(ps, updates), os_, loss

        params, opt_state = ref.params, ref.opt_state
        xb, yb = jnp.asarray(x), jnp.asarray(y)
        mb = jnp.ones(len(x), jnp.float32)
        ref_losses = []
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, xb, yb,
                                           mb)
            ref_losses.append(float(loss))

        np.testing.assert_allclose(
            model.history["loss"], ref_losses, rtol=2e-6, atol=1e-7
        )

    def test_predict_matches_sequential_forward(self):
        """The MPMD stage-hopping inference path == one sequential
        forward over the same (host-gathered) weights."""
        x, y = _toy(n=16)
        model = _mpmd()
        model.fit(x, y, epochs=1, batch_size=16, shuffle=False)
        logits = np.concatenate(
            list(model._forward_chunks(x[:5])), axis=0
        )
        assert logits.shape == (5, 2)
        preds = model.predict(x[:5])
        np.testing.assert_array_equal(preds, logits.argmax(-1))

        ep, sp, hp = jax.device_get(model.params)
        km = x[:5] != 0
        h = model._embed.apply(ep, x[:5])
        for s in range(model.pp):
            h = model._stage.apply(sp[s], h, km)
        ref = model._head.apply(hp, h)
        np.testing.assert_allclose(
            logits, np.asarray(ref, np.float32), rtol=1e-5, atol=1e-6
        )


# -- per-stage compile-cache goldens ------------------------------------------


class TestPerStageCache:
    # 4 embed (fwd/bwd/zeros/opt) + 4 per stage (fwd/bwd/zeros/opt)
    # + 4 head (bwd/zeros/finalize/opt) train programs for one shape.
    ENTRIES_FOR = staticmethod(lambda pp: 4 + 4 * pp + 4)

    def test_first_fit_banks_one_entry_per_stage_program(self):
        # Unique hidden_dim: this golden counts MISSES, so its
        # programs must not be resident from an earlier test.
        x, y = _toy(n=16)
        cache = cc.get_cache()
        before = cache.stats()["misses"]
        model = _mpmd(hidden_dim=32, mlp_dim=32, n_microbatches=2)
        model.fit(x, y, epochs=1, batch_size=8, shuffle=False)
        assert (
            cache.stats()["misses"] - before
            == self.ENTRIES_FOR(model.pp)
        )
        # Per-STAGE identity: stage s's programs key on their stage
        # index — independent entries, not one shared stage program.
        keys = model._mpmd._train.keys
        assert keys[("stage:fwd", 0)] != keys[("stage:fwd", 1)]
        for name, key in keys.items():
            assert cache.contains(key), name

    def test_refit_same_architecture_hits_every_entry(self):
        """The cross-job story: a FRESH instance with the same
        architecture/shape re-fits against a warm cache with ZERO new
        misses — stage compiles are shared across jobs."""
        x, y = _toy(n=16)
        first = _mpmd(hidden_dim=32, mlp_dim=32, n_microbatches=2)
        first.fit(x, y, epochs=1, batch_size=8, shuffle=False)
        cache = cc.get_cache()
        before = cache.stats()["misses"]
        refit = _mpmd(hidden_dim=32, mlp_dim=32, n_microbatches=2)
        refit.fit(x, y, epochs=1, batch_size=8, shuffle=False)
        assert cache.stats()["misses"] - before == 0


# -- per-stage spans + collective-free cost attribution -----------------------


class TestStageObservability:
    def test_fit_records_one_span_per_stage(self):
        from learningorchestra_tpu.obs import tracing

        x, y = _toy(n=16)
        model = _mpmd()
        trace = tracing.new_trace("mpmd-fit")
        assert trace is not None
        with tracing.activate(trace):
            model.fit(x, y, epochs=2, batch_size=16, shuffle=False)
        spans = trace.to_doc()["spans"]
        stage_spans = [s for s in spans if s["name"] == "mpmd.stage"]
        # One span per stage per epoch, attributed by stage index.
        assert sorted(
            (s["attrs"]["epoch"], s["attrs"]["stage"])
            for s in stage_spans
        ) == [(0, 0), (0, 1), (1, 0), (1, 1)]
        epoch_spans = [s for s in spans if s["name"] == "epoch"]
        assert len(epoch_spans) == 2
        # Cost attribution is collective-free by construction; the
        # epoch span says so whenever the flops were analyzed.
        attrs = epoch_spans[0]["attrs"]
        if "flops" in attrs:
            assert attrs["collectivesExcluded"] is True
            assert attrs["flops"] > 0


# -- stage-partitioned checkpoints --------------------------------------------


class TestStagePartitionedCheckpoints:
    def test_layout_and_resume_continue_trajectory(self, tmp_path):
        """A fit checkpointed at step 3 resumes per partition and
        continues on EXACTLY the uninterrupted run's trajectory —
        proving every stage restored its own newest state (and was
        re-committed to its own device)."""
        x, y = _toy(n=32)
        ckdir = tmp_path / "ck"
        ck = dict(
            checkpoint_dir=str(ckdir), checkpoint_every=1,
            checkpoint_min_interval_s=0, checkpoint_async=False,
        )
        first = _mpmd()
        first.fit(x, y, epochs=3, batch_size=32, shuffle=False, **ck)

        # One orbax directory per partition + the top-level marker.
        assert partition_names(first.pp) == [
            "embed", "stage_00", "stage_01", "head"
        ]
        for name in partition_names(first.pp):
            assert (ckdir / name / "latest.json").exists(), name
        top = json.loads((ckdir / "latest.json").read_text())
        assert top["step"] == 3

        resumed = _mpmd()
        resumed.fit(x, y, epochs=7, batch_size=32, shuffle=False, **ck)
        assert len(resumed.history["loss"]) == 7  # 3 restored + 4 new

        straight = _mpmd()
        straight.fit(x, y, epochs=7, batch_size=32, shuffle=False)
        np.testing.assert_allclose(
            resumed.history["loss"], straight.history["loss"],
            rtol=2e-6, atol=1e-7,
        )

    def test_missing_partition_marker_means_fresh_start(self, tmp_path):
        x, y = _toy(n=16)
        ckdir = tmp_path / "ck"
        first = _mpmd()
        first.fit(
            x, y, epochs=2, batch_size=16, shuffle=False,
            checkpoint_dir=str(ckdir), checkpoint_every=1,
            checkpoint_min_interval_s=0, checkpoint_async=False,
        )
        # Tear one stage's marker out: the resume must refuse the torn
        # checkpoint (no consistent common step), not mix epochs.
        (ckdir / "stage_01" / "latest.json").unlink()
        fresh = _mpmd()
        assert fresh._engine() is not None
        fresh._init_params(jnp.asarray(x[:1]))
        assert fresh._engine().resume_checkpoint(ckdir) is None


# -- restoreBestWeights on a pipeline fit -------------------------------------


class TestRestoreBestWeights:
    def test_rollback_restores_best_epoch_and_training_continues(self):
        """min_delta=10 makes epoch 0 the only 'improvement': the
        early stop triggers at epoch 1 and must roll the PARTITIONED
        params back to the epoch-0 snapshot (== a 1-epoch run's
        params), drop the moments, and leave the model fit-able and
        predict-able — the old stage-partitioned refusal is gone."""
        from learningorchestra_tpu.train.neural import EarlyStopping

        x, y = _toy(n=32)
        model = _mpmd()
        es = EarlyStopping(
            monitor="loss", patience=1, min_delta=10.0,
            restore_best_weights=True,
        )
        model.fit(
            x, y, epochs=5, batch_size=32, shuffle=False,
            callbacks=[es],
        )
        assert model.stop_training
        assert len(model.history["loss"]) == 2  # epoch 0 + the stall
        assert es.best_epoch == 0
        assert model.opt_state is None  # moments belong to later epochs

        one_epoch = _mpmd()
        one_epoch.fit(x, y, epochs=1, batch_size=32, shuffle=False)
        for a, b in zip(
            jax.tree_util.tree_leaves(model.params),
            jax.tree_util.tree_leaves(one_epoch.params),
        ):
            np.testing.assert_allclose(
                np.asarray(jax.device_get(a)),
                np.asarray(jax.device_get(b)),
                rtol=1e-6, atol=1e-7,
            )

        # Training continues from the restored weights: the engine
        # re-initializes per-partition moments instead of refusing.
        model.fit(x, y, epochs=1, batch_size=32, shuffle=False)
        assert model.opt_state is not None
        assert model.predict(x[:4]).shape == (4,)


# -- AOT store entries for stage programs -------------------------------------


class TestStageAOTEntries:
    def test_stage_programs_land_in_the_durable_store(self, tmp_path):
        """Single-device stage programs are AOT-serializable: with the
        durable store installed, one fit's deep cost probes persist an
        executable PER STAGE PROGRAM — the multi-chip warm-boot
        carve-out closes."""
        from learningorchestra_tpu.train import aot_store

        store = aot_store.reset_store(
            root=str(tmp_path / "aot"), max_entries=64,
            max_bytes=1 << 30,
        )
        try:
            # Unique dims: the probes only run on real builds.
            x, y = _toy(n=16)
            model = _mpmd(hidden_dim=24, mlp_dim=24, n_microbatches=2)
            model.fit(x, y, epochs=1, batch_size=8, shuffle=False)
            labels = {
                e["label"] for e in store.manifest_entries()
            }
            for want in (
                "mpmd:PipelinedTransformer:stage:fwd:s0",
                "mpmd:PipelinedTransformer:stage:fwd:s1",
                "mpmd:PipelinedTransformer:stage:bwd:s0",
                "mpmd:PipelinedTransformer:stage:bwd:s1",
                "mpmd:PipelinedTransformer:embed:fwd",
                "mpmd:PipelinedTransformer:head:bwd",
            ):
                assert want in labels, (want, sorted(labels))
        finally:
            aot_store.reset_store()


# -- the kill-9 drill (journal crash-recovery, per-stage resume) --------------

_PIPE_PARAMS = """{
    "vocab_size": 32, "hidden_dim": 8, "num_layers": 2,
    "num_heads": 2, "mlp_dim": 8, "max_len": 8, "num_classes": 2,
    "n_microbatches": 2, "pp": 2, "compute_dtype": "float32",
    "schedule": "mpmd", "seed": 0
}"""

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
x = rng.integers(1, 32, (16, 8)).astype("int32")
y = (x.sum(1) % 2).astype("int32")
model.create(
    "pm", module_path="learningorchestra_tpu.parallel.pipeline",
    class_name="PipelinedTransformer",
    class_parameters=json.loads('''__PIPE_PARAMS__'''),
)
ctx.engine.wait("pm", timeout=240)
# Deterministic mid-fit window: epochs 0-1 run free (and checkpoint),
# every later epoch's top delays 400 ms — the SIGKILL below lands
# while the pipelined fit is provably still running.
faults.arm("train.epoch", "delay", delay_ms=400, after=2)
ex.create(
    "fitp", parent_name="pm", method="fit",
    method_parameters={
        "x": x.tolist(), "y": y.tolist(), "epochs": 6,
        "batch_size": 16, "shuffle": False,
        "checkpoint_every": 1, "checkpoint_min_interval_s": 0,
        "checkpoint_async": False,
    },
    artifact_type="train/tensorflow",
)
marker = ctx.checkpoint_dir("fitp") / "latest.json"
deadline = time.time() + 300
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
""".replace("__PIPE_PARAMS__", _PIPE_PARAMS)

_CHILD_RECOVERY = r"""
import json, sys, time
from learningorchestra_tpu.config import Config
from learningorchestra_tpu.services.context import ServiceContext

cfg = Config.from_env()
cfg.store.backend = "python"
ctx = ServiceContext(cfg)  # boot-time recovery re-dispatches fitp
deadline = time.time() + 300
meta = {}
while time.time() < deadline:
    meta = ctx.artifacts.metadata.read("fitp") or {}
    if meta.get("finished") or meta.get("jobState") == "failed":
        break
    time.sleep(0.1)
hist = ctx.artifacts.ledger.history("fitp")
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
    "epochs": epochs,
}), flush=True)
ctx.close()
"""


def test_kill9_mpmd_fit_resumes_every_stage(tmp_path):
    """Orchestrator SIGKILLed mid-pipeline-fit → restarted process
    replays the journal → the MPMD fit resumes EVERY stage partition
    from the newest common step: per-partition checkpoint dirs exist
    at kill time, and the recovery run's first epoch span is >= the
    killed run's marker step (no stage re-runs epoch 0)."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "LO_TPU_STORE_ROOT": str(tmp_path / "store"),
        "LO_TPU_VOLUME_ROOT": str(tmp_path / "vol"),
    })
    env.pop("LO_TPU_WITNESS", None)

    first = subprocess.run(
        [sys.executable, "-c", _CHILD_ORCHESTRATOR],
        env=env, capture_output=True, text=True, timeout=420,
    )
    assert first.returncode == -signal.SIGKILL, (
        first.returncode, first.stdout[-2000:], first.stderr[-2000:]
    )
    assert "KILLING" in first.stdout

    ckdir = tmp_path / "vol" / "_checkpoints" / "fitp"
    step_at_kill = json.loads((ckdir / "latest.json").read_text())[
        "step"
    ]
    assert step_at_kill >= 2
    # The killed process left one orbax tree PER PARTITION, each with
    # its own committed marker.
    for name in ("embed", "stage_00", "stage_01", "head"):
        part = json.loads(
            (ckdir / name / "latest.json").read_text()
        )
        assert part["step"] >= step_at_kill, (name, part)

    second = subprocess.run(
        [sys.executable, "-c", _CHILD_RECOVERY],
        env=env, capture_output=True, text=True, timeout=420,
    )
    assert second.returncode == 0, (
        second.stdout[-2000:], second.stderr[-2000:]
    )
    result = json.loads(
        second.stdout.split("RESULT ", 1)[1].splitlines()[0]
    )
    assert result["jobState"] == "finished", result
    epochs = result["epochs"]
    assert epochs, "recovered run recorded no epoch spans"
    # Resumed per stage, not restarted: only the tail re-ran.
    assert min(epochs) >= step_at_kill, (epochs, step_at_kill)
    assert max(epochs) == 5, epochs
    assert len(epochs) < 6, epochs


# -- sharded fleet replicas over the REST surface -----------------------------


@pytest.fixture(scope="module")
def sharded_api(tmp_path_factory):
    from learningorchestra_tpu.api import APIServer
    from learningorchestra_tpu.config import Config
    from learningorchestra_tpu.jobs.leases import DeviceLeaser

    tmp = tmp_path_factory.mktemp("sharded_api")
    cfg = Config()
    cfg.store.root = str(tmp / "store")
    cfg.store.volume_root = str(tmp / "volumes")
    cfg.serve.max_batch = 4
    cfg.serve.max_queue = 16
    cfg.serve.flush_ms = 1.0
    cfg.fleet.interval_s = 0.05
    server = APIServer(cfg)
    # A 4-chip pool of REAL (virtual-CPU) jax devices: multi-device
    # leases must resolve to actual Device handles for GSPMD placement.
    server.ctx.leaser = DeviceLeaser(
        [f"cpu:{i}" for i in range(4)]
    )
    port = server.start_background()
    base = f"http://127.0.0.1:{port}{PREFIX}"
    yield server, base
    server.shutdown()


def _install_trained_model(server, name):
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


class TestShardedReplicaServe:
    def test_two_chip_replica_serves_sharded(self, sharded_api):
        """The multi-chip serving round-trip: a replica leases 2
        chips, places params mesh-sharded across them, answers
        predicts identically to the plain estimator, and reports its
        device LIST + shard spec on the replicas route.  Width is
        fixed while the set is live (406)."""
        server, base = sharded_api
        est, x = _install_trained_model(server, "shmod")
        resp = requests.post(
            f"{base}/serve/shmod/replicas",
            json={"min": 1, "max": 1, "count": 1,
                  "devicesPerReplica": 2},
        )
        assert resp.status_code == 200, resp.text
        body = resp.json()
        assert body["size"] == 1
        assert body["devicesPerReplica"] == 2
        rep = body["replicas"][0]
        assert len(rep["devices"]) == 2
        assert set(rep["devices"]) <= {f"cpu:{i}" for i in range(4)}

        resp = requests.post(
            f"{base}/serve/shmod/predict",
            json={"instances": x[:3].tolist()},
        )
        assert resp.status_code == 200, resp.text
        preds = np.asarray(resp.json()["predictions"])
        ref = np.asarray(est.predict(x[:3]))
        np.testing.assert_allclose(preds, ref, rtol=1e-5, atol=1e-6)

        # Placement happens at first dispatch; the replicas route now
        # reports the device LIST and the shard layout it produced.
        listed = requests.get(
            f"{base}/serve/shmod/replicas"
        ).json()
        assert listed["replicas"][0]["devices"] == rep["devices"]
        spec = listed["replicas"][0]["shardSpec"]
        assert spec["axis"] == "shard"
        assert spec["devices"] == 2
        assert spec["strategy"] == "leading-dim"
        assert spec["shardedLeaves"] >= 1
        assert "_repl" not in spec  # private placement key stripped

        # Replica width is fixed while the set is live.
        resp = requests.post(
            f"{base}/serve/shmod/replicas",
            json={"devicesPerReplica": 3},
        )
        assert resp.status_code == 406
        assert "dissolve" in resp.json()["error"]

        # Dissolve → the width can change; chips return to the pool.
        requests.post(f"{base}/serve/shmod/unload", json={})
        assert len(server.ctx.leaser.snapshot()["free"]) == 4

    def test_bad_width_rejected(self, sharded_api):
        server, base = sharded_api
        _install_trained_model(server, "shbad")
        resp = requests.post(
            f"{base}/serve/shbad/replicas",
            json={"count": 1, "devicesPerReplica": 0},
        )
        assert resp.status_code == 406
