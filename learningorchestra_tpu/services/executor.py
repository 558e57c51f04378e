"""Executor service: tune / train / evaluate / predict.

The reference's binaryExecutor (microservices/binary_executor_image/): load
the parent binary, ``getattr(instance, method)(**treated_params)``, persist
— train-family methods return the mutated instance itself
(binary_execution.py:188-200); other methods' results are stored as result
rows + binary.  The lineage walk finds the original model spec behind any
chain of steps (utils.py:261-280).

Tune adds what the reference leaves to the user: a managed grid-search
(``param_grid``) that fits one candidate per combination and records each
candidate's score as a result row, selecting the best instance.
"""

from __future__ import annotations

import contextlib
import itertools
import shutil
import time
from concurrent.futures import ThreadPoolExecutor, as_completed
from typing import Any

import numpy as np

from learningorchestra_tpu import dsl
from learningorchestra_tpu.jobs.leases import device_ids, placed_on
from learningorchestra_tpu.obs import tracing as obs_tracing
from learningorchestra_tpu.train.neural import NeuralEstimator
from learningorchestra_tpu.services.context import (
    ServiceContext,
    ValidationError,
)
from learningorchestra_tpu.toolkit import registry

TRAIN_KINDS = ("train", "tune")


def store_history_rows(documents, name: str, history: dict) -> int:
    """Persist a TrainHistory-shaped dict ({metric: [per-epoch...]}) as one
    pollable row per epoch — the durable metrics contract (SURVEY §5.5).
    Shared by the single-device and distributed train paths.  A re-run
    re-stores the full history, so the old rows go first (epochs would
    duplicate); the whole rewrite is the job's ``store_history`` span."""
    with obs_tracing.span("store_history"):
        for doc in documents.find(name, query={"docType": "history"}):
            documents.delete_one(name, doc["_id"])
        keys = list(history)
        n = max((len(history[k]) for k in keys), default=0)
        for i in range(n):
            documents.insert_one(
                name,
                {
                    "docType": "history",
                    "epoch": i,
                    **{
                        k: history[k][i] for k in keys
                        if len(history[k]) > i
                    },
                },
            )
    return n


def publish_object(ctx: ServiceContext, artifact_type: str, name: str,
                   obj: Any, *, replaces: bool = True) -> None:
    """Write a job's result binary as the job's ``publish`` span (attr
    ``bytes``).  ``replaces``: a re-run just replaced this artifact's
    binary, so a serving registry holding its old params resident must
    reload before the next request."""
    with obs_tracing.span("publish"):
        ctx.volumes.save_object(artifact_type, name, obj)
        if obs_tracing.current_trace() is not None:
            obs_tracing.set_span_attrs(
                bytes=ctx.volumes.object_bytes(artifact_type, name)
            )
        if replaces:
            ctx.notify_artifact_changed(name)


class ExecutorService:
    def __init__(self, ctx: ServiceContext):
        self.ctx = ctx

    # -- shared validation (reference: server.py:332-398) ---------------------

    @staticmethod
    def _reject_raw_checkpoint_dir(method_parameters) -> None:
        """Checkpoint placement is managed server-side (ctx.checkpoint_dir);
        a raw path from the network would be written/pruned verbatim."""
        if method_parameters and "checkpoint_dir" in method_parameters:
            raise ValidationError(
                "checkpoint_dir is managed by the service; use "
                "checkpoint_every/resume to control checkpointing"
            )

    def _validate_request(self, name, parent_name, method, method_parameters):
        self.ctx.require_new_name(name)
        self._reject_raw_checkpoint_dir(method_parameters)
        parent_meta = self.ctx.require_finished_parent(parent_name)
        model_meta = self.ctx.artifacts.metadata.find_model_ancestor(
            parent_name
        )
        factory = registry.resolve(
            model_meta.get("modulePath"), model_meta.get("class")
        )
        if not registry.validate_method(factory, method):
            raise ValidationError(f"no such method: {method!r}")
        bad = registry.validate_method_params(
            factory, method, method_parameters or {}
        )
        if bad:
            raise ValidationError(f"invalid methodParameters: {bad}")
        return parent_meta, model_meta

    # -- create ---------------------------------------------------------------

    def create(
        self,
        name: str,
        *,
        parent_name: str,
        method: str,
        method_parameters: dict | None = None,
        artifact_type: str = "train/tensorflow",
        description: str = "",
        deadline_s: float | None = None,
    ) -> dict:
        parent_meta, model_meta = self._validate_request(
            name, parent_name, method, method_parameters
        )
        meta = self.ctx.artifacts.metadata.create(
            name,
            artifact_type,
            parent_name=parent_name,
            module_path=model_meta.get("modulePath"),
            class_name=model_meta.get("class"),
            method=method,
        )
        self._submit(
            name, parent_meta, method, method_parameters, artifact_type,
            description, resume_checkpoint=False,
            warm_key=_warm_key(model_meta, method, method_parameters),
            deadline_s=deadline_s,
        )
        return meta

    def update(
        self,
        name: str,
        *,
        method_parameters: dict | None = None,
        description: str = "",
        deadline_s: float | None = None,
    ) -> dict:
        """PATCH re-run with new parameters (reference:
        server.py:110-156).

        A re-run of a FAILED train job resumes from its newest managed
        checkpoint (the preemption path); a re-run of a finished job is
        a fresh fit from epoch 0 — new parameters must actually apply,
        so stale checkpoints are cleared.
        """
        meta = self.ctx.require_existing(name)
        self._reject_raw_checkpoint_dir(method_parameters)
        parent = meta.get("parentName")
        if not parent:
            raise ValidationError(
                f"artifact {name!r} has no parent — not an executor result"
            )
        parent_meta = self.ctx.require_finished_parent(parent)
        resume = meta.get("jobState") == "failed"
        if not method_parameters:
            # Bare PATCH ("just resume"): fall back to the original
            # request's parameters from the execution ledger (ADVICE r1).
            method_parameters = self.ctx.last_recorded_parameters(name)
        self.ctx.artifacts.metadata.restart(name)
        self._submit(
            name, parent_meta, meta.get("method"), method_parameters,
            meta.get("type"), description, resume_checkpoint=resume,
            warm_key=_warm_key(
                meta, meta.get("method"), method_parameters
            ),
            deadline_s=deadline_s,
        )
        return self.ctx.artifacts.metadata.read(name)

    def _submit(self, name, parent_meta, method, method_parameters,
                artifact_type, description, *, resume_checkpoint=False,
                warm_key=None, deadline_s=None):
        parent_name = parent_meta["name"]
        parent_type = parent_meta.get("type", "")
        kind = artifact_type.split("/", 1)[0]

        def run():
            from learningorchestra_tpu.jobs import engine as engine_mod
            from learningorchestra_tpu.obs import costs as obs_costs
            from learningorchestra_tpu.train import compile_cache

            cache_before = compile_cache.counters_snapshot()
            with obs_tracing.span("load_artifact", parent=parent_name):
                instance = self.ctx.volumes.read_object(
                    parent_type, parent_name
                )
            params = dsl.resolve_params(method_parameters, self.ctx.loader)
            # Which preemption-retry attempt is this body running as?
            # 0 on the first execution; >0 means the engine's in-loop
            # retry re-invoked us after a ``Preempted`` — resume from
            # the managed checkpoints THIS run already wrote instead
            # of restarting at epoch 0 (previously only a manual PATCH
            # of a failed job got resume semantics).
            attempt = engine_mod.current_attempt()
            resume = resume_checkpoint or attempt > 0
            if (
                kind in TRAIN_KINDS
                and method == "fit"
                and getattr(
                    instance, "supports_managed_checkpoints", False
                )
                and "checkpoint_dir" not in params
            ):
                # Managed in-loop checkpointing: a FAILED train job
                # PATCHed back resumes from its newest checkpoint instead
                # of epoch 0 (train/checkpoint.py; the reference loses
                # mid-job state entirely, SURVEY §5.4).  Fresh runs and
                # param-changing re-runs of finished jobs must not
                # resurrect old state, so their checkpoint dir is wiped
                # — but only on attempt 0: a retry's checkpoints are
                # its own run's state, never stale.
                ckdir = self.ctx.checkpoint_dir(name)
                if not resume and ckdir.exists():
                    shutil.rmtree(ckdir, ignore_errors=True)
                params["checkpoint_dir"] = str(ckdir)
                params.setdefault("resume", resume)
                if attempt > 0:
                    # A caller-specified resume=False means "fresh
                    # fit", which attempt 0 honored (the wipe above
                    # didn't run on retries); resuming the SAME
                    # logical run's checkpoints after preemption is
                    # still that fresh fit, continued.
                    params["resume"] = True
            t0 = time.perf_counter()
            # Device-time attribution scope (obs/costs.py): dispatches
            # the body makes (the fit epoch loop) book against THIS
            # job's ledger entry.
            with obs_costs.job_scope(name):
                param_devices = []
                if isinstance(instance, NeuralEstimator):
                    # On-device work: take a chip lease so concurrent
                    # neural jobs get placed, not interleaved, and RUN
                    # on the leased chip (jobs/leases.py).
                    with self.ctx.leaser.lease(1, label=name) as devs, \
                            placed_on(devs):
                        if devs:
                            self.ctx.artifacts.metadata.update(
                                name, {"leasedDevices": devs}
                            )
                        result = getattr(instance, method)(**params)
                        param_devices = device_ids(instance.params)
                else:
                    result = getattr(instance, method)(**params)
            fit_time = time.perf_counter() - t0
            if isinstance(instance, NeuralEstimator) and \
                    compile_cache.enabled():
                # The job's compiled programs are now cached: publish
                # the warm hint (the dispatcher prefers queued
                # same-program jobs) and the per-job counter delta —
                # cache effectiveness observable from the ordinary
                # GET/poll path.  Counters are process-wide, so under
                # concurrent jobs the delta is an upper bound.  With
                # the cache disabled nothing is ever warm — a hint
                # would reorder the queue for zero benefit.
                self.ctx.engine.note_warm(warm_key)
            cache_delta = compile_cache.delta_since(cache_before)
            # Epoch fence at publication: a stale-epoch straggler (a
            # pre-crash worker racing a recovered orchestrator) must
            # not overwrite the artifact a newer epoch owns.
            self.ctx.require_current_epoch()
            if kind in TRAIN_KINDS or result is instance:
                # Train semantics: persist the mutated instance
                # (binary_execution.py:195-200).
                publish_object(self.ctx, artifact_type, name, instance)
                extra = {"fitTime": fit_time,
                         "compileCache": cache_delta}
                if param_devices:
                    # Where the trained params actually lived — the
                    # check on leasedDevices, which only says what the
                    # job was granted.
                    extra["paramDevices"] = param_devices
                device_time = obs_costs.job_summary(name)
                if device_time is not None:
                    # Attributed device seconds/flops (and MFU when a
                    # peak is configured) — cost accounting observable
                    # from the ordinary GET/poll path.
                    extra["deviceTime"] = device_time
                hist = getattr(instance, "history", None)
                if hist:
                    store_history_rows(self.ctx.documents, name, hist)
                return extra
            # Evaluate/predict semantics: persist result rows + binary.
            publish_object(
                self.ctx, artifact_type, name, result, replaces=False
            )
            self._store_result_rows(name, result)
            return {"fitTime": fit_time}

        self.ctx.engine.submit(
            name,
            run,
            description=description or f"{method} on {parent_name}",
            method=method,
            parameters=_json_safe(method_parameters),
            on_success=lambda extra: extra,
            job_class="executor",
            warm_key=warm_key,
            deadline_s=deadline_s,
        )

    def _store_result_rows(self, name: str, result: Any) -> None:
        """Make method results pollable as rows (the reference stores
        results in the collection for GET; utils.py:116-139)."""
        if isinstance(result, dict):
            self.ctx.documents.insert_one(name, _json_safe(result))
            return
        arr = np.asarray(result)
        if arr.ndim == 0:
            self.ctx.documents.insert_one(name, {"result": arr.item()})
        elif arr.ndim == 1:
            self.ctx.documents.insert_many(
                name, ({"result": _json_safe(v)} for v in arr.tolist())
            )
        else:
            self.ctx.documents.insert_many(
                name, ({"result": row} for row in arr.tolist())
            )

    # -- tune: managed grid search -------------------------------------------

    def create_tune(
        self,
        name: str,
        *,
        parent_name: str,
        method: str = "fit",
        param_grid: dict | None = None,
        method_parameters: dict | None = None,
        scoring_parameters: dict | None = None,
        artifact_type: str = "tune/tensorflow",
        description: str = "",
        deadline_s: float | None = None,
    ) -> dict:
        """Grid-search over ``param_grid`` (dict of lists).  Each candidate
        re-instantiates the model ancestor's class with those kwargs, fits
        with ``method_parameters``, scores with ``score``/``evaluate`` on
        ``scoring_parameters`` (defaults to the fit data), and the best
        candidate instance is persisted as this artifact's binary."""
        if not param_grid:
            raise ValidationError("param_grid is required for tune")
        for key, values in param_grid.items():
            if not isinstance(values, (list, tuple)) or not values:
                raise ValidationError(
                    f"param_grid[{key!r}] must be a non-empty list"
                )
        self.ctx.require_new_name(name)
        self._reject_raw_checkpoint_dir(method_parameters)
        self.ctx.require_finished_parent(parent_name)
        model_meta = self.ctx.artifacts.metadata.find_model_ancestor(
            parent_name
        )
        factory = registry.resolve(
            model_meta.get("modulePath"), model_meta.get("class")
        )
        bad = registry.validate_init_params(
            model_meta.get("modulePath"), model_meta.get("class"),
            {k: None for k in param_grid},
        )
        if bad:
            raise ValidationError(f"param_grid keys not in __init__: {bad}")
        meta = self.ctx.artifacts.metadata.create(
            name,
            artifact_type,
            parent_name=parent_name,
            module_path=model_meta.get("modulePath"),
            class_name=model_meta.get("class"),
            method=method,
        )

        warm_key = _warm_key(model_meta, method, param_grid)

        def run():
            from learningorchestra_tpu.jobs import engine as engine_mod
            from learningorchestra_tpu.train import compile_cache

            cache_before = compile_cache.counters_snapshot()
            # Preemption-retry resume for the TRIALS (the PR-7
            # current_attempt() threading, mirroring the single-fit
            # path): each neural trial owns a managed checkpoint dir
            # keyed by its stable combo index, so a retry of the grid
            # resumes every trial from its newest checkpoint instead
            # of epoch 0.  Attempt 0 wipes the tree — a fresh grid
            # must not resurrect a previous run's trial state.
            attempt = engine_mod.current_attempt()
            trial_ck_root = self.ctx.checkpoint_dir(name)
            if attempt == 0 and trial_ck_root.exists():
                shutil.rmtree(trial_ck_root, ignore_errors=True)
            fit_params = dsl.resolve_params(
                method_parameters, self.ctx.loader
            )
            score_params = dsl.resolve_params(
                scoring_parameters, self.ctx.loader
            ) if scoring_parameters else {
                k: v for k, v in fit_params.items() if k in ("x", "y")
            }
            keys = sorted(param_grid)
            combos = [
                dict(zip(keys, combo))
                for combo in itertools.product(
                    *(param_grid[k] for k in keys)
                )
            ]

            def eval_candidate(idx: int, kwargs: dict):
                from learningorchestra_tpu.obs import (
                    costs as obs_costs,
                )

                candidate = factory(**kwargs)
                trial_params = fit_params
                if (
                    isinstance(candidate, NeuralEstimator)
                    and method == "fit"
                ):
                    # Managed per-trial checkpoints: combos is built
                    # deterministically (sorted keys x product), so
                    # index idx names the same trial on every retry.
                    trial_params = dict(fit_params)
                    trial_params.setdefault(
                        "checkpoint_dir",
                        str(trial_ck_root / f"trial_{idx:04d}"),
                    )
                    trial_params.setdefault("resume", attempt > 0)
                if isinstance(candidate, NeuralEstimator):
                    # Each trial leases a chip for its on-device work
                    # (VERDICT r1 weak item 4; reference parity: Ray
                    # placement groups, server.py:16) — and RUNS there:
                    # on a multi-chip host, trials spread ACROSS the
                    # chips concurrently, each pinned to its lease via
                    # jax.default_device (BASELINE config 4's
                    # grid-search-over-a-slice shape).  Single chip
                    # degenerates to the serialized round 2 behavior.
                    lease = self.ctx.leaser.lease(
                        1, label=f"{name}:trial"
                    )
                else:
                    lease = contextlib.nullcontext([])
                with lease as devs:
                    # Re-bind the job scope: trials run on pool
                    # threads, which do not inherit the engine
                    # thread's context — every candidate's epochs
                    # still book against THIS tune job.
                    with placed_on(devs), obs_costs.job_scope(name):
                        t0 = time.perf_counter()
                        getattr(candidate, method)(**trial_params)
                        fit_time = time.perf_counter() - t0
                        score = float(candidate.score(**score_params))
                return candidate, score, fit_time

            # Candidates run concurrently (the reference trains its
            # builder classifiers in parallel threads the same way,
            # builder_image/builder.py:62-78); device compute serializes
            # on the accelerator, but host-side prep/score overlap.
            # Trials stream: each result doc inserts as it completes
            # (clients polling GET see progress) and only the current
            # best candidate's parameters stay referenced — a big grid
            # over a large model must not hold every fitted candidate.
            best_score, best_instance, best_combo = -np.inf, None, None
            # Worker pool sizes to the CHIP pool only when trials
            # actually lease chips (the v4-8 shape runs 8 neural trials
            # at once, one per chip); host-only grids keep the bounded
            # 4-thread default — they never lease, so chip-count
            # threads would just oversubscribe host CPU/RAM.
            trials_lease = isinstance(factory, type) and issubclass(
                factory, NeuralEstimator
            )
            n_chips = self.ctx.leaser.device_count if trials_lease else 0
            workers = min(len(combos), max(4, n_chips))
            with ThreadPoolExecutor(max_workers=workers) as pool:
                futures = {
                    pool.submit(eval_candidate, i, kw): kw
                    for i, kw in enumerate(combos)
                }
                try:
                    for fut in as_completed(list(futures)):
                        # pop: a consumed future (and its non-best
                        # candidate) must become collectable now, not
                        # when the pool exits.
                        kwargs = futures.pop(fut)
                        candidate, score, fit_time = fut.result()
                        self.ctx.documents.insert_one(
                            name,
                            {
                                "params": _json_safe(kwargs),
                                "score": score,
                                "fitTime": fit_time,
                            },
                        )
                        if score > best_score:
                            best_score, best_instance, best_combo = (
                                score, candidate, kwargs,
                            )
                except Exception:
                    # First failure aborts the search: don't burn the
                    # accelerator fitting the remaining queued combos.
                    for pending in futures:
                        pending.cancel()
                    raise
            self.ctx.require_current_epoch()
            publish_object(self.ctx, artifact_type, name, best_instance)
            # Trial checkpoints are per-run scratch: the grid is done,
            # the best candidate is published — keeping them would only
            # let a FUTURE unrelated grid resurrect stale trial state.
            shutil.rmtree(trial_ck_root, ignore_errors=True)
            if trials_lease and compile_cache.enabled():
                self.ctx.engine.note_warm(warm_key)
            # Grid-level compile-cache accounting: candidates sharing
            # an architecture coalesce onto ONE trace (the rest hit),
            # so for an N-candidate same-arch sweep expect hits ≈ N-1
            # per program kind.  Concurrent unrelated jobs can inflate
            # the delta (process-wide counters).
            out = {
                "bestScore": best_score,
                "bestParams": _json_safe(best_combo),
                "compileCache": compile_cache.delta_since(cache_before),
            }
            from learningorchestra_tpu.obs import costs as obs_costs

            device_time = obs_costs.job_summary(name)
            if device_time is not None:
                out["deviceTime"] = device_time
            return out

        self.ctx.engine.submit(
            name, run, description=description or f"grid search {parent_name}",
            method=method, parameters=_json_safe(param_grid),
            on_success=lambda extra: extra,
            job_class="executor",
            warm_key=warm_key,
            deadline_s=deadline_s,
        )
        return meta

    def delete(self, name: str) -> None:
        self.ctx.delete_artifact(name)


def _warm_key(meta: dict, method,
              method_parameters: dict | None = None) -> str | None:
    """Program-fingerprint warm hint for the engine's warm-start
    dispatch preference (``compile_cache.warm_fingerprint``): the
    submitted spec's trace-shaping parameters hash into the key, so
    two jobs share a hint exactly when they would very likely share
    traced programs — an optimizer or layer-width change separates
    them, where the old coarse ``module:class:method`` tag lumped a
    whole class together.  A HINT, not a guarantee — exact matching
    happens inside compile_cache; a wrong hint merely reorders one
    class's queue."""
    from learningorchestra_tpu.train import compile_cache

    module_path = meta.get("modulePath")
    class_name = meta.get("class")
    if not module_path or not class_name:
        return None
    return compile_cache.warm_fingerprint(
        module_path, class_name, method, method_parameters
    )


def _json_safe(obj):
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)
