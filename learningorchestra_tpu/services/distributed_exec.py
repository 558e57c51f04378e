"""Distributed execution service — the reference's flagship paths.

Covers two routes (SURVEY §2.2, §3.3):

- ``POST /train/horovod`` (reference: binary_executor_image/
  binary_execution.py:237-292 — ship model JSON to Ray workers, Horovod
  ring-allreduce inside ``model.fit``, rank-0 weights home): here the
  same request shape drives :class:`DistributedTrainer` — one jitted
  train step over a named mesh, gradients psum'd over ICI by XLA's SPMD
  partitioner; no model serialization, no host ring, no weight lists.

- ``POST /builder/tensorflow|pytorch`` (reference:
  binary_execution.py:295-348 — ast-validate a single user function,
  compile, run on every Ray worker): here the validated function runs
  once per rank with ``rank``/``world_size`` kwargs — locally on
  threads, or fanned over per-host agents when a coordinator is
  configured (parallel/coordinator.py) — and per-rank results persist as
  result rows + a dill binary.

Request parity: ``training_parameters`` split into per-rank ``callbacks``
vs ``rank0callbacks`` survives as a declarative passthrough; the
``compile_code`` escape hatch maps to the declarative ``compile`` spec
(optimizer/loss via the ``#`` DSL) rather than exec'd source.
"""

from __future__ import annotations

import ast
import concurrent.futures
import time

from learningorchestra_tpu import dsl
from learningorchestra_tpu.jobs.leases import device_ids
from learningorchestra_tpu.services.context import (
    ServiceContext,
    ValidationError,
)
from learningorchestra_tpu.services.executor import (
    ExecutorService,
    _json_safe,
    publish_object,
    store_history_rows,
)
from learningorchestra_tpu.services.monitoring import (
    MonitoringService,
    write_scalar_logs,
)

DISTRIBUTED_TRAIN_TYPE = "train/tensorflow"
DISTRIBUTED_BUILDER_TYPE = "builder/horovod"
# One request must not be able to exhaust the server's threads: ranks are
# host threads here (the compute inside each is XLA's concern).
MAX_BUILDER_WORKERS = 256


def _validate_single_function(code: str) -> str:
    """The builder contract: the payload is EXACTLY one top-level function
    definition (reference ast-validates this, binary_execution.py:328-339).
    Returns the function name."""
    try:
        tree = ast.parse(code)
    except SyntaxError as exc:
        raise ValidationError(f"function does not parse: {exc}") from exc
    if any(isinstance(n, ast.AsyncFunctionDef) for n in tree.body):
        # run() calls the function synchronously per rank; an async def
        # would return an un-awaitable coroutine instead of results.
        raise ValidationError("builder function must not be async")
    defs = [n for n in tree.body if isinstance(n, ast.FunctionDef)]

    def allowed(node: ast.stmt) -> bool:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            return True
        # Expr is only a docstring — a bare call would execute at module
        # exec time, outside the per-rank function the contract promises.
        return isinstance(node, ast.Expr) and isinstance(
            node.value, ast.Constant
        ) and isinstance(node.value.value, str)

    others = [n for n in tree.body if n not in defs and not allowed(n)]
    if len(defs) != 1 or others:
        raise ValidationError(
            "builder function must be a single top-level function "
            "definition (imports and a docstring are allowed)"
        )
    return defs[0].name


class DistributedExecutorService:
    def __init__(self, ctx: ServiceContext,
                 monitoring: MonitoringService | None = None):
        self.ctx = ctx
        self.monitoring = monitoring

    # -- distributed training -------------------------------------------------

    def create_train(
        self,
        name: str,
        *,
        parent_name: str,
        training_parameters: dict | None = None,
        compile_spec: dict | None = None,
        mesh: dict | None = None,
        monitoring_path: str | None = None,
        artifact_type: str = DISTRIBUTED_TRAIN_TYPE,
        description: str = "",
    ) -> tuple[dict, dict]:
        """Returns (metadata, extra_results) — extra carries the
        monitoring URL the reference returned inline
        (server.py:70-76,104)."""
        self.ctx.require_new_name(name)
        ExecutorService._reject_raw_checkpoint_dir(training_parameters)
        parent_meta = self.ctx.require_finished_parent(parent_name)
        # Resolve + validate the monitoring nickname BEFORE creating the
        # artifact: a bad monitoringPath must 406, not burn the name on a
        # metadata doc whose job never got submitted.
        session_name = None
        if monitoring_path is not None and self.monitoring is not None:
            session_name = str(monitoring_path).strip("/").replace(
                "/", "_"
            ) or name
            if not self.monitoring.valid_nickname(session_name):
                raise ValidationError(
                    f"invalid monitoringPath {monitoring_path!r}"
                )
        model_meta = self.ctx.artifacts.metadata.find_model_ancestor(
            parent_name
        )
        meta = self.ctx.artifacts.metadata.create(
            name,
            artifact_type,
            parent_name=parent_name,
            module_path=model_meta.get("modulePath"),
            class_name=model_meta.get("class"),
            method="fit",
            extra={"distributed": True, "mesh": _json_safe(mesh or {})},
        )

        extra_results: dict = {}
        session_logdir = None
        if session_name is not None:
            session_info = self.monitoring.start(session_name)
            # Capture the logdir now: a mid-train DELETE of the session
            # must not fail an otherwise-successful training job.
            session_logdir = session_info["logdir"]
            extra_results["monitoring"] = session_info

        self._submit_train(
            name, parent_meta, training_parameters, compile_spec, mesh,
            artifact_type, description,
            session_name=session_name, session_logdir=session_logdir,
            resume_default=False,
        )
        return meta, extra_results

    def update_train(
        self,
        name: str,
        *,
        training_parameters: dict | None = None,
        compile_spec: dict | None = None,
        mesh: dict | None = None,
        description: str = "",
    ) -> dict:
        """PATCH re-run.  A FAILED (e.g. preempted) distributed job
        resumes from its managed in-loop checkpoint; re-running a
        finished job starts fresh so new parameters apply — identical
        semantics to the single-device executor's PATCH."""
        meta = self.ctx.require_existing(name)
        ExecutorService._reject_raw_checkpoint_dir(training_parameters)
        parent = meta.get("parentName")
        if not parent:
            raise ValidationError(
                f"artifact {name!r} has no parent — not a train result"
            )
        parent_meta = self.ctx.require_finished_parent(parent)
        resume = meta.get("jobState") == "failed"
        if not training_parameters:
            # Bare PATCH ("just resume"): re-run with the original
            # request's parameters from the execution ledger rather than
            # reaching fit() with no x/y (ADVICE r1).
            training_parameters = self.ctx.last_recorded_parameters(name)
        self.ctx.artifacts.metadata.restart(name)
        self._submit_train(
            name, parent_meta, training_parameters, compile_spec,
            mesh or meta.get("mesh"), meta.get("type"), description,
            session_name=None, session_logdir=None,
            resume_default=resume,
        )
        return self.ctx.artifacts.metadata.read(name)

    def _submit_train(
        self, name, parent_meta, training_parameters, compile_spec, mesh,
        artifact_type, description, *, session_name, session_logdir,
        resume_default,
    ):
        parent_name = parent_meta["name"]
        parent_type = parent_meta.get("type", "")

        if self.ctx.config.dist.task_coordinator:
            return self._submit_train_cluster(
                name, parent_name, parent_type, training_parameters,
                compile_spec, mesh, artifact_type, description,
                resume_default=resume_default,
                session_logdir=session_logdir,
            )

        def run():
            from learningorchestra_tpu.parallel.distributed import (
                DistributedTrainer,
            )
            from learningorchestra_tpu.parallel.mesh import MeshSpec
            from learningorchestra_tpu.train import compile_cache

            cache_before = compile_cache.counters_snapshot()
            instance = self.ctx.volumes.read_object(parent_type, parent_name)
            if not hasattr(instance, "module"):
                raise ValidationError(
                    f"parent {parent_name!r} is not a neural estimator — "
                    f"distributed training requires one"
                )
            params = dsl.resolve_params(
                training_parameters, self.ctx.loader
            )
            if compile_spec:
                instance.compile(
                    **dsl.resolve_params(compile_spec, self.ctx.loader)
                )
            spec = MeshSpec.from_dict(mesh) if mesh else None
            # shard_sequence=None → trainer auto-default (on iff sp>1);
            # the mesh body can force it with "shardSequence".
            shard_seq = (mesh or {}).get("shardSequence")
            trainer = DistributedTrainer(
                instance, spec=spec,
                shard_sequence=None if shard_seq is None
                else bool(shard_seq),
            )
            # Managed in-loop checkpoints (train/checkpoint.py).  The
            # directory is always the managed one — raw paths were
            # rejected at the route.  resume defaults by request kind:
            # fresh POST wipes stale state; PATCH of a failed job
            # resumes it; an in-engine preemption RETRY (attempt > 0)
            # always resumes — its checkpoints are this run's own
            # state, never stale (the PR-7 current_attempt threading
            # the single-device path already has).
            import shutil as _shutil

            from learningorchestra_tpu.jobs import (
                engine as engine_mod,
            )

            attempt = engine_mod.current_attempt()
            ckdir = self.ctx.checkpoint_dir(name)
            params.setdefault("resume", resume_default)
            if attempt > 0:
                # A retry's checkpoints are this run's own state —
                # resume even when the request said fresh-fit.
                params["resume"] = True
            if not params["resume"] and ckdir.exists():
                _shutil.rmtree(ckdir, ignore_errors=True)
            params["checkpoint_dir"] = str(ckdir)
            # A distributed fit spans the host's whole slice: lease ALL
            # devices so it never interleaves with single-chip jobs.
            with self.ctx.leaser.lease(0, label=name) as devs:
                if devs:
                    self.ctx.artifacts.metadata.update(
                        name, {"leasedDevices": devs}
                    )
                from learningorchestra_tpu.obs import (
                    tracing as obs_tracing,
                )

                t0 = time.perf_counter()
                with obs_tracing.span(
                    "trainer_fit", mesh=str(_json_safe(mesh or {}))
                ):
                    if session_name is not None:
                        with self.monitoring.trace(session_name):
                            trainer.fit(**params)
                    else:
                        trainer.fit(**params)
                fit_time = time.perf_counter() - t0
            # Epoch fence at publication: a stale-epoch straggler must
            # not overwrite the artifact a recovered orchestrator owns.
            self.ctx.require_current_epoch()
            publish_object(self.ctx, artifact_type, name, instance)
            store_history_rows(
                self.ctx.documents, name, dict(trainer.history)
            )
            cache_delta = compile_cache.delta_since(cache_before)
            if session_logdir is not None:
                # Cache counters ride into the tfevents file as
                # single-step scalars next to the training curves, so
                # TensorBoard shows whether this job traced (miss) or
                # warm-started (hit).
                logged = dict(trainer.history)
                logged.update({
                    f"compile_cache_{key}": [float(val)]
                    for key, val in cache_delta.items()
                })
                write_scalar_logs(session_logdir, logged, prefix=name)
            return {
                "fitTime": fit_time,
                "meshDevices": trainer.mesh.size,
                # Where the sharded state and batches actually lived
                # (the check on leasedDevices / meshDevices).
                "paramDevices": device_ids(trainer.params),
                "batchDevices": trainer.batch_devices,
                "compileCache": cache_delta,
            }

        self.ctx.engine.submit(
            name,
            run,
            description=description or f"distributed fit on {parent_name}",
            method="fit",
            parameters=_json_safe(training_parameters),
            on_success=lambda extra: extra,
            job_class="distributed",
        )

    # trainingParameters the cluster path can ship to agents: arrays go
    # via staged .npy files, scalars via JSON; anything else must be
    # rejected loudly, not silently dropped.
    _CLUSTER_ARRAY_KEYS = ("x", "y")

    def _submit_train_cluster(
        self, name, parent_name, parent_type, training_parameters,
        compile_spec, mesh, artifact_type, description, *,
        resume_default, session_logdir=None,
    ):
        """Cluster mode: fan the fit out to HostAgents through the task
        Coordinator — the reference's ``RayExecutor.run(train)`` shape
        (binary_execution.py:237-292), except the agents form ONE SPMD
        program over a global mesh instead of a Horovod ring, and the
        trained state comes home through the shared artifact volume,
        not as weight lists over the control plane.

        Monitoring caveat: profiler traces run on the agents, not here;
        the managed TensorBoard session still gets the scalar curves
        (written from the returned history after the job completes).
        """
        import shutil as _shutil

        import numpy as np

        from learningorchestra_tpu.parallel.coordinator import (
            submit_job,
            wait_job,
        )

        cfg = self.ctx.config.dist
        coord = cfg.task_coordinator
        world = int(cfg.num_processes)
        if world < 2:
            raise ValidationError(
                "cluster mode needs dist.num_processes >= 2 "
                "(LO_TPU_WORLD_SIZE) — one process per agent host"
            )
        # jax_coordinator is optional: when unset, the rank-0 agent
        # binds a port and publishes its address through the task
        # coordinator (launch._negotiate_rendezvous).
        jax_coord = cfg.jax_coordinator

        def run():
            params = dsl.resolve_params(
                training_parameters, self.ctx.loader
            )
            try:
                x = np.asarray(params.pop("x"))
                y = np.asarray(params.pop("y"))
            except KeyError as exc:
                raise ValidationError(
                    f"trainingParameters missing {exc} for cluster fit"
                ) from exc
            validation = params.pop("validation_data", None)
            fit_kwargs = {}
            unsupported = []
            for key, val in params.items():
                if val is None or isinstance(val, (int, float, bool, str)):
                    fit_kwargs[key] = val
                else:
                    unsupported.append(key)
            if unsupported:
                raise ValidationError(
                    f"cluster fit cannot ship parameters {unsupported} "
                    f"(arrays go via x/y/validation_data; callbacks are "
                    f"local-mode only)"
                )
            # Stage data on the shared volume; every agent host mounts
            # it (deploy/: the lo-data volume / RWX claim).
            stage = self.ctx.volumes.root / "_staging" / name
            stage.mkdir(parents=True, exist_ok=True)
            try:
                np.save(stage / "x.npy", x)
                np.save(stage / "y.npy", y)
                data = {
                    "x": str(stage / "x.npy"),
                    "y": str(stage / "y.npy"),
                }
                if validation is not None:
                    vx, vy = validation
                    np.save(stage / "vx.npy", np.asarray(vx))
                    np.save(stage / "vy.npy", np.asarray(vy))
                    data["vx"] = str(stage / "vx.npy")
                    data["vy"] = str(stage / "vy.npy")

                # Fresh runs must not resurrect a previous run's
                # checkpoints (same guard as the local path); an
                # in-engine preemption retry resumes its own run's
                # checkpoints instead of re-fitting from epoch 0.
                from learningorchestra_tpu.jobs import (
                    engine as engine_mod,
                )

                attempt = engine_mod.current_attempt()
                ckdir = self.ctx.checkpoint_dir(name)
                fit_kwargs.setdefault("resume", resume_default)
                if attempt > 0:
                    fit_kwargs["resume"] = True
                if not fit_kwargs["resume"] and ckdir.exists():
                    _shutil.rmtree(ckdir, ignore_errors=True)
                fit_kwargs["checkpoint_dir"] = str(ckdir)

                job_id = submit_job(
                    coord,
                    "lo.multihost_fit",
                    {
                        "jax_coordinator": jax_coord,
                        "estimator_volume": {
                            "volume_root": str(self.ctx.volumes.root),
                            "artifact_type": parent_type,
                            "name": parent_name,
                        },
                        "compile_spec": compile_spec,
                        "mesh": _json_safe(mesh or {}),
                        "data": data,
                        "fit": fit_kwargs,
                        "out": {
                            "volume_root": str(self.ctx.volumes.root),
                            "artifact_type": artifact_type,
                            "name": name,
                        },
                    },
                    n_agents=world,
                )
                from learningorchestra_tpu.obs import (
                    tracing as obs_tracing,
                )

                t0 = time.perf_counter()
                with obs_tracing.span(
                    "cluster_fit", world=world, clusterJob=job_id
                ):
                    job = wait_job(
                        coord, job_id, timeout=cfg.job_timeout_s,
                        poll_interval=1.0,
                    )
                if job["state"] != "finished":
                    raise RuntimeError(
                        f"cluster fit {job['state']}: {job.get('errors')}"
                    )
                fit_time = time.perf_counter() - t0
            finally:
                _shutil.rmtree(stage, ignore_errors=True)
            # Epoch fence: a pre-crash straggler whose cluster job
            # outlived the orchestrator must not rewrite the history
            # rows a recovered run owns.  (The agents' binary write
            # happens on their hosts and is out of this fence's
            # reach — the engine's fenced terminal commit still stops
            # the stale metadata from publishing.)
            self.ctx.require_current_epoch()
            rank0 = job["results"].get("0") or job["results"].get(0)
            history = (rank0 or {}).get("history") or {}
            store_history_rows(self.ctx.documents, name, history)
            if session_logdir is not None:
                write_scalar_logs(session_logdir, history, prefix=name)
            return {
                "fitTime": fit_time,
                "worldSize": world,
                "clusterJob": job_id,
            }

        self.ctx.engine.submit(
            name,
            run,
            description=description
            or f"cluster distributed fit on {parent_name}",
            method="fit",
            parameters=_json_safe(training_parameters),
            on_success=lambda extra: extra,
            job_class="distributed",
        )

    # -- distributed builder --------------------------------------------------

    def create_builder(
        self,
        name: str,
        *,
        function: str,
        function_parameters: dict | None = None,
        n_workers: int | None = None,
        artifact_type: str = DISTRIBUTED_BUILDER_TYPE,
        description: str = "",
    ) -> dict:
        self.ctx.require_new_name(name)
        if not function or not isinstance(function, str):
            raise ValidationError("missing 'function' code")
        fn_name = _validate_single_function(function)
        if n_workers is None:
            world = int(self.ctx.config.dist.num_processes or 1)
        else:
            try:
                world = int(n_workers)
            except (TypeError, ValueError):
                raise ValidationError("n_workers must be an integer")
        if not 1 <= world <= MAX_BUILDER_WORKERS:
            raise ValidationError(
                f"n_workers must be in [1, {MAX_BUILDER_WORKERS}]"
            )
        meta = self.ctx.artifacts.metadata.create(
            name,
            artifact_type,
            method=fn_name,
            extra={"worldSize": world},
        )

        def run():
            params = dsl.resolve_params(
                function_parameters, self.ctx.loader
            )
            globs: dict = {"__name__": f"builder_{name}"}
            exec(compile(function, f"<builder {name}>", "exec"),  # noqa: S102
                 globs)
            fn = globs[fn_name]

            def one_rank(rank: int):
                return fn(rank=rank, world_size=world, **params)

            with concurrent.futures.ThreadPoolExecutor(world) as pool:
                results = list(pool.map(one_rank, range(world)))
            publish_object(
                self.ctx, artifact_type, name, results, replaces=False
            )
            for rank, result in enumerate(results):
                self.ctx.documents.insert_one(
                    name, {"rank": rank, "result": _json_safe(result)}
                )
            return {"worldSize": world}

        self.ctx.engine.submit(
            name,
            run,
            description=description or f"distributed builder ({world} ranks)",
            method=fn_name,
            parameters=_json_safe(function_parameters),
            on_success=lambda extra: extra,
            job_class="distributed",
        )
        return meta
