"""DistributedTrainer — the mesh-sharded training loop.

Replaces the reference's flagship distributed path (reference:
microservices/binary_executor_image/binary_execution.py:237-292 —
``RayExecutor.run(train)`` fanning a Horovod/Gloo ring over Ray workers,
rank-0 weights shipped back as lists).  Here the same request shape
(epochs / batch_size / validation, SURVEY §3.3) drives one jitted train
step over a named mesh:

- the batch enters sharded over ``(dp, fsdp)`` — each device sees its
  slice only; gradients emerge psum'd over ICI because XLA's SPMD
  partitioner sees replicated params meeting sharded data (no host ring,
  no weight serialization);
- parameters/optimizer state live sharded in HBM between steps and are
  gathered to host only at checkpoint boundaries (``jax.device_get`` at
  job edges, SURVEY §5.4);
- an epoch is one ``lax.scan`` over device-resident batches — Python
  dispatch cost is per-epoch, not per-batch (the reference pays a Ray RPC
  + Gloo rendezvous per job and Python dispatch per batch).
"""

from __future__ import annotations

import contextlib
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from learningorchestra_tpu.jobs.cancel import cancel_requested
from learningorchestra_tpu.jobs.leases import device_ids
from learningorchestra_tpu.obs import tracing as obs_tracing
from learningorchestra_tpu.parallel.mesh import MeshSpec, build_mesh
from learningorchestra_tpu.parallel.sharding import param_shardings
from learningorchestra_tpu.toolkit.base import as_array
from learningorchestra_tpu.train import compile_cache
from learningorchestra_tpu.train.neural import (
    NeuralEstimator,
    TrainHistory,
    _batch_data,
    _NoShuffle,
    build_resident_epoch_fns,
)


class DistributedTrainer:
    """Mesh-sharded fit/evaluate over a ``NeuralEstimator``'s model.

    ``batch_size`` below is the GLOBAL batch size (split across the data
    axes), matching the reference's semantics where ``model.fit`` on each
    Horovod worker saw the full user-specified batch per replica only by
    accident of num_workers=1.
    """

    def __init__(
        self,
        estimator: NeuralEstimator,
        spec: MeshSpec | None = None,
        mesh: Mesh | None = None,
        shard_sequence: bool | None = None,
    ):
        self.estimator = estimator
        self.mesh = mesh if mesh is not None else build_mesh(spec)
        if self.mesh.shape.get("pp", 1) > 1:
            # Nothing in this trainer shards over pp, so pp > 1 would
            # replicate every rank's work pp-fold with no speedup.
            raise ValueError(
                "DistributedTrainer does not use the pp axis; "
                "pipeline parallelism is parallel.pipeline."
                "PipelinedTransformer"
            )
        if shard_sequence is None:
            # Auto: an sp>1 mesh only means anything if the token axis
            # is actually sharded.
            shard_sequence = self.mesh.shape.get("sp", 1) > 1
        self.shard_sequence = shard_sequence
        self._bind_depth = 0
        # Mesh-sharded live state, re-anchored every epoch so callbacks
        # (EarlyStopping restore-best) can snapshot/replace it exactly
        # as they do on the single-device estimator.
        self.params = None
        self.opt_state = None
        # Devices the last in-memory fit's batches were placed on.
        self.batch_devices: list[str] = []
        self.history = TrainHistory()
        self._epoch_fn = None
        self._eval_fn = None
        self._loss_kind = None
        self._fn_key = None

    @contextlib.contextmanager
    def _mesh_bound(self):
        """Mesh-aware models (ring attention over sp) get the mesh bound
        for the duration of a trainer call ONLY — left bound, the
        estimator's own single-device predict/evaluate would hit
        shard_map divisibility errors on arbitrary batch shapes.

        The mesh is also jax's ambient mesh for the call
        (``jax.set_mesh``): the Pallas kernels read it to run per shard
        (ops/attention.py ``_per_shard``) — GSPMD cannot partition a
        Mosaic kernel, so without it a flash-attention model fails to
        lower on a multi-chip mesh."""
        est = self.estimator
        bindable = hasattr(est, "bind_mesh")
        if bindable and self._bind_depth == 0:
            est.bind_mesh(self.mesh)
        self._bind_depth += 1
        try:
            with jax.set_mesh(self.mesh):
                yield
        finally:
            self._bind_depth -= 1
            if bindable and self._bind_depth == 0:
                est.bind_mesh(None)

    # -- placement ----------------------------------------------------------

    @property
    def data_axes(self) -> int:
        return self.mesh.shape["dp"] * self.mesh.shape["fsdp"]

    def _data_sharding(self, ndim: int, tokens: bool) -> NamedSharding:
        """(n_batches, global_bs, ...) epoch arrays: shard the per-batch
        batch axis (1); optionally the sequence axis (2) over sp."""
        dims: list = [None, ("dp", "fsdp")]
        if (
            tokens
            and self.shard_sequence
            and ndim > 2
            and self.mesh.shape.get("sp", 1) > 1
        ):
            dims.append("sp")
        while len(dims) < ndim:
            dims.append(None)
        return NamedSharding(self.mesh, P(*dims))

    def _put_global(self, arr, sharding):
        """Host array → global sharded device array.

        Single-process: plain ``device_put``.  Multi-process (every host
        holds the full host-side value — the same convention as the
        reference, where each Horovod worker loaded the dataset;
        binary_execution.py:251-268 shipped the model the same way):
        ``make_array_from_callback`` hands each process exactly its
        addressable shards, so the global array spans all hosts' devices
        without any host ever holding more than its slice on device.
        """
        arr = np.asarray(arr)
        if jax.process_count() == 1:
            return jax.device_put(arr, sharding)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx]
        )

    def _put_tree(self, tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, sh: self._put_global(a, sh), tree, shardings
        )

    def _place_state(self) -> tuple:
        est = self.estimator
        psh = param_shardings(est.params, self.mesh)
        params = self._put_tree(jax.device_get(est.params), psh)
        # Optimizer state inherits param shardings through propagation.
        fresh = self._fresh_moments(params)
        if est.opt_state is not None and jax.tree_util.tree_structure(
            est.opt_state
        ) == jax.tree_util.tree_structure(fresh):
            # Resume accumulated moments (continuation training / PATCH
            # re-run) instead of zeroing them — same contract as the
            # single-device fit (neural.py fit resumes self.opt_state).
            mesh_devices = set(self.mesh.devices.flat)

            def _sh(leaf):
                sh = getattr(leaf, "sharding", None)
                if sh is not None and set(sh.device_set) == mesh_devices:
                    return sh
                # Scalar leaves (e.g. adam's step count) come off the init
                # jit on one device; they must be replicated on the mesh.
                return NamedSharding(self.mesh, P())

            opt_sh = jax.tree_util.tree_map(_sh, fresh)
            opt_state = self._put_tree(
                jax.device_get(est.opt_state), opt_sh
            )
        else:
            opt_state = fresh
        return params, opt_state

    def _check_seq_divisible(self, x: np.ndarray) -> None:
        """Friendly error for sequence lengths the sp axis can't shard
        (otherwise shard_map fails with an opaque divisibility error)."""
        sp = self.mesh.shape.get("sp", 1)
        if (
            self.shard_sequence and sp > 1 and x.ndim > 1
            and np.issubdtype(x.dtype, np.integer) and x.shape[1] % sp
        ):
            raise ValueError(
                f"sequence length {x.shape[1]} not divisible by sp={sp}"
            )

    # -- step construction --------------------------------------------------

    def _build(self, loss_kind: str, shuffle: bool, cost_args=None):
        est = self.estimator
        dtype = jnp.bfloat16 if est.compute_dtype == "bfloat16" else None
        # Same jitted loss/grad/update math as the single-device path
        # (train/neural.py), with the carry donated so params/opt_state
        # update in place in HBM, over a device-RESIDENT sharded dataset:
        # upload happens once per fit, each epoch permutes batch order on
        # device from a PRNG key (host traffic per epoch = key + metric
        # scalars, VERDICT r1 weak item 3).
        #
        # Resolved through the process-wide compiled-program cache,
        # keyed by mesh axis names + device assignment on top of the
        # architecture spec: a re-submitted distributed job on the SAME
        # mesh re-binds the traced program; a different mesh (or a
        # changed device set) can never serve a stale executable.
        # Mesh-aware modules (bind_mesh) carry their bound mesh as a
        # module field, so their fingerprint shifts with the binding.
        from learningorchestra_tpu.train.neural import _cached_program

        # ``cost_args`` (a shape-avatar thunk, see _cost_args below)
        # rides the build-once path into the cost plane (obs/costs.py)
        # so mesh programs land ANALYZED FLOPs/HBM ledger entries like
        # the single-device epoch programs, instead of the un-analyzed
        # fallback rows get_or_build notes on its own; ``want_cost``
        # hands the entry back for per-epoch device-time attribution.
        fns, cost = _cached_program(
            "resident_epoch_fns", est, loss_kind,
            shapes=(bool(shuffle),),
            mesh=(
                compile_cache.mesh_fingerprint(self.mesh),
                bool(self.shard_sequence),
            ),
            donate=True,
            builder=lambda: build_resident_epoch_fns(
                est.module,
                est.optimizer,
                est._loss_and_metrics(loss_kind),
                dtype,
                shuffle=shuffle,
                donate=True,
            ),
            cost_args=cost_args,
            want_cost=True,
        )
        # Same attribute the single-device fit uses, so the shared
        # span/ledger helpers (_attribute_epoch_cost,
        # _epoch_cost_attrs) see mesh fits identically.  Kept on the
        # trainer too: the fit loop re-stamps the estimator each
        # epoch, so an interleaved single-device fit can't leave its
        # own program's entry attributed to mesh epochs.
        self._epoch_cost = est._device_epoch_cost = cost
        return fns

    def _cost_args(self, x, y_arr, batch_size: int):
        """Shape-avatar thunk for the epoch program's cost probe:
        epoch(params, opt_state, xs, ys, ms, key) argument shapes,
        computed WITHOUT batching or placing anything (eval_shape for
        the moments, _batch_data's shape math for the epoch arrays).
        Lowering is global/unsharded — the ledger entry carries the
        whole mesh's per-epoch FLOPs, cross-shard collectives
        excluded."""
        import math as _math

        def thunk():
            est = self.estimator
            n = x.shape[0]
            nb = max(1, _math.ceil(n / batch_size))
            xs = jax.ShapeDtypeStruct(
                (nb, batch_size) + tuple(x.shape[1:]), x.dtype
            )
            ys = jax.ShapeDtypeStruct(
                (nb, batch_size) + tuple(y_arr.shape[1:]), y_arr.dtype
            )
            ms = jax.ShapeDtypeStruct((nb, batch_size), np.float32)
            opt_state = est.opt_state
            if opt_state is None:
                # Avatars only — nothing allocates.
                opt_state = jax.eval_shape(
                    est.optimizer.init, est.params
                )
            return (
                est.params, opt_state, xs, ys, ms,
                jax.random.PRNGKey(est.seed),
            )

        return thunk

    def _ensure_fns(self, loss_kind: str, shuffle: bool,
                    cost_args=None) -> None:
        # _opt_version (not id(optimizer)): object ids can be reused
        # after GC, which would silently serve a stale compiled step.
        key = (loss_kind, bool(shuffle),
               getattr(self.estimator, "_opt_version", 0))
        if self._epoch_fn is None or self._fn_key != key:
            self._epoch_fn, self._eval_fn = self._build(
                loss_kind, bool(shuffle), cost_args=cost_args
            )
            self._fn_key = key
            self._loss_kind = loss_kind

    def _fresh_moments(self, params):
        """Optimizer state initialized for ``params`` under jit, so
        each leaf's state inherits the param's mesh sharding through
        propagation — the ONE re-init used by state placement and the
        restore-best moments-dropped paths."""
        return jax.jit(self.estimator.optimizer.init)(params)

    def _hand_back(self, params, opt_state) -> None:
        """Trained sharded state → host pytrees on the estimator, so
        the artifact contract (any step re-executable from the stored
        binary, SURVEY §5.4) holds regardless of which path trained it.
        Multi-process: fsdp/tp shards live on other hosts — all-gather
        across processes (the rank-0-persists analogue of the reference
        returning rank-0 weights, binary_execution.py:270-272, except
        every host gets a consistent copy).  ``opt_state=None``
        (restore-best dropped the moments) passes through: the next
        fit re-inits them, matching the single-device contract."""
        est = self.estimator
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            est.params = jax.tree_util.tree_map(
                np.asarray,
                multihost_utils.process_allgather(params, tiled=True),
            )
            est.opt_state = None if opt_state is None else (
                jax.tree_util.tree_map(
                    np.asarray,
                    multihost_utils.process_allgather(
                        opt_state, tiled=True
                    ),
                )
            )
        else:
            est.params = jax.device_get(params)
            est.opt_state = (
                None if opt_state is None else jax.device_get(opt_state)
            )

    # -- public surface -----------------------------------------------------

    def fit(
        self,
        x,
        y,
        epochs: int = 1,
        batch_size: int = 64,
        validation_data: tuple | None = None,
        shuffle: bool = True,
        verbose: int = 0,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
        checkpoint_min_interval_s: float = 60.0,
        resume: bool = True,
        accumulate_steps: int = 1,
        checkpoint_async: bool = True,
        callbacks: list | None = None,
        early_stopping=None,
        **_,
    ) -> "DistributedTrainer":
        """Same managed in-loop checkpointing contract as the
        single-device ``NeuralEstimator.fit`` — sharded state gathers to
        host at save points (``jax.device_get``), so a preempted
        distributed job resumes on any mesh shape.

        ``accumulate_steps`` mirrors the single-device knob (gradient
        accumulation via optax.MultiSteps).  Set EXPLICITLY per fit: a
        prior single-device fit's accumulation never leaks in — the
        default resets to plain stepping.

        ``callbacks``/``early_stopping`` mirror the single-device
        surface: callbacks run per epoch as ``cb(epoch, metrics,
        trainer)`` and may set ``trainer.stop_training = True``.
        ``restoreBestWeights`` works here too: the best epoch's params
        are snapshotted DEVICE-SIDE as a sharded copy (``jnp.copy``
        preserves each leaf's mesh sharding — no host gather, no
        resharding) and rolled back on stop; optimizer moments are
        dropped exactly as on the single-device path (they belong to
        later epochs)."""
        from learningorchestra_tpu.train.neural import _is_sharded

        from learningorchestra_tpu.train.neural import (
            build_stop_callbacks,
        )

        callbacks = build_stop_callbacks(self, callbacks, early_stopping)
        if _is_sharded(x) or _is_sharded(y):
            return self._fit_streaming(
                x, y, epochs=epochs, batch_size=batch_size,
                validation_data=validation_data, shuffle=shuffle,
                verbose=verbose, checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
                checkpoint_min_interval_s=checkpoint_min_interval_s,
                resume=resume, accumulate_steps=accumulate_steps,
                checkpoint_async=checkpoint_async, callbacks=callbacks,
            )
        est = self.estimator
        # Explicit (re)configuration each fit: no silent inheritance of
        # a wrapper left by an earlier single-device fit, and the fn
        # cache below keys on the resulting optimizer identity.
        est._set_accumulation(accumulate_steps)
        x = np.asarray(as_array(x))
        y_arr = np.asarray(y if not hasattr(y, "to_numpy") else y.to_numpy())
        if y_arr.ndim == 2 and y_arr.shape[1] == 1:
            y_arr = y_arr.reshape(-1)
        loss_kind = est._resolve_loss(y_arr)
        y_arr = y_arr.astype(
            np.int32 if loss_kind == "softmax_ce" else np.float32
        )
        if batch_size % self.data_axes:
            raise ValueError(
                f"global batch_size {batch_size} not divisible by "
                f"dp*fsdp={self.data_axes}"
            )
        tokens = np.issubdtype(x.dtype, np.integer)
        self._check_seq_divisible(x)
        if validation_data is not None:
            self._check_seq_divisible(np.asarray(validation_data[0]))

        start_epoch = 0
        try:
            with self._mesh_bound():
                if est.params is None:
                    est._init_params(jnp.asarray(x[:1]))
                self._ensure_fns(
                    loss_kind, shuffle,
                    cost_args=self._cost_args(x, y_arr, batch_size),
                )

                params, opt_state = self._place_state()
                if checkpoint_dir and resume:
                    from learningorchestra_tpu.train import checkpoint as ckpt

                    # Sharded restore: the placed (mesh-sharded) state is the
                    # template, so orbax loads each shard straight onto its
                    # device — no host-side full-state materialization, and
                    # the saving mesh shape need not match this one.
                    loaded = ckpt.load_latest(
                        checkpoint_dir,
                        {"params": params, "opt_state": opt_state},
                    )
                    if loaded is not None:
                        state, step, past_history = loaded
                        params = state["params"]
                        opt_state = state["opt_state"]
                        self.history = TrainHistory(past_history)
                        start_epoch = step

                # Upload the epoch-batched dataset ONCE, sharded over the
                # data axes; epochs below reshuffle batch order on device.
                rng = np.random.default_rng(est.seed)
                xb, yb, mb = _batch_data(
                    x, y_arr, batch_size, rng if shuffle else _NoShuffle()
                )
                n_samples = xb.shape[0] * xb.shape[1]
                xs = self._put_global(xb, self._data_sharding(xb.ndim, tokens))
                ys = self._put_global(yb, self._data_sharding(yb.ndim, False))
                ms = self._put_global(mb, self._data_sharding(mb.ndim, False))
                self.batch_devices = device_ids(xs)
                root_key = jax.random.PRNGKey(est.seed)
                last_save = time.monotonic()
                ran = 0  # epochs executed THIS call (early stop may cut short)
                for epoch_i in range(start_epoch, epochs):
                    if cancel_requested():
                        # Engine-side cancellation (deadline watchdog
                        # or bounded shutdown drain): wind down like
                        # an early stop.
                        self.stop_training = True
                        break
                    ran += 1
                    t0 = time.perf_counter()
                    params, opt_state, metrics = self._epoch_fn(
                        params, opt_state, xs, ys, ms,
                        jax.random.fold_in(root_key, epoch_i),
                    )
                    # One host transfer for all metric scalars (replicated
                    # outputs, so this is process-local even multi-host).
                    metrics = {
                        k: float(v)
                        for k, v in jax.device_get(metrics).items()
                    }
                    dt = time.perf_counter() - t0
                    metrics["epoch_time"] = dt
                    metrics["samples_per_sec"] = n_samples / dt
                    # Device-time attribution + flops/MFU span attrs
                    # through the SAME helpers as the single-device
                    # fit (the cost probe above stamped the mesh
                    # program's ledger entry on the estimator).
                    from learningorchestra_tpu.train.neural import (
                        _attribute_epoch_cost,
                        _epoch_cost_attrs,
                    )

                    est._device_epoch_cost = getattr(
                        self, "_epoch_cost", None
                    )
                    _attribute_epoch_cost(est, dt)
                    epoch_cost_attrs = _epoch_cost_attrs(est, dt)
                    if validation_data is not None:
                        vx, vy = validation_data
                        metrics.update(
                            {
                                f"val_{k}": v
                                for k, v in self.evaluate(
                                    vx, vy, batch_size=batch_size,
                                    _params=params,
                                ).items()
                            }
                        )
                    self.history.append(metrics)
                    # Trace span per epoch (step + metric transfer +
                    # validation), same contract as the single-device
                    # fit: the job's span tree shows where the
                    # distributed fit's time went, not one opaque
                    # trainer_fit interval.  Single contextvar read
                    # when no trace is active.
                    obs_tracing.record_span(
                        "epoch", time.perf_counter() - t0,
                        epoch=epoch_i, distributed=True,
                        **epoch_cost_attrs,
                    )
                    # Callbacks run before the checkpoint decision so an
                    # early stop still gets its "final epoch" save —
                    # through the ONE shared policy (should_save).
                    # Re-anchor the live sharded state on the trainer
                    # first: EarlyStopping restore-best snapshots
                    # self.params (a device-side sharded jnp.copy) and
                    # on stop replaces it, dropping the moments.
                    self.params, self.opt_state = params, opt_state
                    for cb in callbacks or []:
                        if callable(cb):
                            cb(epoch_i, metrics, self)
                    params, opt_state = self.params, self.opt_state
                    if opt_state is None and not self.stop_training:
                        # A callback rolled params back but training
                        # continues: fresh moments for the new state.
                        opt_state = self._fresh_moments(params)
                        self.opt_state = opt_state
                    from learningorchestra_tpu.train import (
                        checkpoint as ckpt,
                    )

                    if checkpoint_dir and ckpt.should_save(
                        epoch_i, epochs, checkpoint_every,
                        checkpoint_min_interval_s, last_save,
                        stopped=self.stop_training,
                    ):
                        save_opt = opt_state
                        if save_opt is None:
                            # restore-best dropped the moments: persist
                            # the restored params with FRESH moments so
                            # resume never replays pre-restore state
                            # (same contract as the single-device fit).
                            save_opt = self._fresh_moments(params)
                        ckpt.save(
                            checkpoint_dir, epoch_i + 1,
                            {"params": params, "opt_state": save_opt},
                            history=dict(self.history),
                            async_save=checkpoint_async,
                        )
                        last_save = time.monotonic()
                    if verbose:
                        from learningorchestra_tpu.log import get_logger

                        get_logger("train").info(
                            "epoch %d/%d: %s", epoch_i + 1, epochs, metrics
                        )
                    if self.stop_training:
                        break

        finally:
            if checkpoint_dir:
                from learningorchestra_tpu.train import (
                    checkpoint as ckpt,
                )

                # The last async save must be durable when fit
                # returns — exception paths included.
                ckpt.finalize_async(checkpoint_dir)
        self._hand_back(params, opt_state)
        n_epochs = len(self.history.get("loss", ()))
        for i in range(n_epochs - ran, n_epochs):
            est.history.append(
                {k: v[i] for k, v in self.history.items() if len(v) > i}
            )
        return self

    def _fit_streaming(
        self, x, y, *, epochs, batch_size, validation_data, shuffle,
        verbose, checkpoint_dir, checkpoint_every,
        checkpoint_min_interval_s, resume, accumulate_steps,
        checkpoint_async: bool = True, callbacks: list | None = None,
    ) -> "DistributedTrainer":
        """Shard-streaming distributed fit over a beyond-RAM dataset.

        Per shard: host-side batching (fresh rng per (epoch, shard) —
        deterministic across processes, so every host computes the SAME
        batch composition, the multi-process invariant ``_put_global``
        relies on), global placement over the data axes, one resident-
        epoch call.  Shard k+1 loads and batches on an IO thread while
        the mesh runs shard k; ``_put_global`` stays on the caller
        thread (multi-controller collectives must issue in one order).
        Host memory peaks at O(shard), device memory at O(shard/dp) —
        the BASELINE config-5 shape (ResNet/ImageNet on a v4-32) that a
        whole-dataset upload can never satisfy.  Reference contract:
        database_api_image/database.py:86-151.
        """
        import concurrent.futures

        from learningorchestra_tpu.store import sharded as sh
        from learningorchestra_tpu.train.neural import _is_sharded

        if _is_sharded(validation_data):
            raise ValueError(
                "validation_data must be in-memory arrays, not sharded "
                "views"
            )
        x, y = sh.resolve_xy_views(x, y)

        est = self.estimator
        # Same column memory the single-device streaming fit records:
        # a later est.predict(bare_dataset) must select these features,
        # not the label column too.
        est._sharded_fit_cols = list(x.cols)
        est._set_accumulation(accumulate_steps)
        ds = x.dataset
        y_head = np.asarray(y.head(256))
        loss_kind = est._resolve_loss(y_head)
        y_cast = np.int32 if loss_kind == "softmax_ce" else np.float32
        if batch_size % self.data_axes:
            raise ValueError(
                f"global batch_size {batch_size} not divisible by "
                f"dp*fsdp={self.data_axes}"
            )
        self._check_seq_divisible(np.asarray(x.head(1)))

        def load(epoch_i: int, pos: int, k: int):
            # IO thread: disk → host arrays → host-side batching.  The
            # rng seeds on (epoch, shard position) so every process
            # computes identical batch composition.
            xs = x.load_shard(k)
            ys = y.load_shard(k).astype(y_cast)
            rng = (
                np.random.default_rng(
                    [est.seed, 7 + epoch_i, pos]
                ) if shuffle else _NoShuffle()
            )
            return _batch_data(xs, ys, batch_size, rng)

        start_epoch = 0
        try:
            with self._mesh_bound():
                if est.params is None:
                    est._init_params(
                        jnp.asarray(np.asarray(x.head(1), np.float32))
                    )
                self._ensure_fns(loss_kind, shuffle)
                params, opt_state = self._place_state()
                if checkpoint_dir and resume:
                    from learningorchestra_tpu.train import checkpoint as ckpt

                    loaded = ckpt.load_latest(
                        checkpoint_dir,
                        {"params": params, "opt_state": opt_state},
                    )
                    if loaded is not None:
                        state, step, past_history = loaded
                        params = state["params"]
                        opt_state = state["opt_state"]
                        self.history = TrainHistory(past_history)
                        start_epoch = step

                root_key = jax.random.PRNGKey(est.seed)
                last_save = time.monotonic()
                ran = 0  # epochs executed THIS call
                with concurrent.futures.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="shard-io"
                ) as io:
                    for epoch_i in range(start_epoch, epochs):
                        if cancel_requested():
                            # Same contract as the in-memory loop.
                            self.stop_training = True
                            break
                        ran += 1
                        t0 = time.perf_counter()
                        # Same shard order on every process.
                        order = (
                            np.random.default_rng(
                                [est.seed, 3, epoch_i]
                            ).permutation(ds.n_shards)
                            if shuffle else np.arange(ds.n_shards)
                        )
                        acc = sh.WeightedMetrics()
                        nxt = io.submit(load, epoch_i, 0, int(order[0]))
                        for pos, k in enumerate(order):
                            xb, yb, mb = nxt.result()
                            if pos + 1 < len(order):
                                nxt = io.submit(
                                    load, epoch_i, pos + 1,
                                    int(order[pos + 1]),
                                )
                            tokens = np.issubdtype(xb.dtype, np.integer)
                            params, opt_state, metrics = self._epoch_fn(
                                params, opt_state,
                                self._put_global(
                                    xb, self._data_sharding(xb.ndim, tokens)
                                ),
                                self._put_global(
                                    yb, self._data_sharding(yb.ndim, False)
                                ),
                                self._put_global(
                                    mb, self._data_sharding(mb.ndim, False)
                                ),
                                jax.random.fold_in(
                                    root_key, epoch_i * ds.n_shards + pos
                                ),
                            )
                            acc.add(
                                jax.device_get(metrics),
                                ds.shard_rows[int(k)],
                            )
                        metrics = acc.result()
                        dt = time.perf_counter() - t0
                        metrics["epoch_time"] = dt
                        metrics["samples_per_sec"] = ds.n_rows / dt
                        if validation_data is not None:
                            vx, vy = validation_data
                            metrics.update({
                                f"val_{k2}": v
                                for k2, v in self.evaluate(
                                    vx, vy, batch_size=batch_size,
                                    _params=params,
                                ).items()
                            })
                        self.history.append(metrics)
                        # Same per-epoch span as the in-memory loop;
                        # ``streaming`` marks the sharded-dataset path.
                        obs_tracing.record_span(
                            "epoch", time.perf_counter() - t0,
                            epoch=epoch_i, distributed=True,
                            streaming=True,
                        )
                        from learningorchestra_tpu.train import (
                            checkpoint as ckpt,
                        )

                        if verbose:
                            from learningorchestra_tpu.log import get_logger

                            get_logger("train").info(
                                "epoch %d/%d: %s", epoch_i + 1, epochs,
                                metrics,
                            )
                        # Re-anchor so restore-best can snapshot/replace
                        # the sharded state (see the in-memory loop).
                        self.params, self.opt_state = params, opt_state
                        for cb in callbacks or []:
                            if callable(cb):
                                cb(epoch_i, metrics, self)
                        params, opt_state = self.params, self.opt_state
                        if opt_state is None and not self.stop_training:
                            opt_state = self._fresh_moments(params)
                            self.opt_state = opt_state
                        if checkpoint_dir and ckpt.should_save(
                            epoch_i, epochs, checkpoint_every,
                            checkpoint_min_interval_s, last_save,
                            stopped=self.stop_training,
                        ):
                            save_opt = opt_state
                            if save_opt is None:
                                # restore-best: restored params persist
                                # with fresh moments (single-device
                                # contract).
                                save_opt = self._fresh_moments(params)
                            ckpt.save(
                                checkpoint_dir, epoch_i + 1,
                                {"params": params,
                                 "opt_state": save_opt},
                                history=dict(self.history),
                                async_save=checkpoint_async,
                            )
                            last_save = time.monotonic()
                        if self.stop_training:
                            break

        finally:
            if checkpoint_dir:
                from learningorchestra_tpu.train import (
                    checkpoint as ckpt,
                )

                # Durable-on-return, exception paths included.
                ckpt.finalize_async(checkpoint_dir)
        self._hand_back(params, opt_state)
        n_epochs = len(self.history.get("loss", ()))
        for i in range(n_epochs - ran, n_epochs):
            est.history.append(
                {k: v[i] for k, v in self.history.items() if len(v) > i}
            )
        return self

    def evaluate(
        self, x, y, batch_size: int = 128, _params=None, **_
    ) -> dict:
        from learningorchestra_tpu.train.neural import _is_sharded

        if _is_sharded(x) or _is_sharded(y):
            # Shard-streaming evaluate — beyond-RAM datasets never
            # materialize on host (same contract as the single-device
            # surface, neural.py::_evaluate_streaming).
            from learningorchestra_tpu.store import sharded as sh

            x, y = sh.resolve_xy_views(x, y)
            acc = sh.WeightedMetrics()
            for k in range(x.dataset.n_shards):
                xs = x.load_shard(k)
                acc.add(
                    self.evaluate(
                        xs, y.load_shard(k), batch_size=batch_size,
                        _params=_params,
                    ),
                    len(xs),
                )
            return acc.result()
        est = self.estimator
        x = np.asarray(as_array(x))
        y_arr = np.asarray(y if not hasattr(y, "to_numpy") else y.to_numpy())
        if y_arr.ndim == 2 and y_arr.shape[1] == 1:
            y_arr = y_arr.reshape(-1)
        loss_kind = self._loss_kind or est._resolve_loss(y_arr)
        y_arr = y_arr.astype(
            np.int32 if loss_kind == "softmax_ce" else np.float32
        )
        self._check_seq_divisible(x)
        with self._mesh_bound():
            if self._eval_fn is None:
                self._ensure_fns(loss_kind, shuffle=False)
            params = _params if _params is not None else est.params
            # Round up to a shardable global batch instead of erroring —
            # eval batch size is a throughput knob, not a semantic one.
            batch_size = -(-max(1, batch_size) // self.data_axes) \
                * self.data_axes
            xb, yb, mb = _batch_data(x, y_arr, batch_size, _NoShuffle())
            tokens = np.issubdtype(x.dtype, np.integer)
            metrics = self._eval_fn(
                params,
                self._put_global(xb, self._data_sharding(xb.ndim, tokens)),
                self._put_global(yb, self._data_sharding(yb.ndim, False)),
                self._put_global(mb, self._data_sharding(mb.ndim, False)),
            )
            return {k: float(v) for k, v in metrics.items()}


def distributed_fit(
    estimator: NeuralEstimator,
    x,
    y,
    *,
    mesh_spec: dict | MeshSpec | None = None,
    **fit_kwargs,
) -> NeuralEstimator:
    """One-call distributed training — the executor-service entry point for
    the reference's ``POST /train/horovod`` route (SURVEY §2.2)."""
    if isinstance(mesh_spec, dict):
        mesh_spec = MeshSpec.from_dict(mesh_spec)
    trainer = DistributedTrainer(estimator, spec=mesh_spec)
    trainer.fit(x, y, **fit_kwargs)
    return estimator
