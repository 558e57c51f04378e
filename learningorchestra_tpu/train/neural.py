"""NeuralEstimator — keras-``fit`` semantics over a jitted JAX train loop.

The reference trains keras models by calling ``model.fit(**params)`` inside
a Flask worker, with epochs/batch_size/validation_split/callbacks arriving
as request JSON (reference: microservices/binary_executor_image/
training_function/train_function.py:84-87, binary_execution.py:188-200).
This class accepts the same request shape but executes TPU-first:

- the loss/grad/update step is a single jitted function; an epoch is one
  `lax.scan` over pre-batched device-resident data — zero host round-trips
  per step (the reference pays Python dispatch per batch);
- parameters and optimizer state live in HBM between epochs; host sees them
  only at checkpoint boundaries (`jax.device_get` at job edges, SURVEY §5.4);
- compute dtype is bfloat16 by default on TPU (MXU-native), params fp32;
- the distributed (mesh-sharded) training path lives in
  ``learningorchestra_tpu.parallel`` — it reuses these loss definitions and
  shards the batch axis so XLA inserts the gradient all-reduce over ICI
  (replacing Horovod's host-side ring, reference: train_function.py:55-61).
"""

from __future__ import annotations

import functools
import re
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from learningorchestra_tpu.jobs.cancel import cancel_requested
from learningorchestra_tpu.obs import tracing as obs_tracing
from learningorchestra_tpu.toolkit.base import Estimator, as_array


def _train_logger():
    from learningorchestra_tpu.log import get_logger

    return get_logger("train")


def _faults():
    """Lazy fault-plane handle (the ``train.epoch`` chaos probe)."""
    from learningorchestra_tpu import faults

    return faults


def _spec_get(spec: dict, snake: str, default=None, *, required=False):
    """Read a spec key in snake_case OR camelCase — REST bodies use
    camelCase (vocabSize, maxLen) while Python callers write snake."""
    camel = re.sub(r"_(\w)", lambda m: m.group(1).upper(), snake)
    for key in (snake, camel):
        if key in spec:
            return spec[key]
    if required:
        raise ValueError(f"learning-rate schedule needs {snake!r}")
    return default


def resolve_learning_rate(lr):
    """A float passes through; a dict becomes an optax schedule.

    JSON-expressible schedules so the REST surface (model
    classParameters / train compile bodies, services/model.py) can
    configure warmup and decay without shipping Python — the
    reference's wrapped keras models took schedules via compile_code
    (reference: binary_executor_image/training_function/
    train_function.py:75-82); here the same knob is declarative:

        {"schedule": "warmup_cosine", "peakValue": 3e-4,
         "warmupSteps": 500, "decaySteps": 10000}

    Kinds: constant, warmup_cosine, cosine, exponential, piecewise.
    Steps are optimizer steps (one per batch), the optax convention.
    """
    if not isinstance(lr, dict):
        return float(lr)
    kind = str(lr.get("schedule", "")).lower()
    if kind in ("warmup_cosine", "warmupcosine"):
        peak = float(_spec_get(lr, "peak_value", required=True))
        return optax.warmup_cosine_decay_schedule(
            init_value=float(_spec_get(lr, "init_value", 0.0)),
            peak_value=peak,
            warmup_steps=int(_spec_get(lr, "warmup_steps", required=True)),
            decay_steps=int(_spec_get(lr, "decay_steps", required=True)),
            end_value=float(_spec_get(lr, "end_value", 0.0)),
        )
    if kind == "cosine":
        return optax.cosine_decay_schedule(
            init_value=float(_spec_get(lr, "init_value", required=True)),
            decay_steps=int(_spec_get(lr, "decay_steps", required=True)),
            alpha=float(_spec_get(lr, "alpha", 0.0)),
        )
    if kind == "exponential":
        return optax.exponential_decay(
            init_value=float(_spec_get(lr, "init_value", required=True)),
            transition_steps=int(
                _spec_get(lr, "transition_steps", required=True)
            ),
            decay_rate=float(_spec_get(lr, "decay_rate", required=True)),
            staircase=bool(_spec_get(lr, "staircase", False)),
        )
    if kind == "piecewise":
        # JSON object keys are strings; optax wants {int step: scale}.
        raw = _spec_get(lr, "boundaries_and_scales", required=True)
        return optax.piecewise_constant_schedule(
            init_value=float(_spec_get(lr, "init_value", required=True)),
            boundaries_and_scales={
                int(k): float(v) for k, v in dict(raw).items()
            },
        )
    if kind == "constant":
        return float(_spec_get(lr, "value", required=True))
    raise ValueError(
        f"unknown learning-rate schedule {lr.get('schedule')!r}; "
        "expected warmup_cosine | cosine | exponential | piecewise | "
        "constant"
    )


_OPTIMIZER_FACTORIES = {
    name: getattr(optax, name)
    for name in ("adam", "adamw", "sgd", "rmsprop", "adagrad", "lamb",
                 "lion", "novograd", "radam")
    if hasattr(optax, name)
}


def resolve_optimizer(optimizer, learning_rate=1e-3):
    """Turn a REST-expressible optimizer spec into an optax transform.

    ``optimizer`` may be: None (adam at ``learning_rate``), an optax
    object (passed through), a name string ("sgd"), or a dict
    ``{"name": "adamw", "learningRate": ..., "weightDecay": 1e-2}`` —
    extra keys forward to the optax factory (snake or camelCase); the
    learning rate itself may be a schedule spec
    (:func:`resolve_learning_rate`).
    """
    if optimizer is None:
        return optax.adam(resolve_learning_rate(learning_rate))
    if isinstance(optimizer, str):
        optimizer = {"name": optimizer}
    if not isinstance(optimizer, dict):
        return optimizer  # already an optax GradientTransformation
    spec = dict(optimizer)
    name = str(spec.pop("name", "") or "").lower()
    factory = _OPTIMIZER_FACTORIES.get(name)
    if factory is None:
        raise ValueError(
            f"unknown optimizer {name!r}; expected one of "
            f"{sorted(_OPTIMIZER_FACTORIES)}"
        )
    lr = None
    for key in ("learning_rate", "learningRate"):
        if key in spec:
            lr = spec.pop(key)
    if lr is None:
        lr = learning_rate
    kwargs = {
        re.sub(r"([A-Z])", lambda m: "_" + m.group(1).lower(), k): v
        for k, v in spec.items()
    }
    return factory(resolve_learning_rate(lr), **kwargs)


class TrainHistory(dict):
    """keras-History-shaped: {"loss": [...], "accuracy": [...], ...}."""

    def append(self, metrics: dict) -> None:
        for key, val in metrics.items():
            self.setdefault(key, []).append(float(val))


def build_stop_callbacks(owner, callbacks, early_stopping) -> list:
    """Shared fit-surface plumbing: normalize the callback list, fold
    in an ``early_stopping`` spec, reset reused EarlyStopping
    instances, and clear ``owner.stop_training``.  Every fit surface
    supports ``restoreBestWeights`` now: single-device and mesh-
    sharded fits snapshot device-side with sharding preserved
    (parallel/distributed.py), and stage-partitioned pipeline state
    snapshots leaf-by-leaf with each leaf's own placement preserved
    (:func:`snapshot_params`)."""
    owner.stop_training = False
    cbs = list(callbacks or [])
    # False is the natural JSON off-toggle mirroring True — disabled,
    # not a TypeError deep in from_spec.
    if early_stopping is not None and early_stopping is not False:
        cbs.append(EarlyStopping.from_spec(early_stopping))
    for cb in cbs:
        if isinstance(cb, EarlyStopping):
            cb.reset()
    return cbs


_SNAPSHOT_FN = None


def snapshot_params(params):
    """Device-side copy of a parameter tree for best-weights rollback.

    Eager ``jnp.copy`` rejects non-fully-addressable arrays (a
    multi-host mesh's fsdp/tp shards live on other hosts), so the copy
    runs under one cached jit PER LEAF: each leaf copies following its
    own sharding/placement, which covers host numpy trees,
    single-device arrays, global sharded arrays — and stage-PARTITIONED
    pipeline trees whose leaves are committed to different devices (a
    single whole-tree jit would reject a computation spanning devices;
    leaf-wise, every stage's weights snapshot on their own chip).
    Every process of a multi-controller fit issues the same calls in
    the same order (callbacks run the same loop on every host), the
    SPMD requirement.
    """
    global _SNAPSHOT_FN
    if _SNAPSHOT_FN is None:
        _SNAPSHOT_FN = jax.jit(jnp.copy)
    return jax.tree_util.tree_map(_SNAPSHOT_FN, params)


class EarlyStopping:
    """Keras-parity early stopping, usable as a fit callback or (as a
    JSON dict via the REST train surface) the ``early_stopping`` fit
    parameter — the reference's wrapped keras models took this via
    callback code strings (reference: binary_executor_image/
    training_function/train_function.py:75-87).

    ``monitor=None`` picks ``val_loss`` when validation runs, else
    ``loss``.  ``mode="auto"`` minimizes unless the metric name looks
    like accuracy/F1.  ``restore_best_weights=True`` snapshots the best
    epoch's params (a device-side copy — the epoch runner donates its
    input buffers, so holding a live reference would dangle)."""

    def __init__(self, monitor: str | None = None, patience: int = 0,
                 min_delta: float = 0.0, mode: str = "auto",
                 restore_best_weights: bool = False, baseline=None):
        if mode not in ("auto", "min", "max"):
            raise ValueError(f"mode must be auto|min|max, got {mode!r}")
        self.monitor = monitor
        self.patience = int(patience)
        self.min_delta = abs(float(min_delta))
        self.mode = mode
        self.restore_best_weights = bool(restore_best_weights)
        self.baseline = baseline
        self.reset()

    def reset(self) -> None:
        """Clear per-run state — fit() calls this at train start so a
        reused instance doesn't carry best/wait (or a stale best-params
        snapshot) from a previous fit into a new one."""
        self.best = None
        self.best_params = None
        self.best_epoch = None
        self.wait = 0
        self._warned_missing = False

    @classmethod
    def from_spec(cls, spec) -> "EarlyStopping":
        """Build from a REST-JSON dict (snake_case or camelCase)."""
        if isinstance(spec, cls):
            return spec
        if spec is True:
            return cls()
        spec = dict(spec)
        kw = {}
        for snake in ("monitor", "patience", "min_delta", "mode",
                      "restore_best_weights", "baseline"):
            val = _spec_get(spec, snake)
            if val is not None:
                kw[snake] = val
        return cls(**kw)

    def _resolve(self, metrics: dict) -> tuple[str, bool]:
        name = self.monitor or (
            "val_loss" if "val_loss" in metrics else "loss"
        )
        if self.mode != "auto":
            minimize = self.mode == "min"
        else:
            minimize = not any(
                tag in name for tag in ("acc", "f1", "auc", "precision",
                                        "recall")
            )
        return name, minimize

    def __call__(self, epoch: int, metrics: dict, model) -> None:
        name, minimize = self._resolve(metrics)
        if name not in metrics:
            # e.g. val_loss requested but no validation ran.  Warn once
            # (keras parity): a silent no-op reads as a broken callback
            # when training then runs every epoch (ADVICE r3).
            if not self._warned_missing:
                self._warned_missing = True
                _train_logger().warning(
                    "EarlyStopping monitor %r not in metrics %s — "
                    "early stopping is inactive this fit",
                    name, sorted(metrics),
                )
            return
        value = float(metrics[name])
        if self.best is None and self.baseline is not None:
            # keras semantics: with a baseline, the first "best" to beat
            # is the baseline itself, not the first epoch's value.
            self.best = float(self.baseline)
        improved = (
            self.best is None
            or (value < self.best - self.min_delta if minimize
                else value > self.best + self.min_delta)
        )
        if improved:
            self.best, self.best_epoch, self.wait = value, epoch, 0
            if self.restore_best_weights:
                self.best_params = snapshot_params(model.params)
        else:
            self.wait += 1
        # keras parity: patience=N stops after N consecutive
        # non-improving epochs (patience=0 → the first one).
        if self.wait >= max(1, self.patience):
            model.stop_training = True
            if self.restore_best_weights and self.best_params is not None:
                model.params = self.best_params
                model.opt_state = None  # moments belong to later epochs


def _batch_data(x: np.ndarray, y: np.ndarray, batch_size: int, rng):
    """Shuffle + pad to a whole number of batches; returns (xb, yb, mask)
    with shapes (n_batches, bs, ...).  Padding rows carry mask 0 so metrics
    and gradients ignore them — keras parity without dropping remainders."""
    n = x.shape[0]
    if n == 0:
        raise ValueError("cannot batch an empty dataset")
    perm = rng.permutation(n)
    n_batches = max(1, -(-n // batch_size))
    pad = n_batches * batch_size - n
    # np.resize cycles perm, so pad may exceed n (tiny datasets).
    idx = np.concatenate([perm, np.resize(perm, pad)]) if pad else perm
    mask = np.ones(n_batches * batch_size, np.float32)
    if pad:
        mask[n:] = 0.0
    xb = x[idx].reshape(n_batches, batch_size, *x.shape[1:])
    yb = y[idx].reshape(n_batches, batch_size, *y.shape[1:])
    mb = mask.reshape(n_batches, batch_size)
    return xb, yb, mb


def _apply_with_aux(module, p, xb):
    """Apply the module collecting sown auxiliary losses.

    Modules may ``sow('losses', name, value)`` extra differentiable
    objective terms (the MoE load-balancing loss, ops/moe.py); dense
    modules sow nothing and the collection comes back empty.  Returns
    ``(f32 logits, f32 aux-loss sum)``.
    """
    logits, var = module.apply(p, xb, mutable="losses")
    aux = jnp.asarray(0.0, jnp.float32)
    for leaf in jax.tree_util.tree_leaves(var):
        aux = aux + jnp.sum(leaf).astype(jnp.float32)
    return logits.astype(jnp.float32), aux


def _is_sharded(obj) -> bool:
    """True for sharded-dataset handles/views (and tuples holding one)
    — the dispatch predicate for the streaming fit/evaluate paths."""
    from learningorchestra_tpu.store import sharded as sh

    if isinstance(obj, (sh.ShardedDataset, sh.ShardedView)):
        return True
    if isinstance(obj, tuple):
        return any(_is_sharded(o) for o in obj)
    return False


def _finalize_metrics(metrics):
    """Batch-mean the stacked per-step metrics, then apply the
    post-reduction transforms: 'perplexity' arrives as raw per-token CE
    and becomes exp(mean CE) — exactly exp of the reported loss."""
    out = jax.tree_util.tree_map(jnp.mean, metrics)
    if "perplexity" in out:
        out["perplexity"] = jnp.exp(out["perplexity"])
    return out


def _param_cast_for(dtype):
    """Mixed precision, the TPU-standard way: the OPTIMIZER holds f32
    master weights; the forward/backward run on a low-precision COPY of
    the params cast inside the objective (so the cast is part of the
    differentiated graph and grads come back f32).

    Casting inputs alone is not enough: flax modules with
    ``dtype=None`` promote inputs against their f32 params, which
    silently pins every matmul to f32 — half MXU rate.  The MoE router
    is exempted below (full-precision weights); it also declares an
    explicit f32 compute dtype in ops/moe.py.
    """
    if dtype is None:
        return lambda p: p

    def _leaf(path, l):
        # The MoE router must see full-precision WEIGHTS, not just f32
        # compute (ops/moe.py design note: bf16-rounded router kernels
        # flip near-tied top-k choices).
        name = "/".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in path
        ).lower()
        if "router" in name:
            return l
        return l.astype(dtype) if l.dtype == jnp.float32 else l

    def cast(p):
        return jax.tree_util.tree_map_with_path(_leaf, p)

    return cast


def build_device_epoch(
    module, optimizer, loss_fn, dtype, *, n, batch_size, shuffle
):
    """Jitted whole-epoch step over a DEVICE-RESIDENT dataset.

    The dataset is uploaded once; each epoch is one jitted call that
    permutes indices on device (``jax.random.permutation``), gathers
    batches in HBM and scans the train step — host traffic per epoch is
    one PRNG key and the metrics scalars, vs. the host-side reshuffle +
    full re-upload per epoch of the generic path (the reference pays
    keras' per-batch Python dispatch on top, train_function.py:84-87).
    (params, opt_state) are donated so updates happen in place.
    """
    n_batches = max(1, -(-n // batch_size))
    pad = n_batches * batch_size - n
    _pcast = _param_cast_for(dtype)
    _cast = _cast_for(dtype)

    def epoch(params, opt_state, x, y, key):
        order = (
            jax.random.permutation(key, n) if shuffle else jnp.arange(n)
        )
        if pad:
            # np.resize-style cycling so tiny datasets (pad > n) work.
            extra = jnp.resize(order, (pad,))
            idx = jnp.concatenate([order, extra])
        else:
            idx = order
        mask = jnp.concatenate(
            [jnp.ones(n, jnp.float32), jnp.zeros(pad, jnp.float32)]
        )
        xb = x[idx].reshape(n_batches, batch_size, *x.shape[1:])
        yb = y[idx].reshape(n_batches, batch_size, *y.shape[1:])
        mb = mask.reshape(n_batches, batch_size)

        def body(carry, batch):
            params, opt_state = carry
            bx, by, bm = batch

            def objective(p):
                logits, aux = _apply_with_aux(
                    module, _pcast(p), _cast(bx)
                )
                loss, metrics = loss_fn(logits, by, bm)
                return loss + aux, metrics

            grads, metrics = jax.grad(objective, has_aux=True)(params)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), metrics

        (params, opt_state), metrics = jax.lax.scan(
            body, (params, opt_state), (xb, yb, mb)
        )
        return params, opt_state, _finalize_metrics(metrics)

    return jax.jit(epoch, donate_argnums=(0, 1))


def _cast_for(dtype):
    def _cast(xb):
        return (
            xb.astype(dtype)
            if dtype and jnp.issubdtype(xb.dtype, jnp.floating)
            else xb
        )

    return _cast


def _cached_program(
    kind: str, est, loss_kind, *, shapes=None, mesh=None, donate=None,
    builder, cost_args=None, want_cost=False,
):
    """Fetch (or build-once) a jitted program through the process-wide
    compiled-program cache (train/compile_cache.py), keyed by the
    estimator's architecture/optimizer/loss/dtype spec plus whatever
    the builder bakes into the trace.  Repeat REST jobs and
    same-architecture tune candidates skip tracing entirely.

    ``cost_args`` (a thunk returning example arguments) lets the
    build-once path run XLA cost/memory analysis on the freshly built
    program (obs/costs.py) — shape avatars only, nothing touches real
    buffers.  Builders returning a TUPLE of jitted callables (the
    (epoch, evaluate) pairs the mesh-sharded paths build) probe their
    FIRST element — the epoch program, the one that dominates device
    time.  ``want_cost=True`` returns ``(fn, ProgramCost | None)``
    so dispatch sites can attribute device time with flops attached."""
    from learningorchestra_tpu.train import compile_cache as cc

    key = cc.program_key(
        kind,
        module=cc.module_fingerprint(est.module),
        optimizer=cc.optimizer_fingerprint(est),
        loss=loss_kind,
        dtype=est.compute_dtype,
        shapes=shapes,
        mesh=mesh,
        donate=donate,
    )
    label = f"{kind}:{type(est.module).__name__}"
    building = builder
    if cost_args is not None:
        def building():
            fn = builder()
            target = fn[0] if isinstance(fn, tuple) else fn
            # Tuple-valued builders (epoch, evaluate) are not AOT-
            # eligible: a restored single executable couldn't stand in
            # for the pair the consumers unpack.
            _probe_program_cost(
                key, label, target, cost_args,
                aot_eligible=not isinstance(fn, tuple),
            )
            return fn

    fn = cc.get_cache().get_or_build(key, building, label=label)
    if not want_cost:
        return fn
    from learningorchestra_tpu.obs import costs as obs_costs

    return fn, (
        obs_costs.get_ledger().get(key)
        if obs_costs.enabled() else None
    )


def _probe_program_cost(key, label, fn, cost_args, *,
                        aot_eligible: bool = True,
                        collectives_excluded: bool = False) -> None:
    """Best-effort XLA cost analysis for a just-built program; a
    failed probe (opaque callable, exotic arg tree) must never fail
    the build it rides.  ``collectives_excluded=True`` marks probes
    whose lowering is collective-free by construction (single-device
    MPMD stage programs, host-avatar serve probes) so downstream MFU
    math knows the flops are pure compute."""
    from learningorchestra_tpu.obs import costs as obs_costs

    if not obs_costs.enabled():
        return
    try:
        obs_costs.analyze_jitted(
            key, label, fn, tuple(cost_args()),
            aot_eligible=aot_eligible,
            collectives_excluded=collectives_excluded,
        )
    except Exception:  # noqa: BLE001
        pass


def _attribute_epoch_cost(est, epoch_s: float) -> None:
    """One epoch's device interval into the per-job device-time ledger
    (the job identity rides the executor's ``costs.job_scope``)."""
    from learningorchestra_tpu.obs import costs as obs_costs

    if not obs_costs.enabled():
        return
    try:
        obs_costs.attribute(
            epoch_s, cost=getattr(est, "_device_epoch_cost", None)
        )
    except Exception:  # noqa: BLE001 — accounting never fails a fit
        pass


def _epoch_cost_attrs(est, epoch_s: float) -> dict:
    """flops/bytes/MFU span annotations for one epoch, empty when the
    program was never analyzed (failed probe, costs disabled)."""
    from learningorchestra_tpu.obs import costs as obs_costs

    cost = getattr(est, "_device_epoch_cost", None)
    if cost is None or not getattr(cost, "analyzed", False):
        return {}
    attrs: dict = {}
    if cost.flops is not None:
        attrs["flops"] = cost.flops
    if cost.bytes_accessed is not None:
        attrs["bytesAccessed"] = cost.bytes_accessed
    try:
        util = obs_costs.mfu(
            cost.flops or 0.0, epoch_s,
            peak_flops=obs_costs.peak_flops(),
        )
    except Exception:  # noqa: BLE001
        util = None
    if util is not None:
        attrs["mfu"] = util
    return attrs


def _make_step(module, optimizer, loss_fn, _cast, _pcast):
    def step(params, opt_state, xb, yb, mb):
        def objective(p):
            logits, aux = _apply_with_aux(module, _pcast(p), _cast(xb))
            loss, metrics = loss_fn(logits, yb, mb)
            return loss + aux, metrics

        grads, metrics = jax.grad(objective, has_aux=True)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, metrics

    return step


def build_epoch_fns(module, optimizer, loss_fn, dtype, *, donate=False):
    """Jitted (epoch, evaluate) pair shared by the single-device and
    mesh-sharded training paths — the loss/grad/update math exists once.

    ``donate=True`` donates the (params, opt_state) carry so updates
    happen in place in HBM (the distributed path's steady state).
    """
    _cast = _cast_for(dtype)
    _pcast = _param_cast_for(dtype)
    step = _make_step(module, optimizer, loss_fn, _cast, _pcast)

    def epoch(params, opt_state, xs, ys, ms):
        def body(carry, batch):
            params, opt_state = carry
            params, opt_state, metrics = step(params, opt_state, *batch)
            return (params, opt_state), metrics

        (params, opt_state), metrics = jax.lax.scan(
            body, (params, opt_state), (xs, ys, ms)
        )
        return params, opt_state, _finalize_metrics(metrics)

    def evaluate(params, xs, ys, ms):
        params = _pcast(params)  # same numerics (and MXU rate) as train

        def body(_, batch):
            xb, yb, mb = batch
            logits = module.apply(params, _cast(xb)).astype(jnp.float32)
            return None, loss_fn(logits, yb, mb)[1]

        _, metrics = jax.lax.scan(body, None, (xs, ys, ms))
        return _finalize_metrics(metrics)

    return (
        jax.jit(epoch, donate_argnums=(0, 1)) if donate else jax.jit(epoch),
        jax.jit(evaluate),
    )


def build_resident_epoch_fns(
    module, optimizer, loss_fn, dtype, *, shuffle, donate=True
):
    """Jitted (epoch, evaluate) over a DEVICE-RESIDENT pre-batched
    dataset — the mesh-sharded analogue of ``build_device_epoch``.

    The (n_batches, global_bs, ...) epoch arrays are uploaded (sharded)
    once per fit; each epoch is one jitted call that permutes the BATCH
    ORDER on device from a PRNG key and scans the train step.  The batch
    axis (0) is unsharded, so the permutation gather is device-local —
    no collective, no host traffic beyond the key and the metric
    scalars.  Batch *composition* is fixed by one host-side shuffle at
    upload; per-epoch reshuffling is batch-granular (the standard
    sharded-input-pipeline trade: a sample-granular reshuffle of a
    batch-sharded array would all-gather the dataset every epoch).
    """
    _cast = _cast_for(dtype)
    _pcast = _param_cast_for(dtype)
    step = _make_step(module, optimizer, loss_fn, _cast, _pcast)

    def epoch(params, opt_state, xs, ys, ms, key):
        nb = xs.shape[0]
        order = (
            jax.random.permutation(key, nb) if shuffle else jnp.arange(nb)
        )

        # Scan over the permuted INDEX vector, gathering one batch per
        # step: a whole-dataset jnp.take would materialize a second
        # full-size copy and double peak HBM — defeating the point of
        # keeping the dataset resident.
        def body(carry, i):
            params, opt_state = carry
            params, opt_state, metrics = step(
                params, opt_state, xs[i], ys[i], ms[i]
            )
            return (params, opt_state), metrics

        (params, opt_state), metrics = jax.lax.scan(
            body, (params, opt_state), order
        )
        return params, opt_state, _finalize_metrics(metrics)

    def evaluate(params, xs, ys, ms):
        params = _pcast(params)  # same numerics (and MXU rate) as train

        def body(_, batch):
            xb, yb, mb = batch
            logits = module.apply(params, _cast(xb)).astype(jnp.float32)
            return None, loss_fn(logits, yb, mb)[1]

        _, metrics = jax.lax.scan(body, None, (xs, ys, ms))
        return _finalize_metrics(metrics)

    return (
        jax.jit(epoch, donate_argnums=(0, 1)) if donate else jax.jit(epoch),
        jax.jit(evaluate),
    )


class NeuralEstimator(Estimator):
    """Wraps a Flax module with fit/evaluate/predict/save/load."""

    # The executor injects the managed checkpoint dir (and resume
    # semantics) into ``fit`` for any estimator that declares this —
    # the pipeline model mirrors the surface without subclassing.
    supports_managed_checkpoints = True

    def __init__(
        self,
        module: nn.Module,
        *,
        loss: str = "auto",  # auto | softmax_ce | sigmoid_ce | mse
        optimizer: Any = None,
        learning_rate: float = 1e-3,
        seed: int = 0,
        compute_dtype: str = "bfloat16",
    ):
        self.module = module
        self.loss = loss
        self.learning_rate = learning_rate
        self.seed = seed
        self.compute_dtype = compute_dtype
        self.optimizer = resolve_optimizer(optimizer, learning_rate)
        # Remember the declarative spec (name/dict/None=adam) so a later
        # compile(learning_rate=...) can rebuild the SAME optimizer kind;
        # an opaque optax object can't be rebuilt at a new rate.
        self._optimizer_spec = (
            optimizer if isinstance(optimizer, (str, dict))
            else ({"name": "adam"} if optimizer is None else None)
        )
        self.params = None
        self.opt_state = None
        self.stop_training = False  # callbacks may set True mid-fit
        self.history = TrainHistory()
        self._step_fn = None
        self._eval_fn = None
        self._apply_fn = None
        self._device_epoch = None
        self._device_epoch_key = None
        self._device_epoch_cost = None
        self._eval_loss_kind = None

    # -- keras-compile parity -------------------------------------------------

    def _invalidate_jit(self) -> None:
        """Drop every per-instance compiled-closure reference; the next
        fit/evaluate resolves against the current module/optimizer/loss
        configuration THROUGH the process-wide compiled-program cache
        (train/compile_cache.py) — an unchanged configuration re-binds
        the already-traced program instead of re-jitting, so this is
        cheap to call pessimistically."""
        self._step_fn = None
        self._eval_fn = None
        self._device_epoch = None
        self._device_epoch_key = None
        self._device_epoch_cost = None
        self._opt_version = getattr(self, "_opt_version", 0) + 1

    def compile(self, optimizer=None, loss: str | None = None,
                learning_rate=None, **kw) -> None:
        """Reconfigure optimizer/loss — the reference's ``compile_code``
        contract, declaratively (train_function.py:75-82).  ``optimizer``
        accepts an optax object, a name string, or a REST-JSON dict spec
        (:func:`resolve_optimizer`); ``learning_rate`` (or camelCase
        ``learningRate``) alone rebuilds the current optimizer kind at
        the new rate/schedule."""
        if learning_rate is None:
            learning_rate = kw.pop("learningRate", None)
        if optimizer is None and learning_rate is not None:
            # Rebuild the CURRENT optimizer kind at the new rate.
            # (Missing attribute = artifact pickled before this field
            # existed; those were always adam-default.)
            spec = getattr(self, "_optimizer_spec", {"name": "adam"})
            if spec is None:
                raise ValueError(
                    "current optimizer is an optax object whose rate "
                    "is baked in; pass optimizer= explicitly to "
                    "change it"
                )
            optimizer = spec
        if optimizer is not None:
            if learning_rate is not None and not isinstance(
                optimizer, (str, dict)
            ):
                raise ValueError(
                    "learning_rate is ignored for optax optimizer "
                    "objects — bake the rate into the object, or pass "
                    "a name/dict spec"
                )
            self.optimizer = resolve_optimizer(
                optimizer, learning_rate if learning_rate is not None
                else self.learning_rate,
            )
            self._optimizer_spec = (
                optimizer if isinstance(optimizer, (str, dict)) else None
            )
            if learning_rate is not None:
                self.learning_rate = learning_rate
            # A fresh base optimizer voids any accumulation wrapper and
            # any state built for the old one.
            self._base_optimizer = None
            self._accumulate_steps = 1
            if self.params is not None:
                self.opt_state = jax.jit(self.optimizer.init)(self.params)
        if loss is not None:
            self.loss = loss
        self._invalidate_jit()

    # -- loss -----------------------------------------------------------------

    def _resolve_loss(self, y: np.ndarray) -> str:
        if self.loss != "auto":
            return self.loss
        if np.issubdtype(y.dtype, np.floating) and y.ndim > 1:
            return "mse"
        if np.issubdtype(y.dtype, np.floating) and y.ndim == 1:
            return "mse"
        return "softmax_ce"

    @staticmethod
    def _loss_and_metrics(loss_kind: str) -> Callable:
        def fn(logits, y, mask):
            msum = jnp.maximum(mask.sum(), 1.0)
            if loss_kind == "softmax_ce":
                per = optax.softmax_cross_entropy_with_integer_labels(
                    logits, y
                )
                correct = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
                seq_out = per.ndim == 2
                if seq_out:
                    # Sequence outputs (language models): logits
                    # (B, T, V), y (B, T) — average over NON-PAD target
                    # tokens (pad id 0, the zoo-wide convention) so a
                    # padded batch neither trains on nor scores pad
                    # positions; the per-SAMPLE mask applies unchanged.
                    tok = (y != 0).astype(jnp.float32)
                    denom = jnp.maximum(tok.sum(-1), 1.0)
                    per = (per * tok).sum(-1) / denom
                    correct = (correct * tok).sum(-1) / denom
                loss = jnp.sum(per * mask) / msum
                acc = jnp.sum(correct * mask) / msum
                metrics = {"loss": loss, "accuracy": acc}
                if seq_out:
                    # Carry the RAW per-token CE here; the epoch/eval
                    # reducers exponentiate AFTER averaging
                    # (_finalize_metrics) — exp-then-mean would report
                    # mean-of-exponentials (Jensen-biased upward) once
                    # there is more than one batch.
                    metrics["perplexity"] = loss
                return loss, metrics
            if loss_kind == "sigmoid_ce":
                per = optax.sigmoid_binary_cross_entropy(
                    logits[..., 0], y.astype(jnp.float32)
                )
                loss = jnp.sum(per * mask) / msum
                acc = jnp.sum(
                    ((logits[..., 0] > 0) == (y > 0)).astype(jnp.float32)
                    * mask
                ) / msum
                return loss, {"loss": loss, "accuracy": acc}
            # mse
            pred = logits if logits.ndim == y.ndim else logits[..., 0]
            per = jnp.mean(
                (pred - y) ** 2, axis=tuple(range(1, pred.ndim))
            ) if pred.ndim > 1 else (pred - y) ** 2
            loss = jnp.sum(per * mask) / msum
            return loss, {"loss": loss}

        return fn

    # -- init / jit -----------------------------------------------------------

    def _init_params(self, x0: jnp.ndarray) -> None:
        rng = jax.random.PRNGKey(self.seed)
        self.params = self.module.init(rng, x0)
        self.opt_state = self.optimizer.init(self.params)

    def _set_accumulation(self, accumulate_steps: int) -> None:
        """(Un)wrap the optimizer in optax.MultiSteps; rebuilds jitted
        fns and re-shapes optimizer state when the setting changes —
        PRESERVING the inner optimizer's moments, so toggling
        accumulation mid-training does not reset Adam's warmup."""
        if accumulate_steps < 1:
            raise ValueError(
                f"accumulate_steps must be >= 1, got {accumulate_steps}"
            )
        current = getattr(self, "_accumulate_steps", 1)
        if accumulate_steps == current:
            return
        base = getattr(self, "_base_optimizer", None)
        if base is None:
            base = self.optimizer
        self._base_optimizer = base
        old_state, was_wrapped = self.opt_state, current > 1
        self.optimizer = base if accumulate_steps == 1 else \
            optax.MultiSteps(base, every_k_schedule=accumulate_steps)
        self._accumulate_steps = accumulate_steps
        self._invalidate_jit()
        if self.params is None:
            return
        if old_state is None:
            # No live moments to carry over (e.g. a restore-best early
            # stop dropped them); fit re-inits for the new optimizer.
            return
        if accumulate_steps == 1:
            # Unwrap: the inner state IS the plain optimizer's state.
            self.opt_state = old_state.inner_opt_state if was_wrapped \
                else old_state
        else:
            new_state = jax.jit(self.optimizer.init)(self.params)
            inner = old_state.inner_opt_state if was_wrapped \
                else old_state
            if inner is not None:
                new_state = new_state._replace(inner_opt_state=inner)
            self.opt_state = new_state

    def _build_step(self, loss_kind: str):
        dtype = jnp.bfloat16 if self.compute_dtype == "bfloat16" else None
        return _cached_program(
            "epoch_fns", self, loss_kind, donate=False,
            builder=lambda: build_epoch_fns(
                self.module,
                self.optimizer,
                self._loss_and_metrics(loss_kind),
                dtype,
            ),
        )

    # -- keras-fit surface ----------------------------------------------------

    def fit(
        self,
        x,
        y,
        epochs: int = 1,
        batch_size: int = 32,
        validation_split: float = 0.0,
        validation_data: tuple | None = None,
        shuffle: bool = True,
        verbose: int = 0,
        callbacks: list | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
        checkpoint_min_interval_s: float = 60.0,
        resume: bool = True,
        accumulate_steps: int = 1,
        quantize_checkpoint: bool = False,
        checkpoint_async: bool = True,
        early_stopping: dict | EarlyStopping | None = None,
        **_,
    ) -> "NeuralEstimator":
        """keras-fit surface plus managed in-loop checkpointing: with
        ``checkpoint_dir`` set, (params, opt_state) persist every
        ``checkpoint_every`` epochs — but at most once per
        ``checkpoint_min_interval_s`` (fast epochs on big models must
        not stall the loop on full-state host transfers; the final
        epoch always saves) — and an interrupted fit resumes from the
        newest checkpoint instead of epoch 0 (``resume=False`` ignores
        existing checkpoints) — the preemption story the reference
        lacks (SURVEY §5.4).

        ``accumulate_steps=N`` accumulates gradients over N batches
        before each optimizer update (``optax.MultiSteps``) — the
        effective batch is N·batch_size without N× the activation
        memory.  When the accumulated batches are all full (dataset a
        multiple of N·batch_size, per-sample masks) the N masked-mean
        grads average to the large-batch mean and trajectories match
        large-batch training to compute-dtype rounding; a padded tail
        batch (or per-token LM masks) weights each batch equally
        rather than by its mask mass.

        Beyond-RAM datasets: when x/y are sharded-dataset views
        (store/sharded.py) the fit STREAMS shards — the whole dataset
        never materializes on host or device (``_fit_streaming``).

        ``quantize_checkpoint=True`` marks the estimator so its SAVED
        artifact stores parameters int8 (ops/quant.py) with optimizer
        state dropped — a ~4-7x smaller serving binary; the live
        in-memory model keeps full precision.

        ``early_stopping`` (an :class:`EarlyStopping` or its REST-JSON
        dict spec, e.g. ``{"monitor": "val_loss", "patience": 3,
        "restoreBestWeights": true}``) stops the loop once the
        monitored metric stalls; any callback may likewise set
        ``model.stop_training = True``."""
        self._quantize_persist = bool(quantize_checkpoint)
        callbacks = build_stop_callbacks(self, callbacks, early_stopping)
        if _is_sharded(x) or _is_sharded(y):
            return self._fit_streaming(
                x, y, epochs=epochs, batch_size=batch_size,
                validation_split=validation_split,
                validation_data=validation_data, shuffle=shuffle,
                verbose=verbose, callbacks=callbacks,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
                checkpoint_min_interval_s=checkpoint_min_interval_s,
                resume=resume, accumulate_steps=accumulate_steps,
                checkpoint_async=checkpoint_async,
            )
        # ``fit_init``: everything before the first epoch's dispatch —
        # host arrays, parameter / optimizer state init, the program
        # lookup (a ``compile`` span nests here on a miss), the upload
        # of the dataset, a checkpoint resume.
        with obs_tracing.span("fit_init"):
            self._set_accumulation(accumulate_steps)
            x = np.asarray(as_array(x))
            y_arr = np.asarray(
                y if not hasattr(y, "to_numpy") else y.to_numpy()
            )
            if y_arr.ndim == 2 and y_arr.shape[1] == 1:
                y_arr = y_arr.reshape(-1)
            loss_kind = self._resolve_loss(y_arr)
            if loss_kind == "softmax_ce":
                y_arr = y_arr.astype(np.int32)
            else:
                y_arr = y_arr.astype(np.float32)

            if validation_data is None and validation_split > 0:
                n_val = int(len(x) * validation_split)
                # Tiny datasets: never let the split empty the train set; skip
                # validation instead of silently training on nothing.
                if 0 < n_val < len(x):
                    x, x_val = x[:-n_val], x[-n_val:]
                    y_arr, y_val = y_arr[:-n_val], y_arr[-n_val:]
                    validation_data = (x_val, y_val)

            if len(x) == 0:
                raise ValueError("cannot batch an empty dataset")
            if self.params is None:
                self._init_params(jnp.asarray(x[:1]))
            elif self.opt_state is None:
                # Quantized (serving) artifacts drop optimizer state;
                # continuation training re-inits moments from zero.
                self.opt_state = jax.jit(self.optimizer.init)(self.params)
            if self._eval_fn is None or self._eval_loss_kind != loss_kind:
                _, self._eval_fn = self._build_step(loss_kind)
                self._eval_loss_kind = loss_kind

            # Upload the dataset once; each epoch is one jitted call that
            # shuffles/batches on device (see build_device_epoch).
            epoch_key = (len(x), batch_size, bool(shuffle), loss_kind)
            if self._device_epoch_key != epoch_key:
                dtype = jnp.bfloat16 \
                    if self.compute_dtype == "bfloat16" else None
                self._device_epoch, self._device_epoch_cost = _cached_program(
                    "device_epoch", self, loss_kind,
                    shapes=(len(x), batch_size, bool(shuffle)),
                    builder=lambda: build_device_epoch(
                        self.module,
                        self.optimizer,
                        self._loss_and_metrics(loss_kind),
                        dtype,
                        n=len(x),
                        batch_size=batch_size,
                        shuffle=bool(shuffle),
                    ),
                    # Shape avatars for the cost probe: the whole-epoch
                    # program's flops/HBM, measured once per build.
                    cost_args=lambda: (
                        self.params, self.opt_state, x, y_arr,
                        jax.random.PRNGKey(self.seed),
                    ),
                    want_cost=True,
                )
                self._device_epoch_key = epoch_key
            xs = jnp.asarray(x)
            ys = jnp.asarray(y_arr)
            root_key = jax.random.PRNGKey(self.seed)

            start_epoch = 0
            if checkpoint_dir and resume:
                from learningorchestra_tpu.train import checkpoint as ckpt

                loaded = ckpt.resume_or_none(
                    checkpoint_dir,
                    {"params": self.params, "opt_state": self.opt_state},
                )
                if loaded is not None:
                    state, step, past_history = loaded
                    self.params = state["params"]
                    self.opt_state = state["opt_state"]
                    self.history = TrainHistory(past_history)
                    start_epoch = step

        from learningorchestra_tpu.train import checkpoint as ckpt_mod

        params, opt_state = self.params, self.opt_state
        last_save = time.monotonic()
        try:
            for epoch_i in range(start_epoch, epochs):
                if cancel_requested():
                    # Engine-side cancellation (deadline watchdog or
                    # bounded shutdown drain): wind down exactly like
                    # an early stop — params/history stay consistent
                    # at the last completed epoch.
                    self.stop_training = True
                    break
                t0 = time.perf_counter()
                # Chaos probe per epoch: an armed ``preempt`` schedule
                # models the real TPU event — mid-fit, after some
                # checkpoints committed — so the engine-retry →
                # checkpoint-resume path is provable end to end.  Ahead
                # of the span: a preempted epoch trained nothing and
                # leaves none.
                _faults().hit("train.epoch")
                # Trace span per epoch (train step + validation): the
                # job's span tree shows exactly where fit time went —
                # annotated at its end with the program's measured
                # flops/bytes and achieved-vs-peak utilization, so a
                # trace answers "what was the hardware doing" per epoch.
                with obs_tracing.span("epoch", epoch=epoch_i):
                    params, opt_state, metrics = self._device_epoch(
                        params, opt_state, xs, ys,
                        jax.random.fold_in(root_key, epoch_i),
                    )
                    # Re-anchor the estimator each epoch: the epoch call
                    # donates its (params, opt_state) arguments, so a
                    # raise from a callback/validation below must not
                    # strand self.params on deleted buffers.
                    self.params, self.opt_state = params, opt_state
                    # ONE host transfer for all metric scalars — per-metric
                    # float() pays a device round-trip each.
                    metrics = {
                        k: float(v) for k, v in jax.device_get(metrics).items()
                    }
                    metrics["epoch_time"] = time.perf_counter() - t0
                    # Device-time attribution (obs/costs.py): the metrics
                    # device_get above synced the dispatch, so epoch_time
                    # IS the device interval; the program's measured flops
                    # ride along, giving the per-job ledger (and the MFU
                    # gauge) real numerators.  One config check when the
                    # costs plane is off.
                    _attribute_epoch_cost(self, metrics["epoch_time"])
                    if validation_data is not None:
                        vx, vy = validation_data
                        vy = np.asarray(vy)
                        # Only flatten single-column matrices —
                        # sequence targets (B, T) keep their shape (the
                        # LM loss path).
                        if vy.ndim == 2 and vy.shape[1] == 1:
                            vy = vy.reshape(-1)
                        vmetrics = self._evaluate_arrays(
                            params, np.asarray(as_array(vx)), vy,
                            batch_size, loss_kind,
                        )
                        metrics.update(
                            {f"val_{k}": v for k, v in vmetrics.items()}
                        )
                    self.history.append(metrics)
                    obs_tracing.set_span_attrs(
                        **_epoch_cost_attrs(self, metrics["epoch_time"])
                    )
                if verbose:
                    _train_logger().info(
                        "epoch %d/%d: %s", epoch_i + 1, epochs, metrics
                    )
                # Callbacks run BEFORE the save decision so an early
                # stop counts as the final epoch under the one shared
                # policy (should_save stopped=...).
                for cb in callbacks or []:
                    if callable(cb):
                        cb(epoch_i, metrics, self)
                if checkpoint_dir and ckpt_mod.should_save(
                            epoch_i, epochs, checkpoint_every,
                            checkpoint_min_interval_s, last_save,
                            stopped=self.stop_training,
                        ):
                    from learningorchestra_tpu.train import checkpoint as ckpt

                    opt_state = self.opt_state
                    if opt_state is None:
                        # restore-best dropped the moments: checkpoint
                        # the restored params with FRESH moments, else
                        # resume=True would replay the last periodic
                        # save's pre-restore params (ADVICE r3).
                        opt_state = jax.jit(self.optimizer.init)(
                            self.params
                        )
                    with obs_tracing.span(
                        "checkpoint_save", step=epoch_i + 1
                    ):
                        ckpt.save(
                            checkpoint_dir, epoch_i + 1,
                            {"params": self.params,
                             "opt_state": opt_state},
                            history=dict(self.history),
                            async_save=checkpoint_async,
                        )
                    last_save = time.monotonic()
                if self.stop_training:
                    # A callback (e.g. EarlyStopping) may have replaced
                    # self.params with a restored snapshot — the loop's
                    # own re-anchor above already covered the normal
                    # path, so just stop; do NOT re-assign below.
                    if verbose:
                        _train_logger().info(
                            "early stop after epoch %d", epoch_i + 1
                        )
                    break
        finally:
            if checkpoint_dir:
                # The last async save must be durable when fit returns
                # (and an exception mid-loop must not strand a pending
                # write unpublished for a later fit in this process).
                with obs_tracing.span("checkpoint_save", finalize=True):
                    ckpt_mod.finalize_async(checkpoint_dir)
        return self

    def _fit_streaming(
        self, x, y, *, epochs, batch_size, validation_split,
        validation_data, shuffle, verbose, callbacks, checkpoint_dir,
        checkpoint_every, checkpoint_min_interval_s, resume,
        accumulate_steps, checkpoint_async: bool = True,
    ) -> "NeuralEstimator":
        """Shard-streaming fit over a beyond-host-RAM dataset.

        Contract parity with the in-memory path (same managed
        checkpointing, history, callbacks); mechanics differ where the
        data layout forces it:

        - x/y are views over ONE sharded dataset (x may be the bare
          dataset: it resolves to every column except y's — the
          ``fit(x="$big", y="$big.label")`` request shape);
        - each epoch walks shards in a fresh host-side order; rows
          reshuffle on device WITHIN a shard (store/sharded.py module
          docstring covers the shuffle-granularity trade);
        - shard k+1 loads from disk on an IO thread and starts its
          host→device transfer while the device computes on shard k —
          JAX's async dispatch overlaps them without explicit streams;
        - ``validation_split`` is unsupported (a fractional split of a
          stream would pin an arbitrary shard subset); pass
          ``validation_data`` arrays instead.

        The optimizer step count differs from the in-memory path only
        in batch boundaries at shard edges (each shard's tail batch
        pads, exactly like the in-memory tail).  Reference contract:
        database_api_image/database.py:86-151 (stream-ingest + read-back
        training, the one reference capability round 2 lacked).
        """
        import concurrent.futures

        from learningorchestra_tpu.store import sharded as sh

        if validation_split:
            raise ValueError(
                "validation_split is unsupported for sharded datasets; "
                "pass validation_data=(x, y) arrays"
            )
        if _is_sharded(validation_data):
            raise ValueError(
                "validation_data must be in-memory arrays, not sharded "
                "views (validation sets are small by construction)"
            )
        # ``fit_init``, as in the in-memory path; the shard programs
        # are looked up at their first use, inside the first epoch.
        with obs_tracing.span("fit_init"):
            x, y = sh.resolve_xy_views(x, y)
            # Remember the feature columns so a later predict on the BARE
            # dataset ("x": "$big") selects the same features instead of
            # accidentally feeding the label column too.
            self._sharded_fit_cols = list(x.cols)
            self._set_accumulation(accumulate_steps)

            ds = x.dataset
            y_head = np.asarray(y.head(256))
            loss_kind = self._resolve_loss(y_head)
            y_cast = np.int32 if loss_kind == "softmax_ce" else np.float32
            x_head = np.asarray(x.head(1), np.float32)
            if self.params is None:
                self._init_params(jnp.asarray(x_head))
            elif self.opt_state is None:
                # Quantized (serving) artifacts drop optimizer state.
                self.opt_state = jax.jit(self.optimizer.init)(self.params)
            if self._eval_fn is None or self._eval_loss_kind != loss_kind:
                _, self._eval_fn = self._build_step(loss_kind)
                self._eval_loss_kind = loss_kind

            dtype = jnp.bfloat16 if self.compute_dtype == "bfloat16" else None
            loss_fn = self._loss_and_metrics(loss_kind)
            epoch_fns: dict[int, Any] = {}

            def fn_for(rows: int):
                # One compilation per distinct shard length — all full
                # shards share one executable; the tail adds a second.
                # Resolved through the process-wide cache so a re-submitted
                # streaming job (same dataset, same shard layout) skips
                # every trace.
                if rows not in epoch_fns:
                    epoch_fns[rows] = _cached_program(
                        "device_epoch", self, loss_kind,
                        shapes=(rows, min(batch_size, rows), bool(shuffle)),
                        builder=lambda: build_device_epoch(
                            self.module, self.optimizer, loss_fn, dtype,
                            n=rows, batch_size=min(batch_size, rows),
                            shuffle=bool(shuffle),
                        ),
                    )
                return epoch_fns[rows]

            def load(k: int):
                # IO thread: disk → host arrays → START the async H2D copy.
                # Dtypes pass through exactly as the in-memory path's
                # as_array does (int features stay int — token models).
                xs = x.load_shard(k)
                ys = y.load_shard(k).astype(y_cast)
                return jax.device_put(xs), jax.device_put(ys)

            start_epoch = 0
            if checkpoint_dir and resume:
                from learningorchestra_tpu.train import checkpoint as ckpt

                loaded = ckpt.resume_or_none(
                    checkpoint_dir,
                    {"params": self.params, "opt_state": self.opt_state},
                )
                if loaded is not None:
                    state, step, past_history = loaded
                    self.params = state["params"]
                    self.opt_state = state["opt_state"]
                    self.history = TrainHistory(past_history)
                    start_epoch = step

        from learningorchestra_tpu.train import checkpoint as ckpt_mod

        params, opt_state = self.params, self.opt_state
        root_key = jax.random.PRNGKey(self.seed)
        last_save = time.monotonic()
        try:
            with concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="shard-io"
            ) as io:
                for epoch_i in range(start_epoch, epochs):
                    if cancel_requested():
                        # Same contract as the in-memory loop.
                        self.stop_training = True
                        break
                    t0 = time.perf_counter()
                    _faults().hit("train.epoch")  # see in-memory loop
                    with obs_tracing.span(
                        "epoch", epoch=epoch_i, streaming=True
                    ):
                        # Seeded per (seed, epoch), NOT once per fit: a
                        # checkpoint-resumed epoch 6 must walk the same shard
                        # order the uninterrupted run would have (and the
                        # distributed path already does — one convention).
                        order = (
                            np.random.default_rng(
                                [self.seed, 3, epoch_i]
                            ).permutation(ds.n_shards) if shuffle
                            else np.arange(ds.n_shards)
                        )
                        acc = sh.WeightedMetrics()
                        nxt = io.submit(load, int(order[0]))
                        for pos, k in enumerate(order):
                            xs, ys = nxt.result()
                            if pos + 1 < len(order):
                                nxt = io.submit(load, int(order[pos + 1]))
                            rows = ds.shard_rows[int(k)]
                            params, opt_state, metrics = fn_for(rows)(
                                params, opt_state, xs, ys,
                                jax.random.fold_in(
                                    root_key, epoch_i * ds.n_shards + pos
                                ),
                            )
                            # Re-anchor every shard: the epoch fn donates its
                            # state, so an interrupt must not strand
                            # self.params on deleted buffers.
                            self.params, self.opt_state = params, opt_state
                            acc.add(jax.device_get(metrics), rows)
                        metrics = acc.result()
                        metrics["epoch_time"] = time.perf_counter() - t0
                        if validation_data is not None:
                            vx, vy = validation_data
                            vy = np.asarray(vy)
                            if vy.ndim == 2 and vy.shape[1] == 1:
                                vy = vy.reshape(-1)
                            vmetrics = self._evaluate_arrays(
                                params, np.asarray(as_array(vx)), vy,
                                batch_size, loss_kind,
                            )
                            metrics.update(
                                {f"val_{k2}": v for k2, v in vmetrics.items()}
                            )
                        self.history.append(metrics)
                    if verbose:
                        _train_logger().info(
                            "epoch %d/%d: %s", epoch_i + 1, epochs, metrics
                        )
                    for cb in callbacks or []:
                        if callable(cb):
                            cb(epoch_i, metrics, self)
                    if checkpoint_dir and ckpt_mod.should_save(
                                epoch_i, epochs, checkpoint_every,
                                checkpoint_min_interval_s, last_save,
                                stopped=self.stop_training,
                            ):
                        from learningorchestra_tpu.train import (
                            checkpoint as ckpt,
                        )

                        opt_state = self.opt_state
                        if opt_state is None:
                            # restore-best: fresh moments for the
                            # restored params (see in-memory loop).
                            opt_state = jax.jit(self.optimizer.init)(
                                self.params
                            )
                        with obs_tracing.span(
                            "checkpoint_save", step=epoch_i + 1
                        ):
                            ckpt.save(
                                checkpoint_dir, epoch_i + 1,
                                {"params": self.params,
                                 "opt_state": opt_state},
                                history=dict(self.history),
                                async_save=checkpoint_async,
                            )
                        last_save = time.monotonic()
                    if self.stop_training:
                        # Per-shard re-anchor above already synced
                        # self.params; a callback may have replaced it
                        # (restore-best), so don't re-assign below.
                        if verbose:
                            _train_logger().info(
                                "early stop after epoch %d", epoch_i + 1
                            )
                        break
        finally:
            if checkpoint_dir:
                # Same durability contract as the in-memory
                # loop, incl. the exception path.
                with obs_tracing.span("checkpoint_save", finalize=True):
                    ckpt_mod.finalize_async(checkpoint_dir)
        return self

    def _evaluate_arrays(self, params, x, y, batch_size, loss_kind):
        if loss_kind == "softmax_ce":
            y = y.astype(np.int32)
        else:
            y = y.astype(np.float32)
        xb, yb, mb = _batch_data(x, y, batch_size, _NoShuffle())
        metrics = self._eval_fn(
            params, jnp.asarray(xb), jnp.asarray(yb), jnp.asarray(mb)
        )
        return {k: float(v) for k, v in metrics.items()}

    def evaluate(self, x, y, batch_size: int = 128, **_) -> dict:
        if _is_sharded(x) or _is_sharded(y):
            return self._evaluate_streaming(x, y, batch_size)
        x = np.asarray(as_array(x))
        y = np.asarray(y if not hasattr(y, "to_numpy") else y.to_numpy())
        # Only flatten a single-column matrix; multi-output regression
        # targets (n, k>1) must keep their shape.
        if y.ndim == 2 and y.shape[1] == 1:
            y = y.reshape(-1)
        loss_kind = self._resolve_loss(y)
        if self._eval_fn is None or self._eval_loss_kind != loss_kind:
            if self.params is None:
                raise RuntimeError("evaluate() before fit()")
            self._step_fn, self._eval_fn = self._build_step(loss_kind)
            self._eval_loss_kind = loss_kind
        return self._evaluate_arrays(
            self.params, x, y, batch_size, loss_kind
        )

    def _evaluate_streaming(self, x, y, batch_size: int) -> dict:
        """Shard-streaming evaluate (same x/y resolution as
        ``_fit_streaming``); metrics are row-weighted across shards,
        perplexity averaged in log domain (exp-after-mean)."""
        from learningorchestra_tpu.store import sharded as sh

        x, y = sh.resolve_xy_views(x, y)
        if self.params is None:
            raise RuntimeError("evaluate() before fit()")
        loss_kind = self._resolve_loss(np.asarray(y.head(256)))
        if self._eval_fn is None or self._eval_loss_kind != loss_kind:
            self._step_fn, self._eval_fn = self._build_step(loss_kind)
            self._eval_loss_kind = loss_kind
        ds = x.dataset
        acc = sh.WeightedMetrics()
        for k in range(ds.n_shards):
            acc.add(
                self._evaluate_arrays(
                    self.params, x.load_shard(k), y.load_shard(k),
                    batch_size, loss_kind,
                ),
                ds.shard_rows[k],
            )
        return acc.result()

    def predict(self, x, batch_size: int = 512, **_):
        if _is_sharded(x):
            # Stream shards; the OUTPUT still materializes (n_rows,
            # out_dim) on host — logits/classes are orders of magnitude
            # smaller than beyond-RAM features, but callers with huge
            # row counts should predict per shard view themselves.
            from learningorchestra_tpu.store import sharded as sh

            if isinstance(x, sh.ShardedDataset):
                # Bare dataset: prefer the columns the streaming fit
                # trained on (they exclude the label); otherwise all.
                cols = getattr(self, "_sharded_fit_cols", None)
                if cols and all(c in x.fields for c in cols):
                    # Always the LIST form: a one-element list keeps
                    # the (rows, 1) matrix shape fit trained on
                    # (ShardedView collapses only tensor columns).
                    view = x.view(cols)
                else:
                    view = x.view(x.fields)
            else:
                view = x
            # Dtype passes through untouched — int token columns must
            # stay int for embedding lookups, same as the fit loader.
            return np.concatenate([
                self.predict(view.load_shard(k), batch_size)
                for k in range(view.dataset.n_shards)
            ], axis=0)
        from learningorchestra_tpu.serve.bucketing import (
            bucket_for,
            pad_rows,
        )

        x = np.asarray(as_array(x))
        outs = []
        for i in range(0, len(x), batch_size):
            xb = x[i:i + batch_size]
            k = xb.shape[0]
            # The ragged final slice used to dispatch at its own shape,
            # so EVERY distinct tail length re-traced and re-compiled
            # apply.  Pad it up to its power-of-two bucket (capped at
            # batch_size — full batches dispatch at batch_size exactly)
            # and slice the pad rows off the output: compile count is
            # bounded by the bucket set, never by tail diversity.  Same
            # helper and discipline as the serving path (serve/).
            bucket = bucket_for(k, batch_size)
            padded = jnp.asarray(
                pad_rows(xb, bucket) if k != bucket else xb
            )
            out = np.asarray(
                self._apply_for(bucket, example=padded)(
                    self.params, padded,
                )
            )
            outs.append(out[:k] if k != bucket else out)
        return np.concatenate(outs, axis=0)

    def _apply_for(self, rows: int, example=None):
        """Cache-resolved jitted ``apply`` for a ``rows``-row input.

        Keyed through :func:`compile_cache.apply_program_key` —
        optimizer/loss play no part in inference, and ``rows`` is the
        shape-bucket dimension, so every predict job AND the serving
        path share one executable per (architecture, bucket) and the
        cache's miss counter counts buckets, not calls.  ``example``
        (a bucket-shaped input) lets a first build run the cost probe
        — the same ProgramCost the serving path attributes against."""
        fns = getattr(self, "_apply_fns", None)
        if fns is None:
            fns = self._apply_fns = {}
        fn = fns.get(rows)
        if fn is None:
            from learningorchestra_tpu.train import compile_cache as cc

            key = cc.apply_program_key(self.module, rows=rows)
            label = f"apply:{type(self.module).__name__}:b{rows}"

            def builder():
                jitted = jax.jit(self.module.apply)
                if example is not None and self.params is not None:
                    _probe_program_cost(
                        key, label, jitted,
                        lambda: (self.params, example),
                    )
                return jitted

            fn = fns[rows] = cc.get_cache().get_or_build(
                key, builder, label=label
            )
        return fn

    def predict_classes(self, x, batch_size: int = 512):
        return np.argmax(self.predict(x, batch_size), axis=-1)

    def score(self, x, y) -> float:
        return float(self.evaluate(x, y).get("accuracy", 0.0))

    # -- persistence (pytree checkpoint; see store/volumes.py) ---------------

    def state_dict(self, *, quantize: bool = False) -> dict:
        """``quantize=True`` stores large parameter tensors int8
        (ops/quant.py row-wise format, ~4x smaller) and DROPS the
        optimizer state — a quantized artifact is a serving/inference
        binary; continuation training re-inits moments."""
        extras = {
            "history": dict(self.history),
            "accumulate_steps": getattr(self, "_accumulate_steps", 1),
            # Feature-column memory for bare-sharded-dataset predict;
            # must survive persistence or the restored model reverts
            # to feeding the label column.
            "sharded_fit_cols": getattr(
                self, "_sharded_fit_cols", None
            ),
        }
        if quantize:
            from learningorchestra_tpu.ops.quant import quantize_pytree

            return {
                "params": quantize_pytree(jax.device_get(self.params)),
                "opt_state": None,
                **extras,
            }
        return {
            "params": jax.device_get(self.params),
            "opt_state": jax.device_get(self.opt_state),
            **extras,
        }

    def load_state_dict(self, state: dict) -> None:
        from learningorchestra_tpu.ops.layers import (
            has_separate_qkv,
            migrate_separate_qkv,
        )
        from learningorchestra_tpu.ops.quant import (
            dequantize_pytree,
            has_quantized_leaves,
        )

        params = state["params"]
        if params is not None and has_quantized_leaves(params):
            params = dequantize_pytree(params)
        if params is not None and has_separate_qkv(params):
            # Legacy separate-projection artifact meeting the fused
            # default: block-stack into the qkv layout (bit-identical
            # outputs).  fused_qkv=False models keep their layout by
            # initializing params before loading.
            if self.params is None or not has_separate_qkv(self.params):
                params = migrate_separate_qkv(params)
        self.params = params
        # Restore the accumulation wrapper FIRST so the optimizer and
        # the restored opt_state structure agree (a MultiSteps state
        # under a plain optimizer crashes deep inside the jitted scan).
        self._set_accumulation(state.get("accumulate_steps", 1))
        self.opt_state = state["opt_state"]
        self.history = TrainHistory(state.get("history", {}))
        cols = state.get("sharded_fit_cols")
        if cols:
            self._sharded_fit_cols = list(cols)

    def __getstate__(self):
        """dill support: drop jitted closures, keep module + host arrays.

        With ``self._quantize_persist`` set (the train request's
        ``quantize_checkpoint``), large parameter tensors persist int8
        and the optimizer state is dropped — the artifact path's
        quantized binary format."""
        d = dict(self.__dict__)
        d.pop("_decode_fns", None)  # jitted decode scans (GreedyDecodeMixin)
        d["_step_fn"] = None
        d["_eval_fn"] = None
        d["_apply_fn"] = None
        d.pop("_apply_fns", None)  # per-bucket jitted applies
        d["_device_epoch"] = None
        d["_device_epoch_key"] = None
        d["_device_epoch_cost"] = None
        d["params"] = jax.device_get(d["params"]) if d["params"] is not None \
            else None
        d["opt_state"] = jax.device_get(d["opt_state"]) \
            if d["opt_state"] is not None else None
        if d.get("_quantize_persist") and d["params"] is not None:
            from learningorchestra_tpu.ops.quant import quantize_pytree

            d["params"] = quantize_pytree(d["params"])
            d["opt_state"] = None
        return d

    def __setstate__(self, state):
        from learningorchestra_tpu.ops.quant import (
            dequantize_pytree,
            has_quantized_leaves,
        )

        if state.get("params") is not None and has_quantized_leaves(
            state["params"]
        ):
            state = dict(state)
            state["params"] = dequantize_pytree(state["params"])
        # No qkv migration here: a dill'd instance carries its OWN
        # module (with its fused_qkv setting), so its params always
        # match — only load_state_dict crosses layout versions.
        self.__dict__.update(state)


class _NoShuffle:
    """Identity 'rng' for deterministic batching."""

    def permutation(self, n: int):
        return np.arange(n)
