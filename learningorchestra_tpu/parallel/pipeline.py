"""Pipeline parallelism: GPipe-style microbatched stage execution.

Fills the ``pp`` mesh axis (parallel/mesh.py).  The reference scales
only by replicating whole workers (Ray replicas / Horovod rings —
reference: docker-compose.yml:329-347, binary_executor_image/
binary_execution.py:237-292); it has no way to run a model larger than
one worker's memory.  Pipeline stages are the TPU-native answer: layer
stages shard over ``pp``, microbatches stream through the stages, and
activations hop between ICI neighbours via ``ppermute``.

TPU-first design:

- **SPMD, not a scheduler.**  One program runs on every device; the
  stage index is ``lax.axis_index('pp')``.  The GPipe schedule is a
  static loop of ``n_micro + pp - 1`` ticks — every tick each stage
  applies itself to its current microbatch and ``ppermute``s the
  activation to its ICI neighbour.  No host round-trips, no per-stage
  processes: the whole pipeline (fwd + bwd + optimizer) is ONE jitted
  step.
- **Backward for free.**  ``jax.grad`` through ``ppermute`` transposes
  to the reverse permutation, so the backward pipeline (activations
  flowing last→first stage) falls out of AD — no hand-written reverse
  schedule.
- **Bubble accounting.**  Utilisation is n_micro/(n_micro + pp - 1);
  the default n_micro = 2·pp keeps the bubble ≤ 33%.  Stage params are
  stacked ``(pp, ...)`` and sharded ``P('pp')`` so per-device memory is
  layers/pp of the trunk — the model-size axis dp cannot buy.

``sequential_loss`` runs the mathematically identical computation
without the mesh — the oracle the tests pin the schedule against.
"""

from __future__ import annotations

import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from learningorchestra_tpu.jobs.cancel import cancel_requested
from learningorchestra_tpu.parallel.mesh import MeshSpec, build_mesh
from learningorchestra_tpu.toolkit.registry import register
from learningorchestra_tpu.train.neural import (
    NeuralEstimator,
    TrainHistory,
)

_MODULE = "learningorchestra_tpu.parallel.pipeline"


class _Embed(nn.Module):
    vocab_size: int
    hidden_dim: int
    max_len: int
    dtype: Any = None  # None = promote (bf16 when the step casts params)

    @nn.compact
    def __call__(self, tokens):
        from learningorchestra_tpu.models.text import embed_tokens

        return embed_tokens(
            tokens.astype(jnp.int32), self.vocab_size, self.hidden_dim,
            self.max_len, self.dtype,
        )


class _Stage(nn.Module):
    """``layers_per_stage`` transformer blocks — the unit one pp rank
    owns.  Every stage has identical structure, so stage params stack
    into one pytree with a leading (pp,) axis sharded over the mesh."""

    hidden_dim: int
    num_heads: int
    mlp_dim: int
    layers_per_stage: int
    causal: bool
    dtype: Any = None  # None = promote (bf16 when the step casts params)

    @nn.compact
    def __call__(self, x, key_mask):
        from learningorchestra_tpu.models.text import TransformerBlock

        for i in range(self.layers_per_stage):
            x = TransformerBlock(
                hidden_dim=self.hidden_dim,
                num_heads=self.num_heads,
                mlp_dim=self.mlp_dim,
                dtype=self.dtype,
                causal=self.causal,
                name=f"TransformerBlock_{i}",
            )(x, key_mask=key_mask)
        return x


class _Head(nn.Module):
    hidden_dim: int
    out_dim: int
    kind: str  # 'cls' | 'lm'

    @nn.compact
    def __call__(self, h):
        from learningorchestra_tpu.models.text import cls_head

        h = nn.LayerNorm()(h)
        if self.kind == "lm":
            return nn.Dense(self.out_dim)(h)
        return cls_head(h, self.hidden_dim, self.out_dim)


def gpipe_loss(
    embed_apply,
    stage_apply,
    head_apply,
    loss_fn,
    *,
    n_stages: int,
    n_micro: int,
    axis: str = "pp",
):
    """Per-device GPipe loss for use inside ``shard_map``.

    ``stage_params`` arrives with its (pp,) leading axis already
    sharded away (shape ``(1, ...)``); inputs are this dp-shard's
    batch, replicated across ``pp``.  Returns the pipeline loss psum'd
    to every rank.
    """

    def fn(eparams, sparams, hparams, xb, yb, mb):
        sparams = jax.tree_util.tree_map(lambda l: l[0], sparams)
        idx = lax.axis_index(axis)
        mb_sz = xb.shape[0] // n_micro
        xm = xb.reshape(n_micro, mb_sz, *xb.shape[1:])
        ym = yb.reshape(n_micro, mb_sz, *yb.shape[1:])
        mm = mb.reshape(n_micro, mb_sz)
        key_masks = xm != 0  # (M, mb, T) pad id 0

        # Every rank embeds every microbatch; only rank 0's embedding
        # feeds the pipeline (others get zero cotangent, so embed grads
        # stay correct after the psum below).  Trades pp-1 redundant
        # embed lookups for zero cross-stage plumbing of raw tokens.
        emb = jax.vmap(lambda t: embed_apply(eparams, t))(xm)

        recv = jnp.zeros_like(emb[0])
        outs = []
        right = [(i, i + 1) for i in range(n_stages - 1)]
        for t in range(n_micro + n_stages - 1):
            # Stage s processes microbatch (t - s) at tick t.
            mi = jnp.clip(t - idx, 0, n_micro - 1)
            x_in = jnp.where(idx == 0, emb[jnp.clip(t, 0, n_micro - 1)],
                             recv)
            out = stage_apply(sparams, x_in, key_masks[mi])
            if t >= n_stages - 1:
                outs.append(out)
            if right:
                recv = lax.ppermute(out, axis, right)

        # outs[j] on the LAST rank is microbatch j's trunk output.
        h = jnp.stack(outs)  # (M, mb, T, H)
        logits = jax.vmap(lambda hh: head_apply(hparams, hh))(h)
        flat_logits = logits.reshape(n_micro * mb_sz, *logits.shape[2:])
        flat_y = ym.reshape(n_micro * mb_sz, *ym.shape[2:])
        flat_m = mm.reshape(n_micro * mb_sz)
        loss, metrics = loss_fn(
            flat_logits.astype(jnp.float32), flat_y, flat_m
        )

        # Only the last rank's loss is real; weight by its local mask
        # mass and psum over (dp, pp) for the global masked mean.
        is_last = (idx == n_stages - 1).astype(jnp.float32)
        w = flat_m.sum() * is_last
        axes = ("dp", "fsdp", axis)
        gw = jnp.maximum(lax.psum(w, axes), 1e-9)

        def _avg(v):
            return lax.psum(v * w, axes) / gw

        return _avg(loss), jax.tree_util.tree_map(_avg, metrics)

    return fn


def one_f_one_b_grads(
    embed_apply,
    stage_apply,
    head_apply,
    loss_fn,
    *,
    n_stages: int,
    n_micro: int,
    axis: str = "pp",
):
    """Per-device 1F1B (PipeDream-flush) pipeline step for shard_map:
    returns ``(loss, metrics, grads)`` with the backward INTERLEAVED
    into the schedule instead of left to ``jax.grad``.

    Why it exists: under ``jax.grad``, GPipe's transpose runs as a
    second full pass AFTER the forward loop, so every microbatch's
    residuals stay live through the whole forward — O(n_micro)
    activation memory per rank.  Here each microbatch's backward starts
    the moment it leaves the pipe (last rank: same tick), so a rank
    holds at most ``2·(pp-1-s)`` in-flight inputs — O(pp), independent
    of n_micro.  That converts directly into bubble: at a fixed
    activation budget the 1F1B schedule can run n_micro ≫ pp (bubble
    → (pp-1)/(n_micro+pp-1) → 0) where GPipe's memory wall caps
    n_micro ≈ budget.

    Mechanics (all static Python loops → ONE jitted program, SPMD):

    - macro tick t ∈ [0, n_micro + 2·pp - 3]; rank s forwards
      microbatch ``t - s`` and backwards microbatch
      ``t - 2·pp + 2 + s`` (both masked when out of range);
    - stage inputs are saved in a (2·pp-1)-slot circular buffer; the
      backward RE-APPLIES the stage under ``jax.vjp`` on the saved
      input (rematerialize-in-backward — the standard TPU trade of
      FLOPs for HBM, and what keeps the buffer a stackable tensor
      instead of unstackable residual closures);
    - activations ``ppermute`` right after each forward slot,
      cotangents ``ppermute`` left after each backward slot;
    - the last rank seeds each microbatch's cotangent from the
      head+loss VJP at the forward-completion tick, scaled by
      ``w_m/gw`` so the stitched gradient equals the gradient of the
      same global masked-mean loss as :func:`gpipe_loss`.

    Losses/metrics/grads are psum'd exactly as gpipe's AD would:
    embed/head grads over (dp, fsdp, pp) (replicated out), stage grads
    over (dp, fsdp) only (each rank owns its stage).
    """
    if n_micro < 1:
        raise ValueError("n_micro must be >= 1")
    K = max(1, 2 * n_stages - 1)  # circular input-buffer depth

    def fn(eparams, sparams, hparams, xb, yb, mb):
        sparams = jax.tree_util.tree_map(lambda l: l[0], sparams)
        idx = lax.axis_index(axis)
        P_ = n_stages
        M = n_micro
        mb_sz = xb.shape[0] // M
        xm = xb.reshape(M, mb_sz, *xb.shape[1:])
        ym = yb.reshape(M, mb_sz, *yb.shape[1:])
        mm = mb.reshape(M, mb_sz)
        key_masks = xm != 0  # (M, mb, T) pad id 0

        # Global mask mass — the same normalizer gpipe's psum'd masked
        # mean uses; known upfront so per-microbatch cotangent seeds
        # can be scaled in-schedule.
        gw = jnp.maximum(lax.psum(mb.sum(), ("dp", "fsdp")), 1e-9)

        # Embedding forward ONCE (vmapped over microbatches), its VJP
        # kept for the end: cotangents accumulate per microbatch as
        # rank 0 finishes backwards.
        emb, emb_vjp = jax.vjp(
            lambda ep: jax.vmap(lambda tk: embed_apply(ep, tk))(xm),
            eparams,
        )

        right = [(i, i + 1) for i in range(P_ - 1)]
        left = [(i + 1, i) for i in range(P_ - 1)]
        is_last = idx == P_ - 1
        is_first = idx == 0

        in_buf = jnp.zeros((K, *emb.shape[1:]), emb.dtype)
        demb = jnp.zeros_like(emb)
        dsparams = jax.tree_util.tree_map(jnp.zeros_like, sparams)
        dhparams = jax.tree_util.tree_map(jnp.zeros_like, hparams)
        recv = jnp.zeros_like(emb[0])
        recv_cot = jnp.zeros_like(emb[0])
        loss_acc = jnp.zeros((), jnp.float32)
        w_acc = jnp.zeros((), jnp.float32)
        metrics_acc = None

        def stage_on(km):
            return lambda p, xin: stage_apply(p, xin, km)

        for t in range(M + 2 * P_ - 2):
            # ---- forward slot: rank s, microbatch t - s ----
            m_f = t - idx
            f_valid = ((m_f >= 0) & (m_f < M)).astype(jnp.float32)
            m_fc = jnp.clip(m_f, 0, M - 1)
            km_f = jnp.take(key_masks, m_fc, axis=0)
            x_in = jnp.where(is_first, emb[jnp.clip(t, 0, M - 1)], recv)
            in_buf = in_buf.at[t % K].set(x_in)
            out = stage_apply(sparams, x_in, km_f)
            if right:
                recv = lax.ppermute(out, axis, right)

            # ---- last rank: head + loss + cotangent seed for the
            # backward slot of this SAME tick (1F1B: bwd of m starts
            # the tick its fwd completes) ----
            y_m = jnp.take(ym, m_fc, axis=0)
            mm_m = jnp.take(mm, m_fc, axis=0)

            def head_loss(hp, h, y_m=y_m, mm_m=mm_m):
                logits = head_apply(hp, h).astype(jnp.float32)
                loss, metrics = loss_fn(logits, y_m, mm_m)
                return loss, metrics

            loss_m, hl_vjp, metrics_m = jax.vjp(
                head_loss, hparams, out, has_aux=True
            )
            w_m = mm_m.sum()
            contrib = f_valid * is_last.astype(jnp.float32)
            dhp_m, dh_m = hl_vjp(contrib * w_m / gw)
            dhparams = jax.tree_util.tree_map(
                lambda a, g: a + g, dhparams, dhp_m
            )
            loss_acc = loss_acc + contrib * w_m * loss_m
            w_acc = w_acc + contrib * w_m
            scaled = jax.tree_util.tree_map(
                lambda v: contrib * w_m * v, metrics_m
            )
            metrics_acc = scaled if metrics_acc is None else \
                jax.tree_util.tree_map(
                    lambda a, v: a + v, metrics_acc, scaled
                )

            # ---- backward slot: rank s, microbatch t - 2P + 2 + s ----
            m_b = t - 2 * P_ + 2 + idx
            b_valid = ((m_b >= 0) & (m_b < M)).astype(jnp.float32)
            m_bc = jnp.clip(m_b, 0, M - 1)
            km_b = jnp.take(key_masks, m_bc, axis=0)
            # Rank s forwarded m_b at tick m_b + s = t - 2(P-1-s).
            slot = jnp.mod(t - 2 * (P_ - 1) + 2 * idx, K)
            x_saved = jnp.take(in_buf, slot, axis=0)
            # Cotangents arrive f32 (head_loss upcasts; the where-
            # promote makes stage INPUTS f32 while outputs may be
            # bf16) — cast to this stage's OUTPUT dtype, exactly the
            # cast AD's promote/astype transposes apply on the gpipe
            # path.
            cot_in = jnp.where(is_last, dh_m, recv_cot).astype(
                out.dtype
            )
            _, s_vjp = jax.vjp(stage_on(km_b), sparams, x_saved)
            dsp_m, dx = s_vjp(cot_in)
            dsparams = jax.tree_util.tree_map(
                lambda a, g: a + b_valid * g, dsparams, dsp_m
            )
            dx = dx * b_valid
            # Cast into the buffer dtype: demb is emb-dtype (bf16 under
            # mixed precision) while dx is the f32-promoted input
            # cotangent — a mixed-dtype scatter-add is a future error.
            demb = demb.at[m_bc].add(
                (dx * is_first.astype(jnp.float32)).astype(demb.dtype)
            )
            if left:
                recv_cot = lax.ppermute(dx, axis, left)

        # demb varies over pp (only rank 0 contributed, via
        # axis_index masking) but the embed primal was pp-invariant;
        # psum over pp broadcasts rank 0's cotangent everywhere, making
        # the vjp input's replication type match the primal's — and
        # every rank then computes the identical embed grad.
        (deparams,) = emb_vjp(lax.psum(demb, axis))

        all_axes = ("dp", "fsdp", axis)
        gsum = lambda v: lax.psum(v, all_axes)  # noqa: E731
        gw_all = jnp.maximum(gsum(w_acc), 1e-9)
        loss = gsum(loss_acc) / gw_all
        metrics = jax.tree_util.tree_map(
            lambda v: gsum(v) / gw_all, metrics_acc
        )
        # No explicit grad psums: shard_map's replication-typing makes
        # each jax.vjp transpose psum cotangents onto device-INVARIANT
        # inputs automatically (an invariant param used by varying data
        # transposes to a cross-device sum).  deparams/dhparams come
        # out fully invariant (global sums); dsp_m came out dp-summed
        # per pp rank.  Adding our own psums here double-counts —
        # measured 4x/8x on a dp=4,pp=2 mesh before this comment.
        grads = (
            deparams,
            jax.tree_util.tree_map(lambda g: g[None], dsparams),
            dhparams,
        )
        return loss, metrics, grads

    return fn


def sequential_loss(embed_apply, stage_apply, head_apply, loss_fn,
                    *, n_stages: int):
    """The pipeline's math without the pipeline — stages applied in
    order on one device.  Correctness oracle + predict path."""

    def fn(eparams, sparams, hparams, xb, yb, mb):
        km = xb != 0
        h = embed_apply(eparams, xb)
        for s in range(n_stages):
            sp = jax.tree_util.tree_map(lambda l: l[s], sparams)
            h = stage_apply(sp, h, km)
        logits = head_apply(hparams, h).astype(jnp.float32)
        return loss_fn(logits, yb, mb)

    return fn


@register(_MODULE)
class PipelinedTransformer:
    """Transformer classifier/LM trained GPipe-parallel over ``pp``.

    fit/evaluate/predict mirror the NeuralEstimator surface so the
    executor layer drives it by reflection (services/executor.py).
    ``num_layers`` must divide evenly into ``pp`` stages.
    """

    # Opt in to the executor's managed checkpoint-dir injection so a
    # service-path fit checkpoints (and SIGKILL-resumes) per stage.
    supports_managed_checkpoints = True

    def __init__(
        self,
        vocab_size: int = 20000,
        hidden_dim: int = 128,
        num_layers: int = 4,
        num_heads: int = 4,
        mlp_dim: int | None = None,
        max_len: int = 256,
        num_classes: int = 2,
        head: str = "cls",  # 'cls' | 'lm'
        n_microbatches: int | None = None,
        learning_rate: float = 1e-3,
        seed: int = 0,
        mesh: Mesh | None = None,
        pp: int | None = None,
        compute_dtype: str = "bfloat16",
        schedule: str | None = None,  # 'gpipe' | '1f1b' | 'mpmd'
    ):
        from learningorchestra_tpu.config import get_config

        self.vocab_size = vocab_size
        self.hidden_dim = hidden_dim
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.mlp_dim = mlp_dim or hidden_dim * 4
        self.max_len = max_len
        self.num_classes = num_classes
        self.head = head
        self.learning_rate = learning_rate
        self.seed = seed
        self.compute_dtype = compute_dtype
        mpmd_cfg = get_config().mpmd
        if schedule is None:
            # Deployment-default schedule (LO_TPU_MPMD_SCHEDULE): lets
            # an operator flip a fleet to MPMD dispatch without every
            # client spelling the parameter.
            schedule = mpmd_cfg.schedule or "gpipe"
        if schedule not in ("gpipe", "1f1b", "mpmd"):
            raise ValueError(
                "schedule must be 'gpipe', '1f1b' or 'mpmd', "
                f"got {schedule!r}"
            )
        self.schedule = schedule
        if n_microbatches is None and mpmd_cfg.n_micro > 0:
            n_microbatches = mpmd_cfg.n_micro
        if mesh is None:
            n = jax.device_count()
            if pp is not None:
                # Explicit pp: honour it or fail loudly, exactly like
                # the explicit-mesh path below.
                stages = pp
                if n % stages:
                    raise ValueError(
                        f"pp={stages} does not divide {n} devices"
                    )
            else:
                stages = min(n, num_layers)
                while num_layers % stages or n % stages:
                    stages -= 1
            mesh = build_mesh(
                MeshSpec(dp=n // stages, pp=stages)
            )
        self.mesh = mesh
        self.pp = mesh.shape["pp"]
        if num_layers % self.pp:
            raise ValueError(
                f"num_layers={num_layers} not divisible by pp={self.pp}"
            )
        self.n_micro = n_microbatches or 2 * self.pp
        self.optimizer = optax.adam(learning_rate)
        # Declarative spec → per-stage MPMD optimizer programs share
        # compile-cache entries ACROSS jobs (an opaque-object key never
        # matches another instance's; compile_cache.py).
        self._optimizer_spec = {"name": "adam"}

        causal = head == "lm"
        out_dim = vocab_size if head == "lm" else num_classes
        self._embed = _Embed(vocab_size, hidden_dim, max_len)
        self._stage = _Stage(
            hidden_dim=hidden_dim,
            num_heads=num_heads,
            mlp_dim=self.mlp_dim,
            layers_per_stage=num_layers // self.pp,
            causal=causal,
        )
        self._head = _Head(hidden_dim, out_dim, head)
        self._loss_fn = NeuralEstimator._loss_and_metrics("softmax_ce")
        self.params = None
        self.opt_state = None
        self.history = TrainHistory()
        self._step = None
        self._oracle = None
        self._seq_fwd = None
        self._mpmd = None

    # -- init -----------------------------------------------------------------

    def _engine(self):
        """The MPMD host dispatcher (parallel/mpmd.py), built lazily —
        it holds Device handles and cached program refs, so it drops on
        pickle and rebuilds here on first use."""
        if self._mpmd is None:
            from learningorchestra_tpu.parallel.mpmd import MPMDEngine

            self._mpmd = MPMDEngine(self)
        return self._mpmd

    def _init_params(self, x0: jnp.ndarray) -> None:
        k0, k1, k2 = jax.random.split(jax.random.PRNGKey(self.seed), 3)
        ep = self._embed.init(k0, x0)
        h0 = self._embed.apply(ep, x0)
        km0 = x0 != 0
        sp = jax.vmap(
            lambda k: self._stage.init(k, h0, km0)
        )(jax.random.split(k1, self.pp))
        hp = self._head.init(k2, h0)
        if self.schedule == "mpmd":
            # Stage-partitioned layout: the engine splits the stacked
            # stage stack, commits each partition to its stage device,
            # and inits per-partition optimizer states.
            self.params = (ep, sp, hp)
            self._engine().ensure_placed()
            return
        self.params = self._place_params((ep, sp, hp))
        self.opt_state = jax.jit(
            self.optimizer.init,
        )(self.params)

    def _place_params(self, params: tuple) -> tuple:
        """Placement: embed/head replicated, stage stack over pp."""
        ep, sp, hp = params
        rep = NamedSharding(self.mesh, P())
        stage_sh = jax.tree_util.tree_map(
            lambda l: NamedSharding(
                self.mesh, P("pp", *[None] * (l.ndim - 1))
            ),
            sp,
        )
        return (
            jax.device_put(ep, rep),
            jax.tree_util.tree_map(jax.device_put, sp, stage_sh),
            jax.device_put(hp, rep),
        )

    # -- jitted step ----------------------------------------------------------

    def _build(self):
        mesh = self.mesh
        batch_spec = P(("dp", "fsdp"))
        stage_spec = jax.tree_util.tree_map(
            lambda _: P("pp"), self.params[1]
        )

        from learningorchestra_tpu.train.neural import _param_cast_for

        _pcast = _param_cast_for(
            jnp.bfloat16 if self.compute_dtype == "bfloat16" else None
        )

        if self.schedule == "1f1b":
            pipe = one_f_one_b_grads(
                self._embed.apply, self._stage.apply, self._head.apply,
                self._loss_fn, n_stages=self.pp, n_micro=self.n_micro,
            )
            smapped = jax.shard_map(
                pipe,
                mesh=mesh,
                in_specs=(P(), stage_spec, P(), batch_spec, batch_spec,
                          batch_spec),
                out_specs=(P(), P(), (P(), stage_spec, P())),
            )

            def step(params, opt_state, xb, yb, mb):
                # The schedule computes its own gradients (backward
                # interleaved per microbatch); grads arrive in compute
                # dtype and cast back to f32 master precision — the
                # same cast-transpose jax.grad applies on the gpipe
                # path.
                loss, metrics, grads = smapped(*_pcast(params), xb, yb,
                                               mb)
                grads = jax.tree_util.tree_map(
                    lambda g, p: g.astype(p.dtype), grads, params
                )
                updates, opt_state = self.optimizer.update(
                    grads, opt_state, params
                )
                params = optax.apply_updates(params, updates)
                return params, opt_state, metrics
        else:
            pipe = gpipe_loss(
                self._embed.apply, self._stage.apply, self._head.apply,
                self._loss_fn, n_stages=self.pp, n_micro=self.n_micro,
            )
            smapped = jax.shard_map(
                pipe,
                mesh=mesh,
                in_specs=(P(), stage_spec, P(), batch_spec, batch_spec,
                          batch_spec),
                out_specs=(P(), P()),
            )

            def step(params, opt_state, xb, yb, mb):
                def objective(ps):
                    # Mixed precision: bf16 compute copy, f32 master
                    # weights in the optimizer (train/neural.py
                    # contract).
                    loss, metrics = smapped(*_pcast(ps), xb, yb, mb)
                    return loss, metrics

                grads, metrics = jax.grad(objective, has_aux=True)(
                    params
                )
                updates, opt_state = self.optimizer.update(
                    grads, opt_state, params
                )
                params = optax.apply_updates(params, updates)
                return params, opt_state, metrics

        self._step = jax.jit(step, donate_argnums=(0, 1))
        self._oracle = jax.jit(sequential_loss(
            self._embed.apply, self._stage.apply, self._head.apply,
            self._loss_fn, n_stages=self.pp,
        ))

    def _restore_placed(self, state: dict) -> None:
        """Shared resume re-placement: orbax restores each leaf to the
        TEMPLATE leaf's placement, and scalar optimizer counts can come
        back single-device, which jit rejects against mesh-placed
        params — re-pin both onto the pipeline shardings."""
        self.params = self._place_params(state["params"])
        fresh = jax.jit(self.optimizer.init)(self.params)
        mesh_devices = set(self.mesh.devices.flat)

        def _sh(f):
            sh = getattr(f, "sharding", None)
            if sh is not None and set(sh.device_set) == mesh_devices:
                return sh
            # Scalar leaves (adam's count) come off the init jit on
            # one device; replicate them on the mesh.
            return NamedSharding(self.mesh, P())

        self.opt_state = jax.tree_util.tree_map(
            lambda r, f: jax.device_put(r, _sh(f)),
            state["opt_state"], fresh,
        )

    def _batch_pass(self, xs, ys, order, batch_size):
        """Run the pipelined train step over ``order`` in batch_size
        slices (tail batch padded + masked); returns the DEVICE metric
        dicts and each batch's real-row weight — callers device_get at
        their own granularity (per epoch in-memory, per shard when
        streaming) so host round-trips stay amortized."""
        mpmd = self.schedule == "mpmd"
        engine = self._engine() if mpmd else None
        metrics_list, weights = [], []
        # Accumulates across calls (streaming fits pass one shard per
        # call); the epoch loops zero it per epoch for attribution.
        self._epoch_batches = getattr(self, "_epoch_batches", 0)
        for lo in range(0, len(order), batch_size):
            idx = order[lo: lo + batch_size]
            if len(idx) < batch_size:
                pad = batch_size - len(idx)
                idx = np.concatenate([idx, idx[:1].repeat(pad)])
                mask = np.concatenate([
                    np.ones(batch_size - pad, np.float32),
                    np.zeros(pad, np.float32),
                ])
            else:
                mask = np.ones(batch_size, np.float32)
            if mpmd:
                # Host-dispatched 1F1B over per-stage programs; the
                # engine mutates params/opt_state in place of the
                # donate-and-reassign the jitted step does.
                m, w = engine.train_batch(xs[idx], ys[idx], mask)
                metrics_list.append(m)
                weights.append(w)
            else:
                self.params, self.opt_state, m = self._step(
                    self.params, self.opt_state,
                    jnp.asarray(xs[idx]), jnp.asarray(ys[idx]),
                    jnp.asarray(mask),
                )
                metrics_list.append(m)
                weights.append(float(mask.sum()))
            self._epoch_batches += 1
        return metrics_list, weights

    @staticmethod
    def _weighted_update(totals, metrics_list, weights):
        """device_get + mask-weighted accumulation (a padded tail
        batch must not count like a full one); returns weight added."""
        stacked = jax.device_get(metrics_list)
        for m, w in zip(stacked, weights):
            for k, v in m.items():
                totals[k] = totals.get(k, 0.0) + float(v) * w
        return sum(weights)

    @staticmethod
    def _finish_row(totals, wsum):
        row = {k: v / max(wsum, 1e-9) for k, v in totals.items()}
        if "perplexity" in row:  # raw CE until post-mean exp
            row["perplexity"] = float(np.exp(row["perplexity"]))
        return row

    # -- shared fit plumbing --------------------------------------------------

    def _batch_quantum(self) -> int:
        """Smallest legal global batch: n_micro microbatches, times
        the dp replication for the SPMD schedules.  MPMD ignores dp —
        one device per stage, scale via bigger microbatches."""
        if self.schedule == "mpmd":
            return self.n_micro
        return self.n_micro * (
            self.mesh.shape["dp"] * self.mesh.shape["fsdp"]
        )

    def _ckpt_resume(self, checkpoint_dir) -> int:
        """Resume from ``checkpoint_dir`` if it holds a checkpoint;
        returns the epoch to continue from (0 = fresh).  MPMD resumes
        every stage partition from its newest COMMON step
        (parallel/mpmd.py); the SPMD schedules restore the single
        stacked state."""
        if self.schedule == "mpmd":
            loaded = self._engine().resume_checkpoint(checkpoint_dir)
            if loaded is None:
                return 0
            step, past_history = loaded
            self.history = TrainHistory(past_history)
            return step
        from learningorchestra_tpu.train import checkpoint as ckpt

        loaded = ckpt.resume_or_none(
            checkpoint_dir,
            {"params": self.params, "opt_state": self.opt_state},
        )
        if loaded is None:
            return 0
        state, step, past_history = loaded
        self._restore_placed(state)
        self.history = TrainHistory(past_history)
        return step

    def _ckpt_save(self, checkpoint_dir, step: int,
                   *, async_save: bool) -> None:
        if self.schedule == "mpmd":
            self._engine().save_checkpoint(
                checkpoint_dir, step, dict(self.history),
                async_save=async_save,
            )
            return
        from learningorchestra_tpu.train import checkpoint as ckpt

        opt_state = self.opt_state
        if opt_state is None:
            # restore-best dropped the moments: checkpoint the
            # restored params with FRESH moments, else resume=True
            # would replay the last periodic save's pre-restore params
            # (same contract as train/neural.py).
            opt_state = jax.jit(self.optimizer.init)(self.params)
            self.opt_state = opt_state
        ckpt.save(
            checkpoint_dir, step,
            {"params": self.params, "opt_state": opt_state},
            history=dict(self.history),
            async_save=async_save,
        )

    def _ckpt_finalize(self, checkpoint_dir) -> None:
        from learningorchestra_tpu.train import checkpoint as ckpt

        if self.schedule == "mpmd":
            self._engine().finalize_checkpoints(checkpoint_dir)
        ckpt.finalize_async(checkpoint_dir)

    def _record_epoch_obs(self, epoch_i: int, epoch_s: float) -> None:
        """Per-epoch trace spans + device-time attribution.  MPMD adds
        one ``mpmd.stage`` span per pipeline stage (host dispatch
        seconds — where the schedule spent its enqueue time) and books
        the epoch against the job cost ledger with the aggregate
        per-stage flops, collectives excluded."""
        from learningorchestra_tpu.obs import tracing

        attrs: dict = {}
        if self.schedule == "mpmd" and self._mpmd is not None:
            engine = self._mpmd
            n_batches = getattr(self, "_epoch_batches", 0)
            engine.attribute_epoch(epoch_s, n_batches)
            attrs = engine.epoch_cost_attrs(epoch_s, n_batches)
            for s, secs in enumerate(engine.pop_stage_seconds()):
                tracing.record_span(
                    "mpmd.stage", secs, stage=s, epoch=epoch_i
                )
        tracing.record_span("epoch", epoch_s, epoch=epoch_i, **attrs)

    # -- keras-fit surface ----------------------------------------------------

    def fit(self, x, y, epochs: int = 1, batch_size: int = 32,
            shuffle: bool = True, verbose: int = 0,
            checkpoint_dir: str | None = None,
            checkpoint_every: int = 1,
            checkpoint_min_interval_s: float = 60.0,
            resume: bool = True, checkpoint_async: bool = True,
            callbacks: list | None = None, early_stopping=None, **_):
        """Same managed in-loop checkpointing contract as
        ``NeuralEstimator.fit``: with ``checkpoint_dir`` set the
        (stage-stacked) state persists every ``checkpoint_every``
        epochs via the shard-aware orbax helper — sharded stage params
        save without a host gather — and an interrupted fit resumes
        from the newest checkpoint (the preemption story, SURVEY §5.4).

        Sharded-dataset views stream shard by shard (the beyond-RAM
        contract every fit surface carries, train/neural.py
        ``_fit_streaming``).
        """
        from learningorchestra_tpu.train.neural import (
            _is_sharded,
            build_stop_callbacks,
        )

        callbacks = build_stop_callbacks(self, callbacks,
                                         early_stopping)
        if _is_sharded(x) or _is_sharded(y):
            return self._fit_streaming(
                x, y, epochs=epochs, batch_size=batch_size,
                shuffle=shuffle, verbose=verbose,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
                checkpoint_min_interval_s=checkpoint_min_interval_s,
                resume=resume, checkpoint_async=checkpoint_async,
                callbacks=callbacks,
            )
        x = np.asarray(x)
        y = np.asarray(y).astype(np.int32)
        # Global batch must split into n_micro microbatches that split
        # over dp; round it DOWN to the nearest legal multiple (never
        # below one quantum) so the effective batch fits the request.
        quantum = self._batch_quantum()
        batch_size = max(quantum, (batch_size // quantum) * quantum)
        if self.params is None:
            self._init_params(jnp.asarray(x[:1]))
        if self._step is None and self.schedule != "mpmd":
            self._build()

        start_epoch = 0
        if checkpoint_dir and resume:
            start_epoch = self._ckpt_resume(checkpoint_dir)

        from learningorchestra_tpu import faults
        from learningorchestra_tpu.train import checkpoint as ckpt_mod

        last_save = time.monotonic()
        rng = np.random.default_rng(self.seed)
        n = len(x)
        if shuffle:
            # Burn the completed epochs' draws so a resumed run
            # shuffles exactly as the original would at this epoch.
            for _ in range(start_epoch):
                rng.permutation(n)
        try:
            for epoch_i in range(start_epoch, epochs):
                if cancel_requested():
                    # Engine-side cancellation (deadline watchdog or
                    # bounded shutdown drain): wind down like an
                    # early stop.
                    self.stop_training = True
                    break
                faults.hit("train.epoch")
                t0 = time.perf_counter()
                self._epoch_batches = 0
                order = rng.permutation(n) if shuffle else np.arange(n)
                totals: dict = {}
                wsum = self._weighted_update(
                    totals, *self._batch_pass(x, y, order, batch_size)
                )
                epoch_row = self._finish_row(totals, wsum)
                self.history.append(epoch_row)
                self._record_epoch_obs(
                    epoch_i, time.perf_counter() - t0
                )
                if verbose:
                    print(f"pipeline epoch: {self.history['loss'][-1]:.4f}",
                          flush=True)
                for cb in callbacks or []:
                    if callable(cb):
                        cb(epoch_i, epoch_row, self)
                if checkpoint_dir and ckpt_mod.should_save(
                    epoch_i, epochs, checkpoint_every,
                    checkpoint_min_interval_s, last_save,
                    stopped=self.stop_training,
                ):
                    self._ckpt_save(
                        checkpoint_dir, epoch_i + 1,
                        async_save=checkpoint_async,
                    )
                    last_save = time.monotonic()
                if self.stop_training:
                    break
        finally:
            if checkpoint_dir:
                # The last async save must be durable when fit
                # returns — exception paths included.
                self._ckpt_finalize(checkpoint_dir)
        return self

    def _fit_streaming(
        self, x, y, *, epochs, batch_size, shuffle, verbose,
        checkpoint_dir, checkpoint_every, checkpoint_min_interval_s,
        resume, checkpoint_async, callbacks: list | None = None,
    ) -> "PipelinedTransformer":
        """Shard-streaming pipelined fit: the same microbatched step,
        fed shard by shard with IO-thread prefetch — token datasets
        bigger than host RAM train through the pp mesh unchanged."""
        import concurrent.futures

        from learningorchestra_tpu.store import sharded as sh

        x, y = sh.resolve_xy_views(x, y)
        # Column memory for a later predict/evaluate on the bare
        # dataset (same contract as NeuralEstimator).
        self._sharded_fit_cols = list(x.cols)
        ds = x.dataset
        quantum = self._batch_quantum()
        batch_size = max(quantum, (batch_size // quantum) * quantum)
        if self.params is None:
            self._init_params(jnp.asarray(np.asarray(x.head(1))))
        if self._step is None and self.schedule != "mpmd":
            self._build()

        start_epoch = 0
        if checkpoint_dir and resume:
            start_epoch = self._ckpt_resume(checkpoint_dir)

        from learningorchestra_tpu import faults
        from learningorchestra_tpu.train import checkpoint as ckpt_mod

        def load(k: int):
            xs = np.asarray(x.load_shard(k))
            ys = np.asarray(y.load_shard(k)).astype(np.int32)
            return xs, ys

        last_save = time.monotonic()
        with concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="shard-io"
        ) as io:
            try:
                for epoch_i in range(start_epoch, epochs):
                    if cancel_requested():
                        # Same contract as the in-memory loop.
                        self.stop_training = True
                        break
                    faults.hit("train.epoch")  # see in-memory loop
                    t0 = time.perf_counter()
                    self._epoch_batches = 0
                    order = (
                        np.random.default_rng(
                            [self.seed, 3, epoch_i]
                        ).permutation(ds.n_shards)
                        if shuffle else np.arange(ds.n_shards)
                    )
                    totals: dict = {}
                    wsum = 0.0
                    nxt = io.submit(load, int(order[0]))
                    for pos, k in enumerate(order):
                        xs, ys = nxt.result()
                        if pos + 1 < len(order):
                            nxt = io.submit(load, int(order[pos + 1]))
                        inner = (
                            np.random.default_rng(
                                [self.seed, 7 + epoch_i, pos]
                            ).permutation(len(xs))
                            if shuffle else np.arange(len(xs))
                        )
                        # device_get per SHARD: bounded retained
                        # buffers for beyond-RAM datasets, without
                        # per-batch host round-trips.
                        wsum += self._weighted_update(
                            totals,
                            *self._batch_pass(
                                xs, ys, inner, batch_size
                            ),
                        )
                    epoch_row = self._finish_row(totals, wsum)
                    self.history.append(epoch_row)
                    self._record_epoch_obs(
                        epoch_i, time.perf_counter() - t0
                    )
                    if verbose:
                        print(
                            "pipeline epoch: "
                            f"{self.history['loss'][-1]:.4f}",
                            flush=True,
                        )
                    for cb in callbacks or []:
                        if callable(cb):
                            cb(epoch_i, epoch_row, self)
                    if checkpoint_dir and ckpt_mod.should_save(
                        epoch_i, epochs, checkpoint_every,
                        checkpoint_min_interval_s, last_save,
                        stopped=self.stop_training,
                    ):
                        self._ckpt_save(
                            checkpoint_dir, epoch_i + 1,
                            async_save=checkpoint_async,
                        )
                        last_save = time.monotonic()
                    if self.stop_training:
                        break
            finally:
                if checkpoint_dir:
                    self._ckpt_finalize(checkpoint_dir)
        return self

    _CHUNK = 512  # inference batch: fixed shape -> one compile

    def _forward_chunks(self, x: np.ndarray):
        """Sequential (non-pipelined) forward in fixed-size chunks —
        inference needs no microbatch schedule, and chunking keeps
        activations O(chunk) instead of O(dataset) while the fixed
        chunk shape compiles once."""
        if self.schedule == "mpmd":
            engine = self._engine()
            for lo in range(0, len(x), self._CHUNK):
                chunk = x[lo: lo + self._CHUNK]
                n = len(chunk)
                if n < self._CHUNK:  # pad to the compiled shape
                    chunk = np.pad(
                        chunk, ((0, self._CHUNK - n), (0, 0))
                    )
                yield np.asarray(engine.forward_logits(chunk))[:n]
            return
        if self._seq_fwd is None:
            def fwd(params, xb):
                ep, sp, hp = params
                km = xb != 0
                h = self._embed.apply(ep, xb)
                for s in range(self.pp):
                    ssp = jax.tree_util.tree_map(lambda l: l[s], sp)
                    h = self._stage.apply(ssp, h, km)
                return self._head.apply(hp, h)

            self._seq_fwd = jax.jit(fwd)
        for lo in range(0, len(x), self._CHUNK):
            chunk = x[lo: lo + self._CHUNK]
            n = len(chunk)
            if n < self._CHUNK:  # pad to the compiled shape (id 0)
                chunk = np.pad(chunk, ((0, self._CHUNK - n), (0, 0)))
            yield np.asarray(
                self._seq_fwd(self.params, jnp.asarray(chunk))
            )[:n]

    def evaluate(self, x, y, **_) -> dict:
        from learningorchestra_tpu.train.neural import _is_sharded

        if _is_sharded(x) or _is_sharded(y):
            from learningorchestra_tpu.store import sharded as sh

            x, y = sh.resolve_xy_views(x, y)
            dsx = x.dataset
            acc = sh.WeightedMetrics()
            for k in range(dsx.n_shards):
                acc.add(
                    self.evaluate(x.load_shard(k), y.load_shard(k)),
                    dsx.shard_rows[k],
                )
            return acc.result()
        x = np.asarray(x)
        y = np.asarray(y).astype(np.int32)
        if self.params is None:
            raise RuntimeError("evaluate before fit")
        sums: dict = {}
        total = 0
        for lo, logits in zip(range(0, len(x), self._CHUNK),
                              self._forward_chunks(x)):
            yb = jnp.asarray(y[lo: lo + len(logits)])
            _, metrics = self._loss_fn(
                jnp.asarray(logits, jnp.float32), yb,
                jnp.ones(len(logits), jnp.float32),
            )
            for k, v in metrics.items():
                sums[k] = sums.get(k, 0.0) + float(v) * len(logits)
            total += len(logits)
        out = {k: v / max(total, 1) for k, v in sums.items()}
        if "perplexity" in out:  # raw CE until post-mean exp
            out["perplexity"] = float(np.exp(out["perplexity"]))
        return out

    def predict(self, x, **_):
        from learningorchestra_tpu.train.neural import _is_sharded

        if _is_sharded(x):
            from learningorchestra_tpu.store import sharded as sh

            if isinstance(x, sh.ShardedDataset):
                cols = getattr(self, "_sharded_fit_cols", None)
                view = x.view(cols) if cols and all(
                    c in x.fields for c in cols
                ) else x.view(x.fields)
            else:
                view = x
            return np.concatenate([
                self.predict(view.load_shard(k))
                for k in range(view.dataset.n_shards)
            ], axis=0)
        x = np.asarray(x)
        if self.params is None:
            raise RuntimeError("predict before fit")
        out = np.concatenate(list(self._forward_chunks(x)), axis=0)
        if self.head == "cls":
            return np.argmax(out, -1)
        return out

    # -- persistence ----------------------------------------------------------

    def state_dict(self) -> dict:
        return {
            "params": jax.device_get(self.params),
            "opt_state": jax.device_get(self.opt_state),
            "history": dict(self.history),
        }

    def load_state_dict(self, state: dict) -> None:
        self.params = state["params"]
        self.opt_state = state["opt_state"]
        self.history = TrainHistory(state.get("history", {}))
        self._step = None
        self._oracle = None
        self._seq_fwd = None
        self._mpmd = None  # host state → engine re-places on next use

    def __getstate__(self):
        """dill support (the model service persists instances): drop
        jitted closures and the Mesh (Device handles don't pickle) —
        the mesh rebuilds from its axis sizes on load."""
        d = dict(self.__dict__)
        d["_step"] = None
        d["_oracle"] = None
        d["_seq_fwd"] = None
        d["_mpmd"] = None
        d["mesh"] = None
        d["_mesh_shape"] = dict(self.mesh.shape) \
            if self.mesh is not None else None
        if d["params"] is not None:
            d["params"] = jax.device_get(d["params"])
        if d["opt_state"] is not None:
            d["opt_state"] = jax.device_get(d["opt_state"])
        return d

    def __setstate__(self, d):
        shape = d.pop("_mesh_shape", None)
        self.__dict__.update(d)
        if shape is not None:
            self.mesh = build_mesh(MeshSpec.from_dict(shape))
