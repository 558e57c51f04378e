"""Mixture-of-experts feed-forward layer (expert parallelism).

Beyond-parity headroom: the reference has no conditional-compute story
at all (its model zoo is dense keras/sklearn — SURVEY §2.3); this adds
a GShard/Switch-style MoE FFN designed for the ``ep`` mesh axis
(parallel/mesh.py).

TPU-first design decisions:

- **Static shapes everywhere.**  Routing uses a fixed per-expert
  capacity ``C`` computed from static shapes, so the dispatched tensor
  is always ``(experts, batch, C, hidden)`` — no dynamic gather sizes,
  no recompiles, and XLA can tile every einsum onto the MXU.  Tokens
  over capacity are dropped (their combine weight is zero and the
  residual connection carries them through unchanged — the standard
  Switch trade).
- **Dispatch/combine as einsums, not gathers.**  The one-hot dispatch
  tensor turns routing into two batched matmuls; with expert weights
  sharded ``P('ep', ...)`` XLA's SPMD partitioner lowers the expert
  dimension contraction to an all_to_all over ``ep`` — the collective
  rides ICI, never the host.
- **Router in f32.**  Gating softmax/argmax run in float32 regardless
  of the compute dtype (bf16 router logits measurably destabilise
  top-k choices at scale); expert matmuls run in the model dtype.

The load-balancing auxiliary loss is sown into the ``'losses'``
collection; ``train/neural.py`` adds every sown value to the training
objective (dense models sow nothing and pay nothing).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn


class MoEMlp(nn.Module):
    """Top-k routed expert FFN: drop-in for a transformer's dense MLP.

    Output shape equals input shape ``(batch, seq, hidden)``.  With
    ``num_experts=1`` this degenerates to a plain (gelu) FFN whose
    combine weight is exactly 1 for every token — the equivalence test
    in tests/test_moe.py pins that.
    """

    num_experts: int
    hidden_dim: int
    mlp_dim: int
    top_k: int = 2
    capacity_factor: float = 1.5
    aux_loss_weight: float = 1e-2
    router_z_weight: float = 1e-3
    dtype: jnp.dtype | None = None  # None = promote (bf16 when the train step casts params)

    @nn.compact
    def __call__(self, x):
        b, t, h = x.shape
        e = self.num_experts
        k = min(self.top_k, e)
        # Per-expert slots per GROUP (= batch row): every token admitted
        # if routing were perfectly balanced, times headroom.
        cap = max(1, -(-(k * t * self.capacity_factor) // e).__int__())
        cap = min(cap, t * k)

        # -- routing (f32) --------------------------------------------
        logits = nn.Dense(
            e, use_bias=False, dtype=jnp.float32, name="router"
        )(x.astype(jnp.float32))  # (B, T, E)
        probs = jax.nn.softmax(logits, axis=-1)

        remaining = probs
        assigned = jnp.zeros((b, e), jnp.float32)  # slots used so far
        slot_oh, slot_gate, slot_pos = [], [], []
        for _ in range(k):
            idx = jnp.argmax(remaining, axis=-1)  # (B, T)
            oh = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # (B, T, E)
            slot_gate.append((remaining * oh).sum(-1))  # (B, T)
            remaining = remaining * (1.0 - oh)
            # Position of each token inside its expert's capacity
            # buffer: tokens earlier in the sequence fill lower slots;
            # later routing slots stack after earlier ones.
            pos = jnp.cumsum(oh, axis=1) - oh + assigned[:, None, :]
            slot_pos.append((pos * oh).sum(-1).astype(jnp.int32))  # (B, T)
            slot_oh.append(oh)
            assigned = assigned + oh.sum(axis=1)

        # Renormalise the selected gates to sum to 1 per token BEFORE
        # capacity drops (GShard: drops lose mass rather than re-weight
        # the survivors).
        denom = sum(slot_gate) + 1e-9
        dispatch = jnp.zeros((b, t, e, cap), jnp.float32)
        combine = jnp.zeros((b, t, e, cap), jnp.float32)
        for oh, gate, pos in zip(slot_oh, slot_gate, slot_pos):
            keep = (pos < cap).astype(jnp.float32)  # (B, T)
            pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32)
            sel = oh[..., None] * pos_oh[:, :, None, :] * keep[..., None, None]
            dispatch = dispatch + sel
            combine = combine + (gate / denom)[..., None, None] * sel

        # -- load-balancing aux loss (Switch eq. 4, over 1st choices) --
        if not self.is_initializing():
            frac = slot_oh[0].mean(axis=(0, 1))  # dispatch fraction / e
            prob = probs.mean(axis=(0, 1))  # mean router prob / e
            aux = e * jnp.sum(frac * prob) * self.aux_loss_weight
            z = jnp.mean(
                jax.scipy.special.logsumexp(logits, axis=-1) ** 2
            ) * self.router_z_weight
            self.sow("losses", "moe_aux", aux + z)

        # -- expert compute (model dtype) ------------------------------
        w1 = self.param(
            "expert_w1",
            nn.initializers.lecun_normal(batch_axis=(0,)),
            (e, h, self.mlp_dim),
        )
        b1 = self.param(
            "expert_b1", nn.initializers.zeros, (e, self.mlp_dim)
        )
        w2 = self.param(
            "expert_w2",
            nn.initializers.lecun_normal(batch_axis=(0,)),
            (e, self.mlp_dim, h),
        )
        b2 = self.param("expert_b2", nn.initializers.zeros, (e, h))

        dt = self.dtype if self.dtype is not None else x.dtype
        xe = jnp.einsum(
            "btec,bth->ebch", dispatch.astype(dt), x.astype(dt)
        )  # (E, B, C, H)
        h1 = jnp.einsum("ebch,ehm->ebcm", xe, w1.astype(dt))
        h1 = nn.gelu(h1 + b1.astype(dt)[:, None, None, :])
        h2 = jnp.einsum("ebcm,emh->ebch", h1, w2.astype(dt))
        h2 = h2 + b2.astype(dt)[:, None, None, :]
        return jnp.einsum("btec,ebch->bth", combine.astype(dt), h2)


def route_top_k(logits, top_k: int, scoring: str = "softmax",
                bias=None, scale: float = 1.0):
    """Token-choice routing in float32.  Returns (gates (N, k) float32,
    expert ids (N, k) int32).

    ``scoring="softmax"``: softmax over every expert, the ``top_k``
    largest, their gates renormalised to sum to 1.  ``"sigmoid"``: each
    expert's score is its own sigmoid; the ``top_k`` are those with the
    largest score PLUS ``bias`` (a per-expert correction that balances
    load and never weighs a result: DeepSeek-V3's ``noaux_tc`` with one
    group), and the gates are the chosen scores WITHOUT the bias, over
    their sum, times ``scale``."""
    logits = logits.astype(jnp.float32)
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    if bias is None:
        gates, ids = jax.lax.top_k(scores, top_k)
    else:
        _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        gates = jnp.take_along_axis(scores, ids, axis=-1)
    total = jnp.sum(gates, axis=-1, keepdims=True)
    if scoring == "sigmoid":
        total = total + 1e-20
    gates = gates / total
    return (gates if scale == 1.0 else gates * scale), ids


def grouped_matmul(rows, weights, sizes):
    """``rows`` (M, K), sorted so that group ``g`` is a run of
    ``sizes[g]`` rows, times that group's matrix of ``weights`` (G, K,
    N): (M, N).  Rows behind the last group come out undefined.

    On the TPU this is jax's shipped megablox kernel where its tiles
    divide the shapes, with K tiles as large as 2,048 allows (the
    largest whole number of 128-lane rows that divides K: 2,048 of
    2,048, 1,792 of 7,168): with a few rows a group the kernel is bound
    by the expert matrices it streams, and at the routed layer's shapes
    (256 rows over 128 experts of 2048 x 768) it ran the three matmuls
    in 1.52 ms against ``jax.lax.ragged_dot``'s 3.08 (88% against 43%
    of the HBM roofline; PERF.md, PR 29).  Anywhere else, and for
    shapes the tiles do not divide, it is ``ragged_dot``, which XLA
    lowers on every backend."""
    m, k = rows.shape
    n = weights.shape[-1]
    tiling = (min(m, 128), _tile(k, 2048), _tile(n, 1024))
    if jax.default_backend() == "tpu" and m % 16 == 0 \
            and tiling[1] % 128 == 0 and tiling[2] % 128 == 0 \
            and m % tiling[0] == 0:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(rows, weights, sizes, rows.dtype, tiling)
    return jax.lax.ragged_dot(rows, weights, sizes)


def _tile(size: int, cap: int) -> int:
    """The largest divisor of ``size`` that is at most ``cap`` and a
    whole number of 128-lane rows; ``size`` itself where it is under
    the cap or has no such divisor."""
    if size <= cap:
        return size
    for parts in range(-(-size // cap), size // 128 + 1):
        if size % parts == 0 and (size // parts) % 128 == 0:
            return size // parts
    return size


class RoutedExperts(nn.Module):
    """Dropless routed SwiGLU experts: every token reaches each of its
    ``top_k`` experts, whatever the load.

    The (token, choice) pairs are sorted by expert and each of the three
    expert matrices is ONE grouped matmul over the sorted rows
    (:func:`grouped_matmul`: group ``e`` is the run of rows routed to
    expert ``e``), so there is no capacity, no ``(N, E, cap)`` tensor
    and no dropped token; an expert nobody chose is an empty group.

    ``held`` = (first, count) names the experts whose weights live
    here, out of the ``num_experts`` the router scores (None: all of
    them).  The layer routes over all ``num_experts`` and returns the
    part of the result its own experts give; a choice that lands on an
    absent expert adds nothing here (its chip adds it).

    ``scoring``, ``score_bias`` and ``routed_scale`` are the router's
    variant as a model's configuration states it
    (:func:`route_top_k`): softmax scores renormalised over the chosen,
    or sigmoid scores chosen by score + a ``score_bias`` parameter and
    weighed by the score alone, times ``routed_scale``.

    The router's matmul and scores run in float32; the experts in
    ``dtype``.  Sows ``moe_stats`` (``experts_hit``: held experts with
    at least one row; ``load_max``: rows of the busiest; ``rows``:
    (token, choice) pairs that reached a held expert) when that
    collection is mutable.
    """

    num_experts: int
    expert_dim: int
    top_k: int = 2
    held: tuple | None = None
    scoring: str = "softmax"
    score_bias: bool = False
    routed_scale: float = 1.0
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        shape = x.shape
        h = shape[-1]
        x = x.reshape(-1, h)
        n = x.shape[0]
        first, count = self.held or (0, self.num_experts)
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(
                f"held={(first, count)} names experts beyond the "
                f"num_experts={self.num_experts} the router scores"
            )
        if self.top_k > self.num_experts:
            raise ValueError(
                f"top_k={self.top_k} passes num_experts="
                f"{self.num_experts}"
            )
        k = self.top_k
        dt = self.dtype if self.dtype is not None else x.dtype
        init = nn.initializers.lecun_normal(batch_axis=(0,))

        with jax.named_scope("moe_router"):
            router = self.param(
                "router", nn.initializers.lecun_normal(),
                (h, self.num_experts), self.param_dtype,
            )
            logits = jnp.matmul(
                x.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            bias = self.param(
                "score_bias", nn.initializers.zeros, (self.num_experts,),
                self.param_dtype,
            ) if self.score_bias else None
            gates, ids = route_top_k(
                logits, k, self.scoring, bias, self.routed_scale
            )  # (N, k)

        w_gate = self.param("w_gate", init, (count, h, self.expert_dim),
                            self.param_dtype)
        w_up = self.param("w_up", init, (count, h, self.expert_dim),
                          self.param_dtype)
        w_down = self.param("w_down", init, (count, self.expert_dim, h),
                            self.param_dtype)

        with jax.named_scope("moe_experts"):
            local = ids.reshape(-1) - first  # (N*k,)
            here = (local >= 0) & (local < count)
            # Choices of absent experts sort behind every group: the
            # grouped matmul never reads them.
            local = jnp.where(here, local, count)
            order = jnp.argsort(local, stable=True)
            sizes = jnp.bincount(local, length=count + 1)[:count] \
                .astype(jnp.int32)
            rows = x.astype(dt)[order // k]  # (N*k, H), sorted by expert
            up = grouped_matmul(rows, w_up.astype(dt), sizes)
            gate = grouped_matmul(rows, w_gate.astype(dt), sizes)
            y = grouped_matmul(
                nn.silu(gate) * up, w_down.astype(dt), sizes
            )  # (N*k, H)
            weight = jnp.where(here, gates.reshape(-1), 0.0)[order]
            y = jnp.where(
                weight[:, None] > 0, y.astype(jnp.float32), 0.0
            ) * weight[:, None]
            out = jnp.zeros((n * k, h), jnp.float32).at[order].set(y)
            out = out.reshape(n, k, h).sum(axis=1).astype(dt)

        if self.is_mutable_collection("moe_stats") \
                and not self.is_initializing():
            for name, value in (("experts_hit", jnp.sum(sizes > 0)),
                                ("load_max", jnp.max(sizes)),
                                ("rows", jnp.sum(sizes))):
                self.sow("moe_stats", name, value.astype(jnp.int32),
                         reduce_fn=lambda _, new: new,
                         init_fn=lambda: jnp.int32(0))
        return out.reshape(shape)
