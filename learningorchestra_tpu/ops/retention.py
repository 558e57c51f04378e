"""Power retention, degree 2 (Buckman, Gelada et al., "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239): a causal mixer with
no softmax whose weight of key ``j`` for query ``i`` is ``(q_i . k_j /
sqrt(d))^2`` times the product of the gates between them,

    A_ij = (q_i . k_j / sqrt(d))^2 * exp(sum_{l=j+1..i} log g_l),
    y_i  = sum_j A_ij v_j / (sum_j A_ij + eps),        j <= i.

The square is an inner product of its own: with ``phi(u)`` the
symmetric square of ``u`` (``u_a^2``, and ``sqrt(2) u_a u_b`` for ``a <
b``: ``d (d + 1) / 2`` entries), ``phi(q) . phi(k) = (q . k)^2``.  So
the sums over ``j`` are a state that does not grow with the sequence:

    S_t = g_t S_{t-1} + phi(k_t) v_t^T,   z_t = g_t z_{t-1} + phi(k_t),
    y_t = (phi(q_t)^T S_t / d) / (phi(q_t)^T z_t / d + eps),

read against the state AFTER its update.  The full forward computes the
first form; the decode branch keeps ``S`` and ``z`` per slot and
key/value head (two leaves a layer, ``retained_state`` and
``retained_norm``, float32, no length axis) and steps the second:
:func:`retention_step`.

The layout of a state.  ``phi``'s entries in the order of
:func:`feature_pairs`, padded with zero entries to whole rows of 128:
``C`` rows.  ``retained_norm`` is ``(B, H_kv, C, 128)``;
``retained_state`` is ``(B, H_kv, C, d_v, 128)``, entry ``[c, j, l]``
the weight of feature ``128 c + l`` for value ``j``: features lie along
the lanes, so that a step multiplies whole rows of ``phi`` into it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from learningorchestra_tpu.ops.decode_attention import _partitioned
from learningorchestra_tpu.ops.layers import RMSNorm, apply_rope

HI = jax.lax.Precision.HIGHEST
LANES = 128
#: Values of a state block the kernel's inner loop holds in registers
#: at once: 32 sublanes x 128 lanes are 4 registers, and a group's five
#: accumulators of that size 20 of the 64.
_SLAB = 32
#: A whole state of one (slot, head) is one block, in and out, each
#: double-buffered: 4 x 4.3 MB at a 128-wide key.
_VMEM_LIMIT = 48 * 1024 * 1024


# ---------------------------------------------------------------------------
# The symmetric square
# ---------------------------------------------------------------------------


def state_rows(dim: int) -> int:
    """Entries of the symmetric square of a ``dim``-vector."""
    return dim * (dim + 1) // 2


@functools.lru_cache(maxsize=None)
def feature_pairs(dim: int):
    """(first index, second index, coefficient) of every entry of
    ``phi``, ``a <= b`` row-major, then zero entries up to a multiple
    of 128: numpy arrays of one length ``128 C``."""
    first, second = np.triu_indices(dim)
    coef = np.where(first == second, 1.0, math.sqrt(2.0))
    pad = -len(first) % LANES
    zeros = np.zeros(pad, first.dtype)
    return (np.concatenate([first, zeros]).astype(np.int32),
            np.concatenate([second, zeros]).astype(np.int32),
            np.concatenate([coef, np.zeros(pad)]).astype(np.float32))


def state_shapes(batch: int, kv_heads: int, key_dim: int, value_dim: int):
    """(shape of ``retained_state``, shape of ``retained_norm``)."""
    rows = len(feature_pairs(key_dim)[0]) // LANES
    return ((batch, kv_heads, rows, value_dim, LANES),
            (batch, kv_heads, rows, LANES))


def feature_map(u):
    """``phi(u)`` of ``u`` (..., d) in float32, (..., C, 128): each
    entry the product of two of ``u``'s values, picked by two one-hot
    matmuls (exact: one term a sum) so that the entries come out along
    the lanes, as the state holds them."""
    first, second, coef = feature_pairs(u.shape[-1])
    at = jnp.arange(u.shape[-1], dtype=jnp.int32)[:, None]

    def pick(index):
        return jnp.matmul(
            u, (at == index[None, :]).astype(u.dtype), precision=HI,
            preferred_element_type=jnp.float32,
        )

    phi = pick(first) * pick(second) * coef
    return phi.reshape(*u.shape[:-1], -1, LANES)


# ---------------------------------------------------------------------------
# One step of the recurrence
# ---------------------------------------------------------------------------
#
# ``state`` (B, H, C, D, 128) and ``norm`` (B, H, C, 128) float32;
# ``fq`` (B, H, G, C, 128) the group's queries' ``phi``, ``fk`` (B, H,
# C, 128) the key's (zeros where the step's key may not be seen: the
# state then only decays); ``v`` (B, H, D) and ``g`` (B, H) float32;
# ``live`` (B,): a slot that is not keeps its state untouched and reads
# zeros; ``fresh`` (B,): the slot begins from a zero state.  Returns
# (numerators (B, H, G, D), denominators (B, H, G), state, norm).


def plain_retention_step(state, norm, fq, fk, v, g, live, fresh):
    """The step in plain ``jax.numpy``: what runs off the TPU, and the
    kernel's oracle."""
    def rows(flag, ndim):
        return flag.reshape(-1, *([1] * (ndim - 1)))

    s1 = g[:, :, None, None, None] * jnp.where(
        rows(fresh, 5), 0.0, state
    ) + fk[:, :, :, None, :] * v[:, :, None, :, None]
    z1 = g[:, :, None, None] * jnp.where(rows(fresh, 4), 0.0, norm) + fk
    num = jnp.einsum("bhgcl,bhcdl->bhgd", fq, s1, precision=HI)
    den = jnp.einsum("bhgcl,bhcl->bhg", fq, z1, precision=HI)
    return (jnp.where(rows(live, 4), num, 0.0),
            jnp.where(rows(live, 3), den, 0.0),
            jnp.where(rows(live, 5), s1, state),
            jnp.where(rows(live, 4), z1, norm))


def _step_kernel(plan_ref, s_ref, z_ref, fq_ref, fk_ref, vg_ref,
                 num_ref, den_ref, s_out, z_out, acc_scr,
                 *, groups, dim, rows, slab):
    """One (slot, head) grid step: the whole state of that head, read
    once and written once.  ``plan_ref`` rows: live, fresh, and the
    (slot, head) block a slot that is not live stands on (the block of
    the step before it, so that nothing is fetched or written for
    it)."""
    b, h = pl.program_id(0), pl.program_id(1)
    live = plan_ref[0, b] != 0

    # No step before the first: were it of a dead slot, the blocks it
    # stands on would go back as they never came in.
    @pl.when(jnp.logical_not(live) & (b == 0) & (h == 0))
    def _keep():
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    @pl.when(live & (plan_ref[1, b] != 0))
    def _begin():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    @pl.when(live)
    def _step():
        g = vg_ref[0, 0, 1:2, :]  # (1, 128): the gate in every lane
        # vb[j, l] = v[j]: the step's value down the sublanes
        vb = jnp.broadcast_to(vg_ref[0, 0, 0:1, :], (LANES, LANES)).T
        for s0 in range(0, dim, slab):
            vb_s = vb[s0:s0 + slab]

            def column(c, accs, s0=s0, vb_s=vb_s):
                fk = fk_ref[0, 0, pl.ds(c, 1), :]
                new = g * s_ref[0, 0, c, s0:s0 + slab, :] + fk * vb_s
                s_out[0, 0, c, s0:s0 + slab, :] = new
                return tuple(
                    acc + fq_ref[0, 0, i, pl.ds(c, 1), :] * new
                    for i, acc in enumerate(accs)
                )

            accs = jax.lax.fori_loop(
                0, rows, column,
                tuple(jnp.zeros((slab, LANES), jnp.float32)
                      for _ in range(groups)),
            )
            for i, acc in enumerate(accs):
                acc_scr[i, s0:s0 + slab, :] = acc
        z1 = g * z_ref[0, 0] + fk_ref[0, 0]
        z_out[0, 0] = z1
        for i in range(groups):
            # the features lie along the lanes: sum them away
            num_ref[0, 0, i:i + 1, :dim] = jnp.sum(
                acc_scr[i].T, axis=0, keepdims=True
            )
            den = jnp.sum(
                jnp.sum(fq_ref[0, 0, i] * z1, axis=0, keepdims=True),
                axis=1, keepdims=True,
            )
            den_ref[0, 0, i:i + 1, :] = jnp.broadcast_to(den, (1, LANES))


def kernel_fits(state, fq) -> bool:
    """Whether :func:`retention_step_kernel`'s tiles divide these
    shapes: values on whole sublanes and no wider than a row of lanes,
    a group no larger than a tile's sublanes."""
    dim = state.shape[3]
    return dim % 8 == 0 and dim <= LANES and fq.shape[2] <= 8 \
        and state.dtype == jnp.float32


def _stand_ins(live, heads: int):
    """For each slot the (slot, head) block a dead one stands on: the
    last head of the last live slot before it, or the first block of
    the first live slot where there is none (slot 0's where none is
    live at all)."""
    n = live.shape[0]
    at = jnp.arange(n, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, at, -1))
    first = jnp.where(jnp.any(live), jnp.argmax(live), 0).astype(jnp.int32)
    slot = jnp.where(before >= 0, before, first)
    head = jnp.where(before >= 0, heads - 1, 0)
    return slot.astype(jnp.int32), head.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def retention_step_kernel(state, norm, fq, fk, v, g, live, fresh, *,
                          interpret: bool = False):
    """The kernel behind :func:`retention_step` on the TPU.  For each
    live slot and key/value head: ONE read of the state (a block of its
    own, 4.3 MB at a 128-wide key), decay, the step's outer product
    added, the group's queries read against the NEW state, ONE write
    into the aliased leaf; all on the vector unit in float32 (five
    queries a state would leave the matrix unit loading weights).  A
    dead slot's blocks are neither fetched nor written.  The enclosing
    jit must donate the two leaves for the update to be in place."""
    b, heads, rows, dim, _ = state.shape
    groups = fq.shape[2]
    slab = min(_SLAB, dim)
    live = live.astype(jnp.int32)
    slot_of, head_of = _stand_ins(live != 0, heads)
    plan = jnp.stack([live, fresh.astype(jnp.int32), slot_of, head_of])
    # the step's value in row 0 (its first ``dim`` lanes), the gate in
    # every lane of row 1: one small block a (slot, head)
    vg = jnp.zeros((b, heads, 8, LANES), jnp.float32)
    vg = vg.at[:, :, 0, :dim].set(v).at[:, :, 1, :].set(g[:, :, None])

    def block(bb, hh, plan_ref):
        on = plan_ref[0, bb] != 0
        return (jnp.where(on, bb, plan_ref[2, bb]),
                jnp.where(on, hh, plan_ref[3, bb]))

    def spec(*tail):
        return pl.BlockSpec(
            (1, 1, *tail),
            lambda bb, hh, plan_ref: (*block(bb, hh, plan_ref),
                                      *([0] * len(tail))),
        )

    call = pl.pallas_call(
        functools.partial(_step_kernel, groups=groups, dim=dim, rows=rows,
                          slab=slab),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, heads),
            in_specs=[
                spec(rows, dim, LANES), spec(rows, LANES),
                spec(groups, rows, LANES), spec(rows, LANES),
                spec(8, LANES),
            ],
            out_specs=[
                spec(8, LANES), spec(8, LANES),
                spec(rows, dim, LANES), spec(rows, LANES),
            ],
            scratch_shapes=[pltpu.VMEM((groups, dim, LANES), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, heads, 8, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, heads, 8, LANES), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct(norm.shape, norm.dtype),
        ],
        # the two leaves (inputs 1 and 2, the prefetched plan counted)
        # are the third and fourth results
        input_output_aliases={1: 2, 2: 3},
        compiler_params=pltpu.CompilerParams(
            # in order: a dead slot stands on the block before it
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="retention_step",
    )
    with jax.named_scope("retention_step"):
        num, den, state, norm = call(plan, state, norm, fq, fk, vg)
    on = live != 0
    return (jnp.where(on[:, None, None, None], num[:, :, :groups, :dim], 0.0),
            jnp.where(on[:, None, None], den[:, :, :groups, 0], 0.0),
            state, norm)


def retention_step(state, norm, fq, fk, v, g, live, fresh):
    """One step of every live slot's recurrence.  One path a platform:
    the kernel on the TPU where its tiles divide the shapes, the plain
    form elsewhere; both under the scope ``retention_step``."""
    if jax.default_backend() == "tpu" and kernel_fits(state, fq) \
            and not _partitioned():
        return retention_step_kernel(state, norm, fq, fk, v, g, live, fresh)
    with jax.named_scope("retention_step"):
        return plain_retention_step(state, norm, fq, fk, v, g, live, fresh)


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------


class PowerRetention(nn.Module):
    """Causal power retention of degree 2 over ``num_heads`` query heads
    that read ``num_kv_heads`` key/value heads in contiguous groups,
    with a key-side padding mask (B, T): a key that may not be seen adds
    nothing (the gates between still decay).  Queries and keys pass a
    weight-only RMS norm a head and are rotated (rotate-half,
    ``rope_theta``); the gate is one value a key/value head, ``log
    sigmoid(x W_g + b_g)`` in float32.  Matmuls in ``dtype``; gate,
    feature map, state and read-out in float32.

    ``decode=True`` follows ``MultiHeadSelfAttention``'s convention: an
    uninitialized pass sizes the cache and is the full forward (the
    attention form, quadratic); after it every call feeds ONE position
    a row at ``cache_index`` (scalar: lockstep; (B,): each row at its
    own) through the recurrence.  A row at position 0 begins from a
    zero state whatever its leaves hold; a row whose ``key_mask`` shows
    no key sits the step out."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    eps: float = 1e-6
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x, key_mask=None):
        b, t, hidden = x.shape
        heads, kvh, hd = self.num_heads, self.num_kv_heads, self.head_dim
        if heads % kvh:
            raise ValueError(
                f"num_heads={heads} not divisible by num_kv_heads={kvh}"
            )
        group = heads // kvh
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)

        def proj(name, n):
            y = nn.DenseGeneral((n, hd), use_bias=False, name=name, **kw)(x)
            return y.transpose(0, 2, 1, 3)  # (B, n, T, hd)

        with jax.named_scope("retention_proj"):
            q, k, v = proj("query", heads), proj("key", kvh), \
                proj("value", kvh)
            q = RMSNorm(self.norm_eps, name="q_norm", **kw)(q)
            k = RMSNorm(self.norm_eps, name="k_norm", **kw)(k)
        with jax.named_scope("retention_gate"):
            log_g = jax.nn.log_sigmoid(nn.Dense(
                kvh, use_bias=True, name="gate", dtype=jnp.float32,
                param_dtype=self.param_dtype,
            )(x.astype(jnp.float32)))  # (B, T, H_kv)
        dt = self.dtype if self.dtype is not None else x.dtype

        def out_proj(y):  # (B, H_kv, G, T, hd) float32
            with jax.named_scope("retention_out"):
                y = y.reshape(b, heads, t, hd).transpose(0, 2, 1, 3)
                return nn.DenseGeneral(
                    hidden, axis=(-2, -1), use_bias=False, name="out", **kw
                )(y.astype(dt))

        is_initialized = self.decode and self.has_variable(
            "cache", "retained_state"
        )
        if self.decode:
            s_shape, z_shape = state_shapes(b, kvh, hd, hd)
            state = self.variable("cache", "retained_state", jnp.zeros,
                                  s_shape, jnp.float32)
            norm = self.variable("cache", "retained_norm", jnp.zeros,
                                 z_shape, jnp.float32)
            ci = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
        if is_initialized:
            if t != 1:
                raise ValueError(
                    "the retention state takes ONE position a step; got "
                    f"a {t}-token chunk"
                )
            idx = ci.value
            at = idx if idx.ndim == 1 else jnp.full((b,), idx)
            with jax.named_scope("retention_proj"):
                q = apply_rope(q, at[:, None], self.rope_theta)
                k = apply_rope(k, at[:, None], self.rope_theta)
            if key_mask is None:
                live = own = jnp.ones((b,), bool)
            else:
                keep = key_mask.astype(bool)
                live = jnp.any(keep, axis=-1)
                own = jnp.take_along_axis(keep, at[:, None], axis=1)[:, 0]
            with jax.named_scope("retention_step"):
                fq = feature_map(q[:, :, 0].reshape(b, kvh, group, hd))
                fk = feature_map(k[:, :, 0]) * own[:, None, None, None]
            ci.value = idx + 1
            num, den, state.value, norm.value = retention_step(
                state.value, norm.value, fq, fk,
                v[:, :, 0].astype(jnp.float32), jnp.exp(log_g[:, 0]),
                live, at == 0,
            )
            y = (num / hd) / (den[..., None] / hd + self.eps)
            return out_proj(y[:, :, :, None, :])

        # The full forward: the attention form.
        pos = jnp.arange(t)
        q = apply_rope(q, pos, self.rope_theta)
        k = apply_rope(k, pos, self.rope_theta)
        s = jnp.einsum(
            "bhgqd,bhkd->bhgqk", q.reshape(b, kvh, group, t, hd), k,
            preferred_element_type=jnp.float32,
        ) / math.sqrt(hd)
        decay = jnp.cumsum(log_g, axis=1).transpose(0, 2, 1)  # (B, H_kv, T)
        keep = jnp.tril(jnp.ones((t, t), bool))[None]
        if key_mask is not None:
            keep = keep & key_mask.astype(bool)[:, None, :]
        # masked before the exponent: a later key's difference is > 0
        weight = jnp.square(s) * jnp.exp(jnp.where(
            keep[:, None], decay[:, :, :, None] - decay[:, :, None, :],
            -jnp.inf,
        ))[:, :, None]
        num = jnp.einsum(
            "bhgqk,bhkd->bhgqd", weight, v.astype(jnp.float32),
        )
        return out_proj(
            num / (jnp.sum(weight, -1, keepdims=True) + self.eps)
        )
