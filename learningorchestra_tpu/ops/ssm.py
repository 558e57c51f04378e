"""Mamba-2 mixer (Dao & Gu, "Transformers are SSMs: Generalized Models
and Efficient Algorithms Through Structured State Space Duality",
arXiv:2405.21060): a selective state space layer whose heads each keep
an ``N x P`` state,

    S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T,    y_t = S_t^T C_t + D x_t,

with ``x`` a head's ``P`` values, ``B`` and ``C`` the ``N``-wide input
and output maps its group shares, ``dt`` the head's step size and ``A``
its (negative) decay rate.  Before it, a causal depthwise convolution
of ``conv_width`` taps over ``x``, ``B`` and ``C``; after it, the gate
``silu(z)`` and an RMS norm over each group of heads.

The full forward computes the recurrence in the chunk form of the
paper's state space duality (:func:`ssd_chunked`): quadratic inside a
chunk under the cumulated decays, the state handed from chunk to chunk.
The decode branch keeps, a slot, the state (``ssm_state``, ``(B, H, N,
P)`` float32: a head's values along the lanes, its state's rows down
the sublanes) and the last ``conv_width - 1`` inputs of the convolution
(``conv_tail``, ``(B, conv_width - 1, channels)`` float32), and steps
the first form one position a call: :func:`conv_step`, then
:func:`ssm_step`, a kernel on the TPU.

A position whose key mask is off (pad id 0) is passed over: its
convolution input and output are zero and so is its step size, so the
state neither decays nor gains anything there and the mixer reads 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from learningorchestra_tpu.ops.decode_attention import _partitioned
from learningorchestra_tpu.ops.retention import _stand_ins

HI = jax.lax.Precision.HIGHEST
#: A group's states of one slot are one block, in and out, each
#: double-buffered: 4 x 2 MB at 16 heads of 256 x 128.
_VMEM_LIMIT = 32 * 1024 * 1024
#: Rows of a head's state the kernel takes at a time: 8 vector
#: registers of the state, 8 of ``B`` and 8 of ``C``.
_SLAB = 64
LANES = 128


# ---------------------------------------------------------------------------
# The full forward: the chunk form
# ---------------------------------------------------------------------------


def ssd_chunked(x, b, c, dt, a, chunk: int):
    """``y_t = S_t^T C_t`` for every position (``D x`` not added) from a
    zero state: ``x`` (B, T, H, P), ``b`` and ``c`` (B, T, G, N), ``dt``
    (B, T, H), float32; ``a`` (H,) the decay rates.  Chunks of ``chunk``
    positions (the last one padded with positions that change nothing):
    inside one, ``y_i = sum_{j<=i} e^{L_i - L_j} (C_i . B_j) dt_j x_j``
    with ``L`` the cumulated ``dt A``, plus ``e^{L_i} C_i`` read against
    the state the chunk began from; the state after a chunk is that
    state decayed over the chunk plus the chunk's own sum."""
    bsz, t, h, p = x.shape
    g = b.shape[2]
    n_chunks = -(-t // chunk)
    pad = n_chunks * chunk - t

    def chunks(u):
        u = jnp.pad(u, [(0, 0), (0, pad)] + [(0, 0)] * (u.ndim - 2))
        return u.reshape(bsz, n_chunks, chunk, *u.shape[2:])

    x, dt = chunks(x), chunks(dt)
    # each head reads its group's maps
    bh, ch = (jnp.repeat(chunks(u), h // g, axis=3) for u in (b, c))
    big_l = jnp.cumsum(dt * a, axis=2)  # (B, C, Q, H)
    lh = big_l.transpose(0, 1, 3, 2)  # (B, C, H, Q)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    # masked before the exponent: a later position's difference is > 0
    decay = jnp.exp(jnp.where(
        causal, lh[..., :, None] - lh[..., None, :], -jnp.inf))
    score = jnp.einsum("bcihn,bcjhn->bchij", ch, bh, precision=HI) \
        * decay * dt.transpose(0, 1, 3, 2)[..., None, :]
    inner = jnp.einsum("bchij,bcjhp->bcihp", score, x, precision=HI)
    to_end = jnp.exp(big_l[:, :, -1:] - big_l) * dt  # (B, C, Q, H)
    own = jnp.einsum("bcjh,bcjhn,bcjhp->bchnp", to_end, bh, x,
                     precision=HI)

    def hand_on(state, step):
        own_c, total_c = step
        return total_c[..., None, None] * state + own_c, state

    _, begun = jax.lax.scan(
        hand_on, jnp.zeros((bsz, h, bh.shape[-1], p), jnp.float32),
        (own.swapaxes(0, 1), jnp.exp(big_l[:, :, -1]).swapaxes(0, 1)),
    )
    outer = jnp.einsum("bcihn,bchnp->bcihp", ch * jnp.exp(big_l)[..., None],
                       begun.swapaxes(0, 1), precision=HI)
    y = (inner + outer).reshape(bsz, n_chunks * chunk, h, p)
    return y[:, :t]


# ---------------------------------------------------------------------------
# One step of the recurrence
# ---------------------------------------------------------------------------
#
# ``state`` (S, H, N, P) float32; ``x`` (S, H, P), ``b`` and ``c`` (S,
# G, N), ``dt`` (S, H) float32 (0 where the step's position may not be
# seen: the state is then left as it was, and the convolution has made
# ``x``, ``b``, ``c`` 0); ``a`` and ``d`` (H,); ``live`` (S,): a slot
# that is not keeps its state untouched and reads zeros; ``fresh``
# (S,): the slot begins from a zero state.  Returns (y (S, H, P), the
# state after the step).


def plain_ssm_step(state, x, b, c, dt, a, d, live, fresh):
    """The step in plain ``jax.numpy``: what runs off the TPU, and the
    kernel's oracle."""
    h, g = state.shape[1], b.shape[1]
    bh, ch = (jnp.repeat(u, h // g, axis=1) for u in (b, c))
    s0 = jnp.where(fresh[:, None, None, None], 0.0, state)
    s1 = jnp.exp(dt * a)[..., None, None] * s0 \
        + bh[..., None] * (dt[..., None] * x)[:, :, None, :]
    y = jnp.einsum("shnp,shn->shp", s1, ch, precision=HI) \
        + d[:, None] * x
    return (jnp.where(live[:, None, None], y, 0.0),
            jnp.where(live[:, None, None, None], s1, state))


def _step_kernel(plan_ref, s_ref, hv_ref, bc_ref, y_ref, s_out, bc_scr, *,
                 heads: int):
    """One (slot, group) grid step: the states of the group's ``heads``
    heads read once, decayed, added to, read out against ``C`` and
    written once.  ``plan_ref`` rows: live, fresh, and the (slot,
    group) block a slot that is not live stands on (the block of the
    step before it, so that nothing is fetched or written for it).
    ``hv_ref`` rows of a head: its ``P`` values, then its decay, step
    size and ``D`` in every lane; ``bc_ref`` rows: ``B``, ``C``."""
    slot, grp = pl.program_id(0), pl.program_id(1)
    live = plan_ref[0, slot] != 0

    # No step before the first: were it of a dead slot, the block it
    # stands on would go back as it never came in.
    @pl.when(jnp.logical_not(live) & (slot == 0) & (grp == 0))
    def _keep():
        s_out[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live & (plan_ref[1, slot] != 0))
    def _begin():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(live)
    def _step():
        rows, dim = s_ref.shape[2], s_ref.shape[3]
        # B and C down the sublanes, a row's value in every lane: once
        # for the group's heads
        for j in range(2):
            bc_scr[j] = jnp.broadcast_to(
                bc_ref[0, 0, j:j + 1, :], (dim, rows)).T
        for i in range(heads):
            x_row = hv_ref[0, i, 0:1, :]  # (1, P)
            decay = hv_ref[0, i, 1:2, :]
            dtx = hv_ref[0, i, 2:3, :] * x_row
            acc = jnp.zeros((_SLAB, dim), jnp.float32)
            for r0 in range(0, rows, _SLAB):
                span = slice(r0, r0 + _SLAB)
                new = decay * s_ref[0, i, span, :] + bc_scr[0, span, :] * dtx
                s_out[0, i, span, :] = new
                acc = acc + new * bc_scr[1, span, :]
            # the state's rows lie down the sublanes: sum them away
            y_ref[0, 0, i:i + 1, :] = jnp.sum(acc, axis=0, keepdims=True) \
                + hv_ref[0, i, 3:4, :] * x_row


def kernel_fits(state) -> bool:
    """Whether :func:`ssm_step_kernel`'s tiles divide these shapes: a
    head's values a row of 128 lanes, its state's rows whole rows of
    128, float32."""
    _, _, rows, dim = state.shape
    return dim == LANES and rows % LANES == 0 and state.dtype == jnp.float32


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_step_kernel(state, x, b, c, dt, a, d, live, fresh, *,
                    interpret: bool = False):
    """The kernel behind :func:`ssm_step` on the TPU.  For every live
    slot and group: ONE read of its heads' states (a block of its own,
    2 MB at 16 heads of 256 x 128), decay, the step's ``dt B x^T``
    added, the new state read out against ``C`` (plus ``D x``), ONE
    write into the aliased leaf; all on the vector unit in float32.  A
    dead slot's blocks are neither fetched nor written.  The enclosing
    jit must donate the leaf for the update to be in place."""
    s, h, rows, dim = state.shape
    g = b.shape[1]
    heads = h // g
    live = live.astype(jnp.int32)
    slot_of, grp_of = _stand_ins(live != 0, g)
    plan = jnp.stack([live, fresh.astype(jnp.int32), slot_of, grp_of])
    lanes = lambda v: jnp.broadcast_to(v[..., None], (s, h, dim))  # noqa: E731
    hv = jnp.stack([x, lanes(jnp.exp(dt * a)), lanes(dt),
                    lanes(jnp.broadcast_to(d, (s, h)))], axis=2)
    bc = jnp.stack([b, c], axis=2)  # (S, G, 2, N)

    def spec(*block):
        """The block of one (slot, group): a live slot's own, a dead
        one's the block it stands on (plan rows 2 and 3)."""
        def index(ss, gg, plan_ref):
            on = plan_ref[0, ss] != 0
            return (jnp.where(on, ss, plan_ref[2, ss]),
                    jnp.where(on, gg, plan_ref[3, ss]),
                    *([0] * (len(block) - 1)))

        return pl.BlockSpec((1, *block), index)

    call = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, g),
            in_specs=[spec(heads, rows, dim), spec(heads, 4, dim),
                      spec(1, 2, rows)],
            out_specs=[spec(1, heads, dim), spec(heads, rows, dim)],
            scratch_shapes=[pltpu.VMEM((2, rows, dim), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((s, g, heads, dim), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # the leaf (input 1, the prefetched plan counted) is the
        # second result
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            # in order: a dead slot stands on the block before it
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="ssm_step",
    )
    with jax.named_scope("ssm_step"):
        y, state = call(plan, state, hv, bc)
    y = y.reshape(s, h, dim)
    return jnp.where((live != 0)[:, None, None], y, 0.0), state


def ssm_step(state, x, b, c, dt, a, d, live, fresh):
    """One position of every live slot through its state: the kernel on
    the TPU where its tiles divide the shapes, the plain form
    elsewhere; both under the scope ``ssm_step``."""
    if jax.default_backend() == "tpu" and kernel_fits(state) \
            and not _partitioned():
        return ssm_step_kernel(state, x, b, c, dt, a, d, live, fresh)
    with jax.named_scope("ssm_step"):
        return plain_ssm_step(state, x, b, c, dt, a, d, live, fresh)


def conv_step(tail, step_in, kernel, bias, live, fresh):
    """One position of every slot through the causal depthwise
    convolution: ``tail`` (S, W - 1, channels), the slot's last inputs
    (zeros for a ``fresh`` slot), ``step_in`` (S, channels) the new
    one.  Returns (the convolution's output before its activation, the
    tail after the step: shifted by one for a ``live`` slot, as it was
    for any other)."""
    window = jnp.concatenate([
        jnp.where(fresh[:, None, None], 0.0, tail), step_in[:, None],
    ], axis=1)  # (S, W, channels)
    out = jnp.sum(window * kernel[None], axis=1) + bias
    return out, jnp.where(live[:, None, None], window[:, 1:], tail)


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------


def _dt_bias_init(dt_min: float = 1e-3, dt_max: float = 1e-1):
    """The family's: ``softplus^-1(dt)`` for step sizes spread evenly in
    the logarithm over [dt_min, dt_max] across the heads."""
    def init(_key, shape, dtype=jnp.float32):
        dt = np.exp(np.linspace(np.log(dt_min), np.log(dt_max), shape[0]))
        return jnp.asarray(dt + np.log(-np.expm1(-dt)), dtype)

    return init


def _a_log_init(_key, shape, dtype=jnp.float32):
    """The family's: ``A = -(1 .. H)``."""
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32)) \
        .astype(dtype)


def _conv_init(width: int):
    """PyTorch's ``Conv1d`` default for a depthwise convolution of
    ``width`` taps: uniform in +-1 / sqrt(width), kernel and bias."""
    bound = 1.0 / np.sqrt(width)

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer of ``num_heads`` heads of ``head_dim`` values,
    ``n_groups`` groups of ``B`` and ``C`` of ``state_dim`` (head ``j``
    reads group ``j // (num_heads / n_groups)``), a causal depthwise
    convolution of ``conv_width`` taps with a bias over ``x``, ``B``,
    ``C``, and the gated RMS norm over each group of heads (the gate
    applied before the norm).  ``in_multiplier`` scales the input of
    the in-projection, ``part_multipliers`` its five parts (``z``,
    ``x``, ``B``, ``C``, ``dt``).  Projections in ``dtype``; the
    convolution, step sizes, state, read-out and norm in float32.

    ``decode=True`` follows ``MultiHeadSelfAttention``'s convention: an
    uninitialized pass sizes the cache and is the full forward
    (:func:`ssd_chunked`, chunks of ``chunk_size``); after it every call
    feeds ONE position a row from ``cache_index`` on (scalar: lockstep;
    (B,): each row at its own) through the convolution's tail and the
    state.  A row at position 0 begins from a zero state and a zero
    tail whatever its leaves hold; a row whose ``key_mask`` shows no key
    sits the step out."""

    num_heads: int
    head_dim: int
    n_groups: int
    state_dim: int
    conv_width: int = 4
    chunk_size: int = 128
    in_multiplier: float = 1.0
    part_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    norm_eps: float = 1e-5
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, u, key_mask=None):
        b, t, _ = u.shape
        h, p, g, n = self.num_heads, self.head_dim, self.n_groups, \
            self.state_dim
        if h % g:
            raise ValueError(f"num_heads={h} not divisible by n_groups={g}")
        inner, bc_width = h * p, g * n
        channels = inner + 2 * bc_width
        parts = (inner, inner, bc_width, bc_width, h)
        f32 = jnp.float32
        with jax.named_scope("ssm_proj"):
            if self.in_multiplier != 1.0:
                u = u * jnp.asarray(self.in_multiplier, u.dtype)
            zxbcdt = nn.Dense(
                sum(parts), use_bias=False, name="in_proj",
                dtype=self.dtype, param_dtype=self.param_dtype,
            )(u).astype(f32) * np.concatenate([
                np.full(w, m, np.float32)
                for w, m in zip(parts, self.part_multipliers)
            ])
            z, xbc, dt = jnp.split(
                zxbcdt, np.cumsum([inner, channels]), axis=-1)
        kernel, bias = (
            self.param(name, _conv_init(self.conv_width), shape,
                       self.param_dtype).astype(f32)
            for name, shape in (("conv_kernel", (self.conv_width, channels)),
                                ("conv_bias", (channels,)))
        )
        a = -jnp.exp(self.param("A_log", _a_log_init, (h,),
                                self.param_dtype).astype(f32))
        dt_bias = self.param("dt_bias", _dt_bias_init(), (h,),
                             self.param_dtype).astype(f32)
        d = self.param("D", nn.initializers.ones, (h,),
                       self.param_dtype).astype(f32)

        is_initialized = self.decode and self.has_variable(
            "cache", "ssm_state")
        if self.decode:
            state = self.variable("cache", "ssm_state", jnp.zeros,
                                  (b, h, n, p), f32)
            tail = self.variable("cache", "conv_tail", jnp.zeros,
                                 (b, self.conv_width - 1, channels), f32)
            ci = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
        if is_initialized:
            y = self._recurrent(xbc, dt, kernel, bias, dt_bias, a, d,
                                key_mask, state, tail, ci)
        else:
            seen = jnp.ones((b, t), f32) if key_mask is None \
                else key_mask.astype(f32)
            with jax.named_scope("ssm_conv"):
                padded = jnp.pad(xbc * seen[..., None],
                                 [(0, 0), (self.conv_width - 1, 0), (0, 0)])
                conv = sum(
                    padded[:, i:i + t] * kernel[i]
                    for i in range(self.conv_width)
                ) + bias
                xbc = jax.nn.silu(conv) * seen[..., None]
            x, bm, cm = jnp.split(xbc, [inner, inner + bc_width], axis=-1)
            with jax.named_scope("ssm_step"):
                dt = jax.nn.softplus(dt + dt_bias) * seen[..., None]
                x = x.reshape(b, t, h, p)
                y = ssd_chunked(
                    x, bm.reshape(b, t, g, n), cm.reshape(b, t, g, n),
                    dt, a, self.chunk_size,
                ) + d[:, None] * x
            y = y.reshape(b, t, inner)
        with jax.named_scope("ssm_norm"):
            grouped = (y * jax.nn.silu(z)).reshape(b, t, g, inner // g)
            grouped = grouped * jax.lax.rsqrt(jnp.mean(
                jnp.square(grouped), -1, keepdims=True) + self.norm_eps)
            scale = self.param("norm_scale", nn.initializers.ones,
                               (inner,), self.param_dtype)
            y = grouped.reshape(b, t, inner) * scale.astype(f32)
        with jax.named_scope("ssm_out"):
            return nn.Dense(
                u.shape[-1], use_bias=False, name="out_proj",
                dtype=self.dtype, param_dtype=self.param_dtype,
            )(y.astype(self.dtype or u.dtype))

    def _recurrent(self, xbc, dt, kernel, bias, dt_bias, a, d, key_mask,
                   state, tail, ci):
        """The decode branch: ONE position a row through the tail of the
        convolution and the state.  Returns the read-out (B, 1, H P),
        zeros on a row that sits the step out."""
        b, t, _ = xbc.shape
        if t != 1:
            raise ValueError(
                "the decode branch of a state space layer takes one "
                f"position a row a call; got {t}")
        h, p, g, n = self.num_heads, self.head_dim, self.n_groups, \
            self.state_dim
        inner = h * p
        idx = ci.value
        at = idx if idx.ndim == 1 else jnp.full((b,), idx)
        if key_mask is None:
            own = live = jnp.ones((b,), bool)
        else:
            keep = key_mask.astype(bool)
            own = jnp.take_along_axis(
                keep, jnp.minimum(at, keep.shape[-1] - 1)[:, None], axis=1
            )[:, 0]
            live = jnp.any(keep, axis=-1)
        fresh = at == 0
        ci.value = idx + 1
        with jax.named_scope("ssm_conv"):
            conv, tail.value = conv_step(
                tail.value, xbc[:, 0] * own[:, None], kernel, bias, live,
                fresh)
            xbc1 = jax.nn.silu(conv) * own[:, None]
        x, bm, cm = jnp.split(xbc1, [inner, inner + g * n], axis=-1)
        with jax.named_scope("ssm_step"):
            step = jax.nn.softplus(dt[:, 0] + dt_bias) * own[:, None]
        y, state.value = ssm_step(
            state.value, x.reshape(b, h, p), bm.reshape(b, g, n),
            cm.reshape(b, g, n), step, a, d, live, fresh,
        )
        return y.reshape(b, 1, inner)
