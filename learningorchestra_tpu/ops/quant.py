"""Row-wise int8 quantization kernels (Pallas).

Artifact/HBM footprint tool: trained parameter matrices and cached
activations quantize to int8 with one scale per row — 4x smaller than
f32 — and dequantize on load.  On TPU the quantizer uses the on-core
PRNG for stochastic rounding (unbiased: E[q] = x/scale, so repeated
quantize→accumulate steps don't drift the way round-to-nearest does);
off-TPU the same kernels run in interpret mode.

API:
  quantize_rowwise(x)   -> (values int8 (n, d), scales f32 (n, 1))
  dequantize_rowwise(v, s) -> f32 (n, d)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _quantize_kernel(seed_ref, x_ref, values_ref, scales_ref, *, stochastic):
    x = x_ref[:].astype(jnp.float32)
    abs_max = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(abs_max, 1e-12) / 127.0
    scaled = x / scale
    if stochastic:
        # Re-seed per row block so streams stay independent across the
        # grid (every program would otherwise draw identical bits).
        # Multi-word seed: (seed + i) would collide with (seed+1, i-1)
        # when callers seed by step counter.
        pltpu.prng_seed(seed_ref[0], pl.program_id(0))
        bits = pltpu.bitcast(
            pltpu.prng_random_bits(scaled.shape), jnp.uint32
        )
        # Uniform in [0, 1): 23 mantissa bits of the random word.  The
        # shift clears the sign bit, so the int32 hop is lossless —
        # Mosaic has no direct uint32->f32 cast.
        u = (
            (bits >> jnp.uint32(9)).astype(jnp.int32).astype(jnp.float32)
        ) * (1.0 / (1 << 23))
        q = jnp.floor(scaled + u)
    else:
        q = jnp.round(scaled)
    values_ref[:] = jnp.clip(q, -127, 127).astype(jnp.int8)
    scales_ref[:] = scale


def _dequantize_kernel(values_ref, scales_ref, out_ref):
    out_ref[:] = values_ref[:].astype(jnp.float32) * scales_ref[:]


def _row_block(n: int, d: int, bytes_per_elt: int = 4) -> int:
    """Rows per grid step, sized to ~4 MB of VMEM per staged block so
    arbitrarily large matrices (e.g. a 30k x 768 embedding) compile —
    a single whole-array block caps out at VMEM (~16 MB)."""
    target = (4 * 1024 * 1024) // max(1, d * bytes_per_elt)
    block = max(8, min(n, target) // 8 * 8)
    return block


def quantize_rowwise(
    x,
    *,
    stochastic: bool | None = None,
    seed: int = 0,
    interpret: bool | None = None,
):
    """int8-quantize each row of a 2-D array with a per-row scale.

    ``stochastic`` defaults to True on TPU (hardware PRNG), False in
    interpret mode (the interpreter's PRNG is slow and tests want
    determinism).
    """
    if x.ndim != 2:
        raise ValueError(f"expected 2-D input, got shape {x.shape}")
    if interpret is None:
        interpret = _auto_interpret()
    if stochastic is None:
        stochastic = not interpret
    n, d = x.shape
    bn = _row_block(n, d)
    pad = (-n) % bn
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    seed_arr = jnp.asarray([seed], jnp.int32)
    call = pl.pallas_call(
        functools.partial(_quantize_kernel, stochastic=stochastic),
        grid=((n + pad) // bn,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n + pad, d), jnp.int8),
            jax.ShapeDtypeStruct((n + pad, 1), jnp.float32),
        ],
        interpret=interpret,
        name="quant_rowwise",
    )
    with jax.named_scope("quant_rowwise"):
        values, scales = call(seed_arr, x)
    return (values[:n], scales[:n]) if pad else (values, scales)


def dequantize_rowwise(values, scales, *, interpret: bool | None = None):
    if interpret is None:
        interpret = _auto_interpret()
    n, d = values.shape
    # Block by the f32 OUTPUT element size — the output block is the
    # largest VMEM resident here, not the int8 input.
    bn = _row_block(n, d)
    pad = (-n) % bn
    if pad:
        values = jnp.pad(values, ((0, pad), (0, 0)))
        scales = jnp.pad(scales, ((0, pad), (0, 0)))
    call = pl.pallas_call(
        _dequantize_kernel,
        grid=((n + pad) // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, d), jnp.float32),
        interpret=interpret,
        name="dequant_rowwise",
    )
    with jax.named_scope("dequant_rowwise"):
        out = call(values, scales)
    return out[:n] if pad else out


# -- quantized artifact format (pytree level) --------------------------------


class QuantizedLeaf:
    """Host-side container for one int8-quantized parameter tensor.

    The on-disk unit of the quantized artifact format: row-wise int8
    values + per-row f32 scales + the original shape/dtype.  Plain
    numpy fields, so dill/pickle round-trips it without this module
    imported at save time on the reader's side.
    """

    __slots__ = ("values", "scales", "shape", "dtype")

    def __init__(self, values, scales, shape, dtype):
        self.values = values
        self.scales = scales
        self.shape = tuple(shape)
        self.dtype = str(dtype)

    def __repr__(self):
        return (f"QuantizedLeaf(shape={self.shape}, dtype={self.dtype}, "
                f"int8+scales)")


# Below this many elements a tensor stays full precision: biases and
# norm scales are tiny (no footprint win) and precision-critical.
_QUANT_MIN_ELEMENTS = 4096


def quantize_pytree(tree, *, min_elements: int = _QUANT_MIN_ELEMENTS):
    """int8-quantize every large float tensor of a (host) pytree.

    >=2-D float leaves with at least ``min_elements`` elements become
    :class:`QuantizedLeaf` (leading axes flattened so the row-wise
    kernel sees 2-D); everything else passes through untouched.
    Rounding is DETERMINISTIC (round-to-nearest): a persistence format
    must load the same bytes every save — stochastic rounding is for
    in-training accumulation, not artifacts.
    """
    import numpy as np

    def leaf_fn(l):
        arr = np.asarray(l)
        if (
            arr.ndim >= 2
            and arr.size >= min_elements
            and np.issubdtype(arr.dtype, np.floating)
        ):
            mat = jnp.asarray(
                arr.astype(np.float32).reshape(-1, arr.shape[-1])
            )
            values, scales = quantize_rowwise(mat, stochastic=False)
            return QuantizedLeaf(
                np.asarray(values), np.asarray(scales),
                arr.shape, arr.dtype,
            )
        return l

    return jax.tree_util.tree_map(leaf_fn, tree)


def dequantize_pytree(tree):
    """Inverse of :func:`quantize_pytree`: QuantizedLeaf → dense array
    in the original shape/dtype; other leaves pass through."""
    import numpy as np

    def leaf_fn(l):
        if isinstance(l, QuantizedLeaf):
            mat = dequantize_rowwise(
                jnp.asarray(l.values), jnp.asarray(l.scales)
            )
            return np.asarray(mat).reshape(l.shape).astype(l.dtype)
        return l

    return jax.tree_util.tree_map(
        leaf_fn, tree,
        is_leaf=lambda x: isinstance(x, QuantizedLeaf),
    )


def has_quantized_leaves(tree) -> bool:
    return any(
        isinstance(l, QuantizedLeaf)
        for l in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, QuantizedLeaf)
        )
    )
