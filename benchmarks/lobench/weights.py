"""Weights from ``--seed``: leaf ``i`` of a configuration's leaf list
is ``normal(fold_in(key(seed), i)) * std`` (or zeros / ones), float32.

One generator feeds both sides.  The harness builds the whole tree on
the device in one jitted call and hands it to the program through its
REST surface; the plain reference calls :func:`leaf` for the leaves it
needs, when it needs them (layer by layer), so it never takes a weight
the program has held."""

from __future__ import annotations

import jax
import jax.numpy as jnp

STD = 0.02  # BERT's and GPT-2's published initializer_range


def key_for(seed: int):
    """``--seed`` may exceed 31 bits: fold the high bits in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seed must be a non-negative whole number")
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def leaf(key, index, shape, init: str):
    """Leaf ``index`` (a python int or a traced int32)."""
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init != "normal":
        raise ValueError(f"unknown init {init!r}")
    return STD * jax.random.normal(
        jax.random.fold_in(key, index), shape, jnp.float32
    )


def make_flat(seed: int, leaves: list) -> dict:
    """``{name: array}`` for ``leaves = [(name, shape, init), ...]``,
    made on the default device in ONE jitted call."""
    names = [n for n, _, _ in leaves]
    if len(set(names)) != len(names):
        raise ValueError("duplicate leaf names")

    def build(key):
        return {
            name: leaf(key, i, tuple(shape), init)
            for i, (name, shape, init) in enumerate(leaves)
        }

    return jax.jit(build)(key_for(seed))
