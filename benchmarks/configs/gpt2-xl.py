"""Plain reference of the ``gpt2-xl`` configuration, as the program's
``models/text.py DecoderLM`` states it (departures from the published
model are under ``assumed`` in the .json beside this file): token +
learned position embeddings, causal pre-LN blocks, final LayerNorm, an
untied LM head with a bias.  A key whose token id is 0 (the pad id) is
never attended to.  float32 at ``highest`` precision, one full forward
over the whole row, no cache; it imports nothing of the program and
makes its own weights from the seed, layer by layer, so it never holds
the whole model."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from lobench import plain, weights


def _block_leaves(cp):
    return plain.block_leaves(cp["hidden_dim"], cp["mlp_dim"])


def leaves(cp: dict) -> list:
    h, v = cp["hidden_dim"], cp["vocab_size"]
    out = [
        ("tok_emb", (v, h), "normal"),
        ("pos_emb", (cp["max_len"], h), "normal"),
    ]
    for layer in range(cp["num_layers"]):
        out += [(f"l{layer}.{n}", s, i) for n, s, i in _block_leaves(cp)]
    out += [
        ("lnf_s", (h,), "ones"), ("lnf_b", (h,), "zeros"),
        ("head_w", (h, v), "normal"), ("head_b", (v,), "zeros"),
    ]
    return out


def program_params(flat: dict, cp: dict) -> dict:
    """The flat leaves as the program's flax variables."""
    tree = {
        "Embed_0": {"embedding": flat["tok_emb"]},
        "Embed_1": {"embedding": flat["pos_emb"]},
        "LayerNorm_0": {"scale": flat["lnf_s"], "bias": flat["lnf_b"]},
        "Dense_0": {"kernel": flat["head_w"], "bias": flat["head_b"]},
    }
    for layer in range(cp["num_layers"]):
        pre = f"l{layer}."
        tree[f"TransformerBlock_{layer}"] = plain.block_program_tree(
            {k[len(pre):]: a for k, a in flat.items()
             if k.startswith(pre)},
            cp["num_heads"],
        )
    return {"params": tree}


def _index_of(cp: dict, name: str) -> int:
    return [n for n, _, _ in leaves(cp)].index(name)


@functools.partial(jax.jit, static_argnames=("cp_items",))
def _embed(key, tokens, *, cp_items):
    cp = dict(cp_items)
    spec = leaves(cp)
    tok = weights.leaf(key, 0, spec[0][1], "normal")
    pos = weights.leaf(key, 1, spec[1][1], "normal")
    return tok[tokens] + pos[None, : tokens.shape[1]]


@functools.partial(jax.jit, static_argnames=("cp_items", "quant"))
def _layer(key, layer, x, key_mask, *, cp_items, quant):
    """Block ``layer`` (a traced int: one program serves every layer)
    with its weights made here from the seed."""
    cp = dict(cp_items)
    spec = _block_leaves(cp)
    base = 2 + layer * len(spec)
    w = {
        name: weights.leaf(key, base + j, shape, init)
        for j, (name, shape, init) in enumerate(spec)
    }
    return plain.block(x, w, cp["num_heads"], key_mask, True, quant)


@functools.partial(jax.jit, static_argnames=("cp_items", "quant"))
def _head(key, x, *, cp_items, quant):
    cp = dict(cp_items)
    spec = leaves(cp)
    n = len(spec)
    lnf_s, lnf_b, head_w, head_b = (
        weights.leaf(key, n - 4 + j, spec[n - 4 + j][1], spec[n - 4 + j][2])
        for j in range(4)
    )
    x = plain.layer_norm(x, lnf_s, lnf_b)
    return plain.dot(x, head_w, quant) + head_b


def reference_logits(seed: int, cp: dict, tokens, quant=None):
    """(R, T, V) logits of one full forward over ``tokens`` (R, T),
    zero-padded rows allowed (pad keys are masked, and no query that
    matters sits on a pad)."""
    key = weights.key_for(seed)
    items = tuple(sorted(cp.items()))
    tokens = jnp.asarray(tokens, jnp.int32)
    key_mask = tokens != 0
    x = _embed(key, tokens, cp_items=items)
    for layer in range(cp["num_layers"]):
        x = _layer(key, jnp.int32(layer), x, key_mask, cp_items=items,
                   quant=quant)
    return _head(key, x, cp_items=items, quant=quant)
