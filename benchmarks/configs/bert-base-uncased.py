"""Plain reference of the ``bert-base-uncased`` configuration, as the
program's ``models/text.py BertModel`` states it (every departure from
the published block is listed under ``assumed`` in the .json beside
this file): token + learned position embeddings, pre-LN blocks, final
LayerNorm, tanh pooler on position 0, linear classifier, softmax
cross-entropy, Adam.  float32 at ``highest`` precision; it imports
nothing of the program."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from lobench import plain, weights


def leaves(cp: dict) -> list:
    h, m = cp["hidden_dim"], cp["mlp_dim"]
    out = [
        ("tok_emb", (cp["vocab_size"], h), "normal"),
        ("pos_emb", (cp["max_len"], h), "normal"),
    ]
    for layer in range(cp["num_layers"]):
        out += [
            (f"l{layer}.{n}", s, i) for n, s, i in plain.block_leaves(h, m)
        ]
    out += [
        ("lnf_s", (h,), "ones"), ("lnf_b", (h,), "zeros"),
        ("pool_w", (h, h), "normal"), ("pool_b", (h,), "zeros"),
        ("cls_w", (h, cp["num_classes"]), "normal"),
        ("cls_b", (cp["num_classes"],), "zeros"),
    ]
    return out


def _layer(flat: dict, layer: int) -> dict:
    pre = f"l{layer}."
    return {k[len(pre):]: v for k, v in flat.items() if k.startswith(pre)}


def program_params(flat: dict, cp: dict) -> dict:
    """The flat leaves as the program's flax variables."""
    enc = {
        "Embed_0": {"embedding": flat["tok_emb"]},
        "Embed_1": {"embedding": flat["pos_emb"]},
        "LayerNorm_0": {"scale": flat["lnf_s"], "bias": flat["lnf_b"]},
    }
    for layer in range(cp["num_layers"]):
        enc[f"TransformerBlock_{layer}"] = plain.block_program_tree(
            _layer(flat, layer), cp["num_heads"]
        )
    return {"params": {
        "encoder": enc,
        "Dense_0": {"kernel": flat["pool_w"], "bias": flat["pool_b"]},
        "Dense_1": {"kernel": flat["cls_w"], "bias": flat["cls_b"]},
    }}


def from_program(variables: dict, cp: dict) -> dict:
    """The inverse of :func:`program_params` (any tree of that shape:
    the parameters, or Adam's moments)."""
    p = variables["params"]
    enc = p["encoder"]
    flat = {
        "tok_emb": enc["Embed_0"]["embedding"],
        "pos_emb": enc["Embed_1"]["embedding"],
        "lnf_s": enc["LayerNorm_0"]["scale"],
        "lnf_b": enc["LayerNorm_0"]["bias"],
        "pool_w": p["Dense_0"]["kernel"], "pool_b": p["Dense_0"]["bias"],
        "cls_w": p["Dense_1"]["kernel"], "cls_b": p["Dense_1"]["bias"],
    }
    for layer in range(cp["num_layers"]):
        block = plain.block_from_program(enc[f"TransformerBlock_{layer}"])
        flat.update({f"l{layer}.{k}": v for k, v in block.items()})
    return flat


def logits(flat: dict, tokens, cp: dict, quant=None):
    t = tokens.shape[1]
    x = flat["tok_emb"][tokens] + flat["pos_emb"][None, :t]
    key_mask = tokens != 0
    blk = jax.checkpoint(
        functools.partial(
            plain.block, num_heads=cp["num_heads"], causal=False,
            quant=quant,
        )
    )
    for layer in range(cp["num_layers"]):
        x = blk(x, _layer(flat, layer), key_mask=key_mask)
    x = plain.layer_norm(x, flat["lnf_s"], flat["lnf_b"])
    pooled = jnp.tanh(
        plain.dot(x[:, 0], flat["pool_w"], quant) + flat["pool_b"]
    )
    return plain.dot(pooled, flat["cls_w"], quant) + flat["cls_b"]


def loss(flat, tokens, labels, cp, quant=None):
    lg = logits(flat, tokens, cp, quant)
    picked = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - picked)


def feed_order(model_seed: int, epoch: int, rows: int):
    """Row order of one epoch as the configuration states it
    (``batch_order`` in the .json)."""
    return jax.random.permutation(
        jax.random.fold_in(jax.random.PRNGKey(model_seed), epoch), rows
    )


@functools.partial(
    jax.jit, static_argnames=("cp_items", "batch", "lr", "quant", "fault")
)
def _epoch(flat, tokens, labels, order, *, cp_items, batch, lr, quant,
           fault):
    cp = dict(cp_items)
    steps = tokens.shape[0] // batch
    xb = tokens[order].reshape(steps, batch, -1)
    yb = labels[order].reshape(steps, batch)
    if fault == "half_batch":  # the mean is taken over the half left
        xb, yb = xb[:, : batch // 2], yb[:, : batch // 2]
    b1, b2, eps = 0.9, 0.999, 1e-8
    zeros = jax.tree_util.tree_map(jnp.zeros_like, flat)

    def step(carry, feed):
        w, mu, nu, t = carry
        val, g = jax.value_and_grad(loss)(w, feed[0], feed[1], cp, quant)
        t = t + 1
        mu = jax.tree_util.tree_map(
            lambda m, gg: b1 * m + (1 - b1) * gg, mu, g)
        nu = jax.tree_util.tree_map(
            lambda v, gg: b2 * v + (1 - b2) * gg * gg, nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        w = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
            w, mu, nu,
        )
        return (w, mu, nu, t), val

    (w, _, nu, _), losses = jax.lax.scan(
        step, (flat, zeros, zeros, jnp.float32(0.0)), (xb, yb)
    )
    return w, nu, losses


def reference_epoch(seed: int, cp: dict, tokens, labels, *, batch: int,
                    lr: float, model_seed: int = 0, quant=None,
                    fault=None) -> dict:
    """One epoch (the program's compiled unit) from the seed's weights:
    the epoch's mean loss (and each step's), the parameters after it,
    Adam's second moment after it, and the weights it started from."""
    flat0 = weights.make_flat(seed, leaves(cp))
    order = feed_order(model_seed, 0, tokens.shape[0])
    w, nu, losses = _epoch(
        flat0, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(labels, jnp.int32), order,
        cp_items=tuple(sorted(cp.items())), batch=batch, lr=lr,
        quant=quant, fault=fault,
    )
    return {"loss": float(jnp.mean(losses)), "start": flat0, "end": w,
            "nu": nu, "step_losses": [float(v) for v in losses]}
