"""Weights from ``--seed`` for a configuration that is held in
bfloat16: the same draws as :mod:`lobench.weights` (leaf ``i`` is
``normal(fold_in(key(seed), i)) * 0.02``, or zeros / ones), each leaf
made on the device, cast to bfloat16 there (round to nearest even:
what the reference's ``reduce_precision`` gives) and fetched before the
next is made.  ``weights.make_flat`` builds every leaf in float32 in
one call, 19.9 GB at this configuration's seven layers; here the device
never holds more than one leaf (0.4 GB) and nothing ever holds a
float32 copy of the model."""

from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp

from lobench import weights

#: What the ``/function/python`` job runs (in the server's process,
#: where ``lobench`` is importable): the servable estimator artifact.
_JOB = (
    "from lobench import weights_bf16\n"
    "response = weights_bf16.estimator_artifact({config_path!r}, {seed})\n"
)


@functools.partial(jax.jit, static_argnames=("shape", "init"))
def _leaf(key, index, *, shape, init):
    return weights.leaf(key, index, shape, init).astype(jnp.bfloat16)


def make_flat(seed: int, leaves: list) -> dict:
    """``{name: bfloat16 host array}``; one program a distinct (shape,
    init), whatever the number of layers."""
    key = weights.key_for(seed)
    return {
        name: jax.device_get(
            _leaf(key, jnp.int32(i), shape=tuple(shape), init=init)
        )
        for i, (name, shape, init) in enumerate(leaves)
    }


def estimator_artifact(config_path: str, seed: int):
    """Runs inside the job: the configuration's estimator holding the
    seed's weights in bfloat16 on the host."""
    from learningorchestra_tpu.toolkit import registry
    from lobench import loader

    config, module = loader.config(Path(config_path))
    cp = config["class_parameters"]
    est = registry.resolve(config["module_path"], config["class"])(**cp)
    est.params = module.program_params(
        make_flat(seed, module.leaves(cp)), cp
    )
    return est


def submit(ctx, name: str, config_path: Path, seed: int) -> dict:
    from lobench import rest

    ctx.function.create(name, function=_JOB.format(
        config_path=str(config_path), seed=int(seed),
    ))
    return rest.finished(ctx, name)
