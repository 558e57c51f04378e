"""Compiles for a described (not attached) TPU v5e chip, at the real
widths of the routed expert layer: what the chip's compiler would refuse
(a tile that does not divide, too much fast memory) fails here, at no
chip time.  Nothing runs; no result or time is read.  All such compiles
live in this one file: the process that describes the topology holds
the TPU library until it exits."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("slots", [1, 2, 4, 8])
def test_grouped_matmul_compiles_at_the_routed_layers_shapes(
        slots, one_chip, monkeypatch):
    """Rows of a block step (slots x 4 positions x 8 experts a token)
    over 128 experts of 2048 x 768 and back: on the TPU the megablox
    kernel, its tiles dividing every slot bucket's rows."""
    from learningorchestra_tpu.ops import moe

    # the compile is for the chip: steer the layer's choice of path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows = slots * 4 * 8

    def experts(x, w_up, w_down, sizes):
        return moe.grouped_matmul(
            moe.grouped_matmul(x, w_up, sizes), w_down, sizes
        )

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(experts).lower(
        arg((rows, 2048)), arg((128, 2048, 768)), arg((128, 768, 2048)),
        arg((128,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2 and "ragged-dot" not in text
