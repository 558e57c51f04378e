"""Operations and bytes, from shapes, of a dense decoder whose mixer is
power retention of degree 2 (``configs/brumby-14b-base``), stepping one
token a slot through the recurrent form.  ``cfg`` is the
configuration's ``class_parameters``.  A matmul is ``2*m*n*k``; nothing
recomputed counts.  The state is counted at the LEAST the operator
needs, the ``d (d + 1) / 2`` products of a ``d``-wide key, whatever
rows the program's layout pads them to."""

from __future__ import annotations

STATE_BYTES = 4  # float32, as the configuration states


def state_rows(cfg: dict) -> int:
    """Products of the symmetric square of one key: 8,256 at 128."""
    d = cfg["head_dim"]
    return d * (d + 1) // 2


def state_values_per_layer(cfg: dict) -> int:
    """Values of one slot's state in one layer: a key/value head keeps
    a row of ``head_dim`` values and one normaliser a product."""
    return cfg["num_kv_heads"] * state_rows(cfg) * (cfg["head_dim"] + 1)


def state_bytes_per_slot(cfg: dict, rows: int | None = None) -> int:
    """A slot's state over all layers (at ``rows`` rows a head where a
    layout pads the products)."""
    per_head = (rows or state_rows(cfg)) * (cfg["head_dim"] + 1)
    return cfg["num_layers"] * cfg["num_kv_heads"] * per_head * STATE_BYTES


def layer_params(cfg: dict) -> int:
    """One block: the four projections, the gate with its bias, the two
    head norms, the two block norms, the gated FFN."""
    h, m = cfg["hidden_dim"], cfg["mlp_dim"]
    heads, kvh, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    return 2 * h * heads * hd + 2 * h * kvh * hd + h * kvh + kvh \
        + 2 * hd + 2 * h + 3 * h * m


def fixed_params(cfg: dict) -> int:
    """What every step reads: the layers, the final norm and the head.
    The embedding is gathered by row and left out."""
    h = cfg["hidden_dim"]
    return cfg["num_layers"] * layer_params(cfg) + h + h * cfg["vocab_size"]


def retention_flops(cfg: dict, slot_steps: float) -> float:
    """The recurrence of every layer for ``slot_steps`` live slots: the
    update (decay and outer product, 2 a value) and the read-out of
    each query head against its key/value head's state."""
    values = state_rows(cfg) * (cfg["head_dim"] + 1)
    return cfg["num_layers"] * slot_steps * 2.0 * values * (
        cfg["num_kv_heads"] + cfg["num_heads"]
    )


def retention_bytes(cfg: dict, slot_steps: float,
                    bytes_per_value: int = 2) -> float:
    """What the recurrence of every layer must move for ``slot_steps``
    live slots: each state read ONCE and written ONCE, and the step's
    queries, keys, values (the activations' dtype) and gates in and the
    read-outs (float32) out."""
    heads, kvh, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    io = (heads + 2 * kvh) * hd * bytes_per_value + kvh * 4 \
        + heads * hd * 4
    return cfg["num_layers"] * slot_steps * (
        2.0 * state_values_per_layer(cfg) * STATE_BYTES + io
    )


def forward_flops_per_token(cfg: dict) -> float:
    """One processed token (prompt or output: the state update is the
    same): the projections and the gate, the recurrence, the FFN, and
    the head (computed every slot-step)."""
    h, m = cfg["hidden_dim"], cfg["mlp_dim"]
    heads, kvh, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    matmuls = 2.0 * (2 * h * heads * hd + 2 * h * kvh * hd + h * kvh
                     + 3 * h * m)
    return cfg["num_layers"] * matmuls + retention_flops(cfg, 1.0) \
        + 2.0 * h * cfg["vocab_size"]


def step_bytes(cfg: dict, slot_steps: float,
               bytes_per_value: int = 2) -> float:
    """HBM bytes one step must move: the layers and the head once, and
    the live slots' states in and out."""
    return bytes_per_value * fixed_params(cfg) \
        + retention_bytes(cfg, slot_steps, bytes_per_value)
