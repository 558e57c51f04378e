"""Operations and bytes, from shapes, of a latent-attention decoder
with a leading dense layer and routed layers of which this chip holds a
share (``configs/kimi-k2.6``), stepping one token a slot through the
absorbed decode form.  ``cfg`` is the configuration's
``class_parameters``.  A matmul is ``2*m*n*k``; nothing recomputed
counts."""

from __future__ import annotations


def routed_layers(cfg: dict) -> int:
    return cfg["num_layers"] - cfg["first_dense_layers"]


def held_experts(cfg: dict) -> int:
    return cfg["experts_held"][1]


def latent_width(cfg: dict) -> int:
    """Values a cached position holds a layer: the latent and the
    rotary key all heads share."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attention_params(cfg: dict) -> int:
    """The five projections of one latent attention layer."""
    h, heads = cfg["hidden_dim"], cfg["num_heads"]
    ql, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return h * ql + ql * heads * (nope + rope) + h * (rank + rope) \
        + rank * heads * (nope + vd) + heads * vd * h


def expert_params(cfg: dict) -> int:
    """One routed (or shared) expert: gate, up and down matrices."""
    return 3 * cfg["hidden_dim"] * cfg["expert_dim"]


def fixed_params(cfg: dict) -> int:
    """What every step reads whatever the routing: of each layer the
    attention with its two latent norms and the two block norms; the
    dense layers' FFN; each routed layer's router, bias and shared
    experts; the final norm and the head.  The embedding is gathered
    by row and left out."""
    h = cfg["hidden_dim"]
    per_layer = attention_params(cfg) + cfg["q_lora_rank"] \
        + cfg["kv_lora_rank"] + 2 * h
    routed = h * cfg["num_experts"] + cfg["num_experts"] \
        + cfg["shared_experts"] * expert_params(cfg)
    return cfg["num_layers"] * per_layer \
        + cfg["first_dense_layers"] * 3 * h * cfg["mlp_dim"] \
        + routed_layers(cfg) * routed + h + h * cfg["vocab_size"]


def attend_flops_per_key(cfg: dict) -> float:
    """One layer's absorbed attention, one query token against one
    cached key: every head scores the whole row and mixes its latent."""
    return 2.0 * cfg["num_heads"] * (
        latent_width(cfg) + cfg["kv_lora_rank"]
    )


def forward_flops_per_token(cfg: dict, keys: float,
                            expert_rows: float) -> float:
    """One processed token (prompt or output) that attends over
    ``keys`` cached keys and whose choices reached held experts
    ``expert_rows`` times, summed over the routed layers: the
    projections, the absorption of ``kv_b`` into query and output, the
    attention, the FFNs and the head (computed every slot-step)."""
    h = cfg["hidden_dim"]
    # kv_b's two halves are absorbed into the query and the output:
    # the same 2 * rank * heads * (nope + v) a token as multiplying by it
    layer = 2.0 * attention_params(cfg) + keys * attend_flops_per_key(cfg)
    routed = 2.0 * (
        h * cfg["num_experts"]
        + cfg["shared_experts"] * expert_params(cfg)
    )
    return cfg["num_layers"] * layer \
        + cfg["first_dense_layers"] * 2.0 * 3 * h * cfg["mlp_dim"] \
        + routed_layers(cfg) * routed \
        + expert_rows * 2.0 * expert_params(cfg) \
        + 2.0 * h * cfg["vocab_size"]


def step_bytes(cfg: dict, experts_hit: float, keys: float,
               bytes_per_value: int = 2) -> float:
    """HBM bytes one step must read: the fixed weights, the held
    experts its rows reached (``experts_hit``: distinct held experts a
    layer, summed over layers, as the program counted them), and the
    latent row of every layer for ``keys`` cached positions (summed
    over the live slots), each read ONCE."""
    return bytes_per_value * (
        fixed_params(cfg) + experts_hit * expert_params(cfg)
        + cfg["num_layers"] * keys * latent_width(cfg)
    )


def attend_flops(cfg: dict, keys: float) -> float:
    """The latent attend of every layer for ``keys`` attended keys."""
    return cfg["num_layers"] * keys * attend_flops_per_key(cfg)


def attend_bytes(cfg: dict, keys: float, queries: float,
                 bytes_per_value: int = 2) -> float:
    """What the latent attend of every layer must move: each attended
    latent row once (it is key and value at once), and for each of
    ``queries`` query tokens the heads' queries in (the pages' dtype)
    and their latent mixes out (float32)."""
    q_io = cfg["num_heads"] * (
        latent_width(cfg) * bytes_per_value + cfg["kv_lora_rank"] * 4
    )
    return cfg["num_layers"] * (
        keys * latent_width(cfg) * bytes_per_value + queries * q_io
    )


def experts_flops(cfg: dict, rows: float) -> float:
    """The grouped matmuls over ``rows`` (token, choice) pairs that
    reached a held expert, all layers summed."""
    return 2.0 * rows * expert_params(cfg)


def experts_bytes(cfg: dict, experts_hit: float, rows: float,
                  bytes_per_value: int = 2) -> float:
    """What the grouped matmuls must move: the held experts reached,
    and each of their rows in and out of the three matmuls."""
    acts = rows * (3 * cfg["hidden_dim"] + 3 * cfg["expert_dim"])
    return bytes_per_value * (experts_hit * expert_params(cfg) + acts)
