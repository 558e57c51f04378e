"""Operations and bytes, from shapes, of a sparse-expert decoder whose
step processes a block of positions a slot (``configs/
sdar-30b-a3b-chat``).  ``cfg`` is the configuration's
``class_parameters``: ``hidden_dim, num_layers, num_heads,
num_kv_heads, head_dim, expert_dim, num_experts, experts_per_token,
vocab_size``.  A matmul is ``2*m*n*k``; nothing recomputed counts."""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """q, k, v and output projections of one layer."""
    h, hd = cfg["hidden_dim"], cfg["head_dim"]
    heads, kvh = cfg["num_heads"], cfg["num_kv_heads"]
    return h * (heads + 2 * kvh) * hd + heads * hd * h


def expert_params(cfg: dict) -> int:
    """One expert: gate, up and down matrices."""
    return 3 * cfg["hidden_dim"] * cfg["expert_dim"]


def dense_layer_params(cfg: dict) -> int:
    """What of a layer every step reads whatever the routing:
    attention, the router, the two norms and the q/k norms."""
    return attention_params(cfg) \
        + cfg["hidden_dim"] * cfg["num_experts"] \
        + 2 * cfg["hidden_dim"] + 2 * cfg["head_dim"]


def forward_flops_per_position(cfg: dict, keys: float) -> float:
    """One processed position of a step that attends over ``keys``
    keys: the active parameters (attention, router, the experts per
    token), QK^T and PV, and the head (computed for every position)."""
    layer = 2.0 * (
        attention_params(cfg)
        + cfg["hidden_dim"] * cfg["num_experts"]
        + cfg["experts_per_token"] * expert_params(cfg)
    ) + 4.0 * keys * cfg["num_heads"] * cfg["head_dim"]
    return cfg["num_layers"] * layer \
        + 2.0 * cfg["hidden_dim"] * cfg["vocab_size"]


def step_bytes(cfg: dict, experts_hit: float, keys: float,
               bytes_per_value: int = 2) -> float:
    """HBM bytes one step must read: the dense weights of every layer,
    the experts its rows reached (``experts_hit``: distinct experts a
    layer, summed over layers, as the program counted them), the head,
    and K and V of every layer for ``keys`` cached positions (summed
    over the live slots).  Embedding rows are gathered and left out."""
    weights = cfg["num_layers"] * dense_layer_params(cfg) \
        + experts_hit * expert_params(cfg) \
        + cfg["hidden_dim"] * cfg["vocab_size"] + cfg["hidden_dim"]
    kv = 2.0 * cfg["num_layers"] * keys \
        * cfg["num_kv_heads"] * cfg["head_dim"]
    return bytes_per_value * (weights + kv)


def experts_flops(cfg: dict, positions: float) -> float:
    """The grouped matmuls of every layer for ``positions`` positions:
    each reaches ``experts_per_token`` experts."""
    return 2.0 * cfg["num_layers"] * positions \
        * cfg["experts_per_token"] * expert_params(cfg)


def experts_bytes(cfg: dict, experts_hit: float, positions: float,
                  bytes_per_value: int = 2) -> float:
    """What the grouped matmuls must move: the experts reached, and
    each row in and out of the three matmuls."""
    rows = positions * cfg["experts_per_token"] * cfg["num_layers"]
    acts = rows * (3 * cfg["hidden_dim"] + 3 * cfg["expert_dim"])
    return bytes_per_value * (experts_hit * expert_params(cfg) + acts)
