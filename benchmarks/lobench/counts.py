"""Operations and bytes from shapes.  Every share of a peak or of a
roofline the benchmark reports divides one of these by a measured time.
Recomputed operations never count: a matmul is ``2*m*n*k``, training is
three forwards (forward, and the two matmuls of each backward).

``cfg`` is a configuration file's ``class_parameters`` with the keys
``hidden_dim, num_layers, num_heads, mlp_dim, vocab_size``."""

from __future__ import annotations


def block_matmul_params(cfg: dict) -> int:
    """Weights of one transformer block that a token is multiplied by:
    qkv (3H^2), out (H^2), the two MLP matrices (2HM)."""
    h, m = cfg["hidden_dim"], cfg["mlp_dim"]
    return 4 * h * h + 2 * h * m


def attention_flops_per_token(cfg: dict, keys: float) -> float:
    """QK^T and PV of one layer for one query against ``keys`` keys."""
    return 4.0 * keys * cfg["hidden_dim"]


def encoder_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward of the encoder per token, full attention over
    ``seq`` keys; the [CLS] head (two small matmuls per sequence) is
    spread over the sequence's tokens."""
    fwd_layer = 2.0 * block_matmul_params(cfg) \
        + attention_flops_per_token(cfg, seq)
    h = cfg["hidden_dim"]
    head = 2.0 * (h * h + h * cfg.get("num_classes", 2)) / seq
    return 3.0 * (cfg["num_layers"] * fwd_layer + head)


def decoder_forward_flops_per_token(cfg: dict, keys: float) -> float:
    """One processed token (prompt or output) of a decoder step that
    attends over ``keys`` cached keys, LM head included (the engine
    computes it for every slot-step)."""
    fwd_layer = 2.0 * block_matmul_params(cfg) \
        + attention_flops_per_token(cfg, keys)
    return cfg["num_layers"] * fwd_layer \
        + 2.0 * cfg["hidden_dim"] * cfg["vocab_size"]


def decoder_weight_bytes(cfg: dict, bytes_per_param: int = 4) -> int:
    """Bytes of every weight a decode step reads once: the blocks'
    matrices, biases and norms, and the LM head.  The embedding tables
    are gathered by row and are left out."""
    h, m, v = cfg["hidden_dim"], cfg["mlp_dim"], cfg["vocab_size"]
    block = block_matmul_params(cfg) + (3 * h + h + m + h) + 4 * h
    head = h * v + v + 2 * h
    return bytes_per_param * (cfg["num_layers"] * block + head)


def decoder_kv_bytes(cfg: dict, live_keys: float,
                     bytes_per_value: int = 4) -> float:
    """K and V of every layer for ``live_keys`` cached positions (summed
    over the live slots)."""
    return 2.0 * cfg["num_layers"] * live_keys * cfg["hidden_dim"] \
        * bytes_per_value


def flash_train_flops(cfg: dict, batch: int, seq: int) -> float:
    """Attention of ONE layer for one training step: forward QK^T and
    PV, backward dP, dV, dQ, dK: six (T x T x hd) matmuls a head.  The
    kernel's recomputation of the scores in dq and dkv is not counted."""
    hd = cfg["hidden_dim"] // cfg["num_heads"]
    return 6.0 * 2.0 * seq * seq * hd * batch * cfg["num_heads"]


def flash_train_bytes(cfg: dict, batch: int, seq: int,
                      bytes_per_value: int = 2) -> float:
    """HBM traffic of one layer's three kernels at the least: forward
    reads q, k, v and writes o; backward reads q, k, v, o, do and writes
    dq, dk, dv (12 tensors of B x T x H; the f32 row statistics are
    small beside them and left out)."""
    return 12.0 * batch * seq * cfg["hidden_dim"] * bytes_per_value


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """(least seconds, which bound) for a call of ``flops`` and
    ``nbytes`` on a chip with ``peaks``."""
    t_c = flops / peaks["flops_bf16"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
