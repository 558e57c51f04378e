"""Operations and bytes, from shapes, of a decoder whose every block runs
a Mamba-2 mixer beside grouped-query attention, then a gated FFN
(``configs/falcon-h1-34b-instruct``), stepping one token a slot: the
mixer through its recurrent form (a float32 state of ``mamba_heads x
mamba_state x mamba_head_dim`` and a float32 convolution tail of
``mamba_conv - 1`` inputs a slot and layer), attention over K/V pages.
``cfg`` is the configuration's ``class_parameters``.  A matmul is
``2*m*n*k``; nothing recomputed counts."""

from __future__ import annotations

F32 = 4  # the state, the tail and the mixer's step inputs, as stated


def _mixer_widths(cfg: dict) -> tuple:
    """(inner, the B/C width, the convolution's channels, the
    in-projection's width)."""
    heads, groups = cfg["mamba_heads"], cfg["mamba_groups"]
    inner = heads * cfg["mamba_head_dim"]
    bc = groups * cfg["mamba_state"]
    return inner, bc, inner + 2 * bc, 2 * inner + 2 * bc + heads


def state_values(cfg: dict) -> int:
    """One slot's SSM state in one layer: 4,194,304 values at 32 heads
    of 256 x 128."""
    return cfg["mamba_heads"] * cfg["mamba_state"] * cfg["mamba_head_dim"]


def tail_values(cfg: dict) -> int:
    """One slot's convolution tail in one layer: 3 x 5,120."""
    return (cfg["mamba_conv"] - 1) * _mixer_widths(cfg)[2]


def layer_params(cfg: dict) -> int:
    """One block: the mixer (in- and out-projection, convolution with
    its bias, ``A_log``, ``dt_bias``, ``D``, the gated norm), attention
    (q, k, v, o; no bias), the gated FFN and the two block norms."""
    h, m = cfg["hidden_dim"], cfg["mlp_dim"]
    heads, kvh, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    inner, _bc, channels, proj = _mixer_widths(cfg)
    mixer = h * proj + inner * h + cfg["mamba_conv"] * channels \
        + channels + 3 * cfg["mamba_heads"] + inner
    attention = 2 * h * heads * hd + 2 * h * kvh * hd
    return mixer + attention + 3 * h * m + 2 * h


def fixed_params(cfg: dict) -> int:
    """What every step reads: the layers, the final norm and the head.
    The embedding is gathered by row and left out."""
    h = cfg["hidden_dim"]
    return cfg["num_layers"] * layer_params(cfg) + h + h * cfg["vocab_size"]


def ssm_flops(cfg: dict, slot_steps: float) -> float:
    """The kernel ``ssm_step`` of every layer for ``slot_steps`` live
    slots: each state value decayed and added to (2) and read out
    against ``C`` (2)."""
    return cfg["num_layers"] * slot_steps * 4.0 * state_values(cfg)


def ssm_bytes(cfg: dict, slot_steps: float) -> float:
    """What the kernel ``ssm_step`` of every layer must move for
    ``slot_steps`` live slots: each state read ONCE and written ONCE,
    the step's ``x``, ``B``, ``C`` and step sizes in and the read-out
    out, all float32.  The convolution's tail is not the kernel's."""
    inner, bc, _c, _p = _mixer_widths(cfg)
    io = (inner + 2 * bc + cfg["mamba_heads"] + inner) * F32
    return cfg["num_layers"] * slot_steps * (
        2.0 * state_values(cfg) * F32 + io
    )


def kv_bytes(cfg: dict, keys: float, bytes_per_value: int = 2) -> float:
    """K and V of every layer for ``keys`` cached positions (summed over
    the live slots), each read once."""
    return 2.0 * cfg["num_layers"] * keys * cfg["num_kv_heads"] \
        * cfg["head_dim"] * bytes_per_value


def forward_flops_per_token(cfg: dict, keys: float) -> float:
    """One processed token (prompt or output: the state's update is the
    same) that attends over ``keys`` cached keys: the mixer's
    projections, convolution and recurrence, attention's projections
    and its two products, the FFN, and the head (computed every
    slot-step)."""
    h, m = cfg["hidden_dim"], cfg["mlp_dim"]
    heads, kvh, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    inner, _bc, channels, proj = _mixer_widths(cfg)
    matmuls = 2.0 * (h * proj + inner * h + 2 * h * heads * hd
                     + 2 * h * kvh * hd + 3 * h * m)
    layer = matmuls + 2.0 * cfg["mamba_conv"] * channels \
        + 4.0 * keys * heads * hd
    return cfg["num_layers"] * layer + ssm_flops(cfg, 1.0) \
        + 2.0 * h * cfg["vocab_size"]


def step_bytes(cfg: dict, slot_steps: float, keys: float,
               bytes_per_value: int = 2) -> float:
    """HBM bytes one step must move: the layers and the head once, the
    live slots' states and tails in and out, and the K/V they attend
    over (``keys``, summed over the live slots)."""
    tails = cfg["num_layers"] * slot_steps * 2.0 * tail_values(cfg) * F32
    return bytes_per_value * fixed_params(cfg) + ssm_bytes(cfg, slot_steps) \
        + tails + kv_bytes(cfg, keys, bytes_per_value)
