"""Plain reference of ``models/retention.py`` ``RetentionLM`` for the
tests: ``jax.numpy`` in float32 at ``highest`` precision, the ATTENTION
form of power retention only (the masked ``(Q K^T)^2`` with the
cumulated log-gates as a difference of prefix sums): no state, no
feature map, no cache.  It reads the estimator's parameter tree and
nothing else of the program."""

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6  # the read-out's


def _f32(a):
    return jnp.asarray(np.asarray(a, np.float32))


def dot(x, w):
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps
    ) * scale


def rope(x, theta):
    """Rotate-half on (T, heads, hd) at positions 0..T-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
         x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1,
    )


def retention(est, w, x, key_mask):
    """(T, H) -> (T, H): ``A_ij = (q_i . k_j / sqrt(d))^2 exp(L_i -
    L_j)`` for ``j <= i`` a key that may be seen, ``L`` the prefix sums
    of ``log g``; ``y = A v / (sum A + eps)``."""
    heads, kvh, hd = est.num_heads, est.num_kv_heads, est.head_dim
    t = x.shape[0]

    def proj(name, n):
        return dot(x, _f32(w[name]["kernel"]).reshape(x.shape[-1], -1)) \
            .reshape(t, n, hd)

    q = rope(rms_norm(proj("query", heads), _f32(w["q_norm"]["scale"]),
                      est.norm_eps), est.rope_theta)
    k = rope(rms_norm(proj("key", kvh), _f32(w["k_norm"]["scale"]),
                      est.norm_eps), est.rope_theta)
    v = proj("value", kvh)
    log_g = jax.nn.log_sigmoid(
        dot(x, _f32(w["gate"]["kernel"])) + _f32(w["gate"]["bias"])
    )  # (T, H_kv)
    decay = jnp.cumsum(log_g, axis=0).T  # (H_kv, T)
    keep = jnp.tril(jnp.ones((t, t), bool)) & key_mask[None, :]
    k, v = (jnp.repeat(a, heads // kvh, axis=1) for a in (k, v))
    decay = jnp.repeat(decay, heads // kvh, axis=0)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(hd)
    a = jnp.square(s) * jnp.exp(jnp.where(
        keep[None], decay[:, :, None] - decay[:, None, :], -jnp.inf))
    y = jnp.einsum("hqk,khd->qhd", a, v, precision=HI) \
        / (jnp.sum(a, -1).T[..., None] + EPS)
    return dot(y.reshape(t, heads * hd),
               _f32(w["out"]["kernel"]).reshape(heads * hd, -1))


def swiglu(x, w):
    return dot(jax.nn.silu(dot(x, _f32(w["gate"]["kernel"])))
               * dot(x, _f32(w["up"]["kernel"])),
               _f32(w["down"]["kernel"]))


def forward(est, tokens):
    """(B, T) int tokens -> (B, T, V) float32 logits."""
    params = est.params["params"]
    rows = []
    for row in np.asarray(tokens):
        x = _f32(params["Embed_0"]["embedding"])[row]
        key_mask = jnp.asarray(row != 0)
        for i in range(est.num_layers):
            w = params[f"RetentionBlock_{i}"]
            x = x + retention(
                est, w["PowerRetention_0"],
                rms_norm(x, _f32(w["mixer_norm"]["scale"]), est.norm_eps),
                key_mask,
            )
            x = x + swiglu(
                rms_norm(x, _f32(w["ffn_norm"]["scale"]), est.norm_eps),
                w["GatedMlp_0"],
            )
        x = rms_norm(x, _f32(params["final_norm"]["scale"]), est.norm_eps)
        rows.append(dot(x, _f32(params["head"]["kernel"])))
    return jnp.stack(rows)


def generate(est, prompt, max_new: int):
    """Greedy continuation by full forwards: the tokens a state-free
    decode gives."""
    row = list(prompt)
    for _ in range(max_new):
        logits = forward(est, np.array([row], np.int32))[0, -1]
        row.append(int(jnp.argmax(logits)))
    return row


def served_gaps(est, prompt, served):
    """How far under the best logit each served token's lies, by ONE
    full forward over prompt + served."""
    row = np.array([list(prompt) + list(served)], np.int32)
    logits = np.asarray(forward(est, row)[0])
    at = np.arange(len(prompt) - 1, row.shape[1] - 1)
    return logits[at].max(-1) - logits[at, row[0, at + 1]]
