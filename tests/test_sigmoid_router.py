"""The sigmoid, bias-corrected router of ``ops/moe.py`` ``RoutedExperts``
and what stands beside the routed experts in a DeepSeek-V3-shaped block
(a shared expert; a leading dense layer), at tiny widths on the CPU
against ``tests/latent_oracle.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learningorchestra_tpu.ops.moe import RoutedExperts, route_top_k
from tests import latent_oracle as oracle
from tests.test_kimi_decode import TINY, _estimator

KW = dict(num_experts=16, expert_dim=32, top_k=4, scoring="sigmoid",
          score_bias=True, routed_scale=2.5)


def _layer_params(seed=0):
    params = RoutedExperts(**KW).init(
        jax.random.PRNGKey(seed), jnp.ones((1, 64)))["params"]
    params = jax.tree_util.tree_map(lambda a: a * 2.0, params)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 1), (16,))
    return {**params, "score_bias": bias}


def test_the_bias_chooses_and_never_weighs():
    """Expert 2 scores lowest and its bias lifts it into the chosen:
    it is chosen, and its gate is its own small score over the chosen
    scores' sum, the bias nowhere in it."""
    logits = jnp.array([[2.0, 1.5, -3.0, 1.0, 0.5, 0.0]])
    bias = jnp.array([0.0, 0.0, 5.0, 0.0, 0.0, 0.0])
    gates, ids = route_top_k(logits, 3, "sigmoid", bias, 2.827)
    assert sorted(np.asarray(ids)[0].tolist()) == [0, 1, 2]
    plain, plain_ids = route_top_k(logits, 3, "sigmoid", None, 2.827)
    assert sorted(np.asarray(plain_ids)[0].tolist()) == [0, 1, 3]
    s = jax.nn.sigmoid(logits[0])
    want = 2.827 * s[jnp.array([0, 1, 2])] / (s[0] + s[1] + s[2])
    order = np.argsort(np.asarray(ids)[0])
    np.testing.assert_allclose(np.asarray(gates)[0][order], want,
                               rtol=1e-6)


@pytest.mark.parametrize("scale", [1.0, 2.827])
def test_gates_sum_to_the_scaling_factor(scale):
    logits = jax.random.normal(jax.random.PRNGKey(0), (50, 16)) * 2.0
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    gates, ids = route_top_k(logits, 4, "sigmoid", bias, scale)
    np.testing.assert_allclose(gates.sum(-1), scale, rtol=1e-5)
    # chosen by score + bias: the four largest of the corrected scores
    want = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, 4)[1]
    assert np.array_equal(np.asarray(ids), np.asarray(want))


def test_softmax_routing_is_what_it_was():
    logits = jax.random.normal(jax.random.PRNGKey(2), (40, 8))
    gates, ids = route_top_k(logits, 2)
    top, want_ids = jax.lax.top_k(jax.nn.softmax(logits, -1), 2)
    assert np.array_equal(np.asarray(ids), np.asarray(want_ids))
    assert np.array_equal(
        np.asarray(gates), np.asarray(top / top.sum(-1, keepdims=True)))
    with pytest.raises(ValueError, match="scoring"):
        route_top_k(logits, 2, "tanh")


def test_sigmoid_layer_matches_the_reference_and_counts_its_rows():
    lp = _layer_params()
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 9, 64))
    got, stats = RoutedExperts(**KW).apply(
        {"params": lp}, x, mutable=["moe_stats"])
    want = oracle.routed(x.reshape(-1, 64), lp, 4, 2.5).reshape(x.shape)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)
    stats = stats["moe_stats"]
    assert int(stats["rows"]) == 18 * 4  # every choice lands on a held one
    # the bias changes the choice of some rows here (else it tests nothing)
    _, with_bias = oracle.route(x.reshape(-1, 64), lp, 4, 2.5)
    _, without = oracle.route(
        x.reshape(-1, 64), {**lp, "score_bias": jnp.zeros(16)}, 4, 2.5)
    assert (np.sort(with_bias, -1) != np.sort(without, -1)).any()


def test_four_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """Four chips of 4 of the 16 experts each: the parts their routed
    experts give, with the shared expert (which every chip computes
    alike) counted once, sum to the uncut layer's reference."""
    est = _estimator()
    block = est.params["params"]["LatentExpertBlock_1"]
    lp, shared = block["RoutedExperts_0"], block["shared_expert"]
    x = jax.random.normal(jax.random.PRNGKey(8), (3, 7, 64))
    flat = x.reshape(-1, 64)
    whole = oracle.routed(flat, lp, 4, TINY["routed_scale"]) \
        + oracle.swiglu(flat, shared)
    parts = oracle.swiglu(flat, shared)  # once
    rows = 0
    for first in (0, 4, 8, 12):
        share = {"router": lp["router"], "score_bias": lp["score_bias"],
                 **{k: lp[k][first: first + 4]
                    for k in ("w_gate", "w_up", "w_down")}}
        got, stats = RoutedExperts(
            **{**KW, "routed_scale": TINY["routed_scale"]},
            held=(first, 4),
        ).apply({"params": share}, x, mutable=["moe_stats"])
        parts = parts + got.reshape(-1, 64)
        rows += int(stats["moe_stats"]["rows"])
        assert int(stats["moe_stats"]["experts_hit"]) <= 4
    np.testing.assert_allclose(parts, whole, atol=2e-5, rtol=1e-4)
    assert rows == 21 * 4  # each (token, choice) pair reached ONE share


@pytest.mark.parametrize("kw,names", [
    (dict(held=(12, 8)), ("held=(12, 8)", "num_experts=16")),
    (dict(held=(0, 0)), ("held=(0, 0)", "num_experts=16")),
    (dict(top_k=17), ("top_k=17", "num_experts=16")),
])
def test_a_share_or_a_top_k_beyond_the_router_is_refused(kw, names):
    layer = RoutedExperts(**{**KW, **kw})
    with pytest.raises(ValueError) as err:
        layer.init(jax.random.PRNGKey(0), jnp.ones((1, 64)))
    assert all(name in str(err.value) for name in names)


def test_the_leading_layer_is_dense_and_the_rest_routed():
    est = _estimator()
    params = est.params["params"]
    first = params["LatentExpertBlock_0"]
    assert "GatedMlp_0" in first and "RoutedExperts_0" not in first \
        and "shared_expert" not in first
    assert first["GatedMlp_0"]["gate"]["kernel"].shape == (64, 96)
    for i in (1, 2):
        block = params[f"LatentExpertBlock_{i}"]
        assert block["RoutedExperts_0"]["router"].shape == (64, 16)
        assert block["RoutedExperts_0"]["score_bias"].shape == (16,)
        assert block["shared_expert"]["up"]["kernel"].shape == (64, 32)


def test_gated_mlp_is_swiglu():
    from learningorchestra_tpu.ops.layers import GatedMlp

    layer = GatedMlp(24)
    params = layer.init(jax.random.PRNGKey(0), jnp.ones((1, 16)))
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 16))
    np.testing.assert_allclose(
        layer.apply(params, x), oracle.swiglu(x, params["params"]),
        atol=1e-6, rtol=1e-5)
