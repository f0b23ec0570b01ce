"""The decoder's sparse MLP (``ops/routed_experts.py``): sigmoid routing with
a selection bias, and routed experts that drop no token, against a plain
NumPy loop over the experts.

Tolerance ``TOL`` 2e-5 on outputs of size about 0.3-1: both sides are float32
and differ in the order of their sums only (rows gathered by expert against
a masked loop); sound readings here are under 2e-6.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.ops import routed_experts as ops

T, H, I, E, K = 24, 32, 20, 8, 2
TOL = 2e-5


def kernels(seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(E, H, I)).astype(np.float32) * H ** -0.5,
            rng.normal(size=(E, H, I)).astype(np.float32) * H ** -0.5,
            rng.normal(size=(E, I, H)).astype(np.float32) * I ** -0.5)


def plain(x, chosen, weights, gate, up, down, live=None):
    """Every expert in turn over the tokens that chose it."""
    y = np.zeros_like(x)
    counts = np.zeros((E,), np.int64)
    for t in range(x.shape[0]):
        if live is not None and not live[t]:
            continue
        for e, w in zip(chosen[t], weights[t]):
            g = x[t] @ gate[e]
            h = g / (1.0 + np.exp(-g)) * (x[t] @ up[e])
            y[t] += w * (h @ down[e])
            counts[e] += 1
    return y, counts


def routing(name):
    rng = np.random.default_rng(5)
    if name == "all_to_one_pair":        # experts 3 and 5 get everything
        return np.tile(np.array([[3, 5]]), (T, 1))
    if name == "one_expert_starved":     # expert 0 gets nothing
        return np.stack([rng.choice(np.arange(1, E), K, replace=False)
                         for _ in range(T)])
    return np.stack([rng.choice(E, K, replace=False) for _ in range(T)])


@pytest.mark.parametrize("name", ["even", "all_to_one_pair",
                                  "one_expert_starved"])
def test_no_token_is_dropped_whatever_the_imbalance(name):
    rng = np.random.default_rng(1)
    x = rng.normal(size=(T, H)).astype(np.float32)
    chosen = routing(name).astype(np.int32)
    weights = rng.uniform(0.2, 1.0, size=(T, K)).astype(np.float32)
    gate, up, down = kernels()
    want, want_counts = plain(x, chosen, weights, gate, up, down)
    got, counts = jax.jit(ops.routed_experts)(x, chosen, weights, gate, up,
                                              down)
    assert np.abs(want).max() > 0.1
    assert np.abs(np.asarray(got) - want).max() < TOL
    assert np.asarray(counts).tolist() == want_counts.tolist()
    assert int(counts.sum()) == T * K               # every pair computed
    if name == "all_to_one_pair":
        assert np.asarray(counts).tolist() == [0, 0, 0, T, 0, T, 0, 0]
    if name == "one_expert_starved":
        assert int(counts[0]) == 0


def test_a_row_that_is_not_live_reads_no_expert_and_comes_back_zero():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(T, H)).astype(np.float32)
    chosen = routing("even").astype(np.int32)
    weights = np.full((T, K), 0.5, np.float32)
    live = rng.uniform(size=T) < 0.5
    gate, up, down = kernels()
    want, want_counts = plain(x, chosen, weights, gate, up, down, live)
    got, counts = ops.routed_experts(x, chosen, weights, gate, up, down,
                                     jnp.asarray(live))
    assert np.abs(np.asarray(got) - want).max() < TOL
    assert np.all(np.asarray(got)[~live] == 0.0)
    assert np.asarray(counts).tolist() == want_counts.tolist()
    assert int(counts.sum()) == int(live.sum()) * K


@pytest.mark.parametrize("rows", [T, 300], ids=["one_tile", "two_tiles"])
def test_the_pallas_grouped_product_is_the_ragged_dot(rows):
    """``megablox.gmm`` in interpret mode (what the chip compiles) against
    ``jax.lax.ragged_dot`` (what the CPU runs), through the whole layer:
    16 padded rows a tile of their own, 600 pairs two tiles of 512 with
    dead rows behind the last group."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(rows, H)).astype(np.float32)
    chosen = np.stack([rng.choice(E, K, replace=False)
                       for _ in range(rows)]).astype(np.int32)
    weights = rng.uniform(0.2, 1.0, size=(rows, K)).astype(np.float32)
    live = jnp.asarray(rng.uniform(size=rows) < 0.8)
    gate, up, down = kernels(4)
    a, ca = ops.routed_experts(x, chosen, weights, gate, up, down, live,
                               kernel=False)
    b, cb = ops.routed_experts(x, chosen, weights, gate, up, down, live,
                               kernel=True, interpret=True)
    assert np.asarray(ca).tolist() == np.asarray(cb).tolist()
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < TOL
    assert not np.isnan(np.asarray(b)).any()


def test_route_is_sigmoid_top_k_renormalised_and_scaled():
    rng = np.random.default_rng(6)
    logits = rng.normal(size=(T, E)).astype(np.float32)
    chosen, weights = ops.route(jnp.asarray(logits), jnp.zeros((E,)), K, 1.8)
    s = 1.0 / (1.0 + np.exp(-logits))
    want = np.argsort(-s, axis=1)[:, :K]
    assert np.asarray(chosen).tolist() == want.tolist()
    picked = np.take_along_axis(s, want, 1)
    assert np.allclose(np.asarray(weights),
                       picked / picked.sum(1, keepdims=True) * 1.8,
                       atol=1e-6)
    assert np.allclose(np.asarray(weights).sum(1), 1.8, atol=1e-5)


def test_the_selection_bias_changes_the_choice_and_not_the_weights():
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(T, E)).astype(np.float32))
    bias = jnp.zeros((E,)).at[6].set(10.0)        # expert 6 always chosen
    plain_c, plain_w = ops.route(logits, jnp.zeros((E,)), K)
    chosen, weights = ops.route(logits, bias, K)
    assert np.all(np.asarray(chosen)[:, 0] == 6)
    assert not np.array_equal(np.asarray(chosen), np.asarray(plain_c))
    # the weights are the SCORES at the chosen, renormalised: no bias in them
    s = np.asarray(jax.nn.sigmoid(logits))
    picked = np.take_along_axis(s, np.asarray(chosen), 1)
    assert np.allclose(np.asarray(weights),
                       picked / picked.sum(1, keepdims=True), atol=1e-6)
    assert float(np.asarray(weights).max()) <= 1.0
