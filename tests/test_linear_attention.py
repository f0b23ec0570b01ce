"""The gated delta rule's two forms (``ops/linear_attention.py``) against
each other and against the benchmark's plain reference, which writes the
recurrence token by token and shares no code with either
(``perfbench/refs/olmo-hybrid-7b.py``, loaded by path: one reference, not
two).

Tolerances: everything here is float32 on the CPU.  The chunked form
reorders a sum of up to a few hundred products of unit keys and O(1)
values, so it agrees with the recurrence to a few float32 ulps of the
result's size: 2e-5 of the largest entry, where computing any of it in
bfloat16 (8 bits of mantissa) reads 1e-2.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.ops import linear_attention as la
from perfbench import spec

REF = spec.load_module(os.path.join(spec.HERE, "refs", "olmo-hybrid-7b.py"))
TOL = 2e-5
H, DK, DV = 3, 8, 16


def inputs(T, seed=0, B=2, resembling_keys=False):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(B, T, H, DK))
    k = rng.normal(size=(B, T, H, DK))
    if resembling_keys:      # what broke (I - a)(I + a^2)(I + a^4)...
        k = k + 3.0 * rng.normal(size=(1, 1, H, DK))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(DK)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(B, T, H, DV))
    g = -np.abs(rng.normal(size=(B, T, H))) * 0.05
    beta = 2.0 / (1.0 + np.exp(-2.0 * rng.normal(size=(B, T, H))))
    state = rng.normal(size=(B, H, DV, DK))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta, state)]


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def reference(q, k, v, g, beta, state):
    with jax.default_matmul_precision("highest"):
        o, s = jax.vmap(REF.delta_rule)(q, k, v, g, beta, state)
    return o, s


@pytest.mark.parametrize("T", [1, 63, 64, 65, 128, 200])
@pytest.mark.parametrize("resembling_keys", [False, True])
def test_chunked_is_the_recurrence_is_the_reference(T, resembling_keys):
    """Lengths that are and are not multiples of the chunk, from a state
    that is not zero."""
    *x, state = inputs(T, seed=T, resembling_keys=resembling_keys)
    o_ref, s_ref = reference(*x, state)
    o_rec, s_rec = la.gated_delta_recurrent(*x, state)
    o_chk, s_chk = la.gated_delta_chunked(*x, state)
    assert rel(o_rec, o_ref) < TOL and rel(s_rec, s_ref) < TOL
    assert rel(o_chk, o_ref) < TOL and rel(s_chk, s_ref) < TOL


@pytest.mark.parametrize("T,keep", [(96, 70), (128, 64), (40, 0)])
def test_a_masked_tail_changes_nothing(T, keep):
    """Tokens with g = 0 and beta = 0 (padding, idle lanes) leave the state
    where the last real token left it, an all-zero key among them."""
    q, k, v, g, beta, state = inputs(T, seed=3)
    mask = (jnp.arange(T) < keep)[None, :, None]
    g, beta = jnp.where(mask, g, 0.0), jnp.where(mask, beta, 0.0)
    k = jnp.where(mask[..., None], k, 0.0)
    o, s = la.gated_delta_chunked(q, k, v, g, beta, state)
    o_ref, s_ref = reference(q[:, :keep], k[:, :keep], v[:, :keep],
                             g[:, :keep], beta[:, :keep], state)
    assert bool(jnp.all(jnp.isfinite(o)))
    assert rel(s, s_ref) < TOL
    if keep:
        assert rel(o[:, :keep], o_ref) < TOL
    else:
        assert bool(jnp.all(s == state))


def test_the_step_leaves_an_idle_row_bit_for_bit():
    q, k, v, g, beta, state = inputs(1, seed=5)
    live = jnp.asarray([True, False])[:, None]
    _, s = la.gated_delta_step(
        q[:, 0], jnp.where(live[..., None], k[:, 0], 0.0), v[:, 0],
        jnp.where(live, g[:, 0], 0.0), jnp.where(live, beta[:, 0], 0.0),
        state)
    assert bool(jnp.all(s[1] == state[1]))
    assert not bool(jnp.all(s[0] == state[0]))


def test_the_convolution_and_its_tail():
    """``causal_conv`` over a sequence cut in two, the second half reading
    the first's tail, is the convolution of the whole; ``conv_tail`` before
    position 0 is zeros."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(2, 20, 6)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    whole = la.causal_conv(x, taps)
    lengths = jnp.asarray([11, 2])
    tail = la.conv_tail(x, lengths, 3)
    for b, n in enumerate([11, 2]):
        rest = la.causal_conv(x[b:b + 1, n:], taps, tail[b:b + 1])
        np.testing.assert_allclose(rest[0], whole[b, n:], atol=1e-6)
    assert bool(jnp.all(la.conv_tail(x, jnp.asarray([0, 1]), 3)[0] == 0))
    assert bool(jnp.all(la.conv_tail(x, jnp.asarray([0, 1]), 3)[1, :2] == 0))


def test_both_forms_are_named_in_the_program_text():
    *x, state = inputs(64)
    text = jax.jit(la.gated_delta_chunked).lower(*x, state).as_text(
        debug_info=True)
    assert "linear_attention.scan" in text
    step = jax.jit(la.gated_delta_step).lower(
        *(a[:, 0] for a in x), state).as_text(debug_info=True)
    assert "linear_attention.step" in step
