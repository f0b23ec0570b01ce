"""The gated delta rule's two forms (``ops/linear_attention.py``) against
each other and against the benchmark's plain reference, which writes the
recurrence token by token and shares no code with either
(``perfbench/refs/olmo-hybrid-7b.py``, loaded by path: one reference, not
two); and the chunked form's second carrier, the Pallas kernel of
``ops/pallas/gated_delta.py``, under the TPU interpreter (it proves the
kernel's arithmetic and its walk, not what Mosaic makes of its products:
that is ``tests/test_chip_compile.py``'s and a chip run's).

Tolerances: everything here is float32 on the CPU.  The chunked form
reorders a sum of up to a few hundred products of unit keys and O(1)
values, so it agrees with the recurrence to a few float32 ulps of the
result's size: 2e-5 of the largest entry, where computing any of it in
bfloat16 (8 bits of mantissa) reads 1e-2.
"""

import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.ops import linear_attention as la
from distributed_tensorflow_tpu.ops.pallas import flash_attention as flash
from distributed_tensorflow_tpu.ops.pallas import gated_delta as kernel
from perfbench import spec

REF = spec.load_module(os.path.join(spec.HERE, "refs", "olmo-hybrid-7b.py"))
TOL = 2e-5
H, DK, DV = 3, 8, 16


def inputs(T, seed=0, B=2, resembling_keys=False, H=H, DK=DK, DV=DV):
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
    o_ker, s_ker = kernel.gated_delta(*x, state)
    assert rel(o_rec, o_ref) < TOL and rel(s_rec, s_ref) < TOL
    assert rel(o_chk, o_ref) < TOL and rel(s_chk, s_ref) < TOL
    assert rel(o_ker, o_ref) < TOL and rel(s_ker, s_ref) < TOL


@pytest.mark.parametrize("B,heads,dk,dv,T", [
    (1, 2, 12, 20, 130),     # widths that fill neither a sublane nor a lane
    (3, 4, 8, 16, 70),       # rows beside each other, four heads a step
    (2, 9, 24, 40, 64),      # nine heads: three a grid step, three steps
    (1, 3, 96, 192, 96),     # the hybrid's own widths
], ids=["ragged_widths", "three_rows", "nine_heads", "widths_96_192"])
def test_the_kernel_at_other_widths_and_rows(B, heads, dk, dv, T):
    """The wrapper's part: heads in groups a grid step, widths the lanes
    pad, more than one row, a last chunk half empty."""
    *x, state = inputs(T, seed=B, B=B, resembling_keys=True, H=heads, DK=dk,
                       DV=dv)
    o_ref, s_ref = reference(*x, state)
    o_ker, s_ker = kernel.gated_delta(*x, state)
    assert o_ker.shape == (B, T, heads, dv) and o_ker.dtype == jnp.float32
    assert rel(o_ker, o_ref) < TOL and rel(s_ker, s_ref) < TOL


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("T,keep", [(96, 70), (128, 64), (40, 0)])
def test_a_masked_tail_changes_nothing(T, keep, form):
    """Tokens with g = 0 and beta = 0 (padding, idle lanes) leave the state
    where the last real token left it, an all-zero key among them."""
    q, k, v, g, beta, state = inputs(T, seed=3)
    mask = (jnp.arange(T) < keep)[None, :, None]
    g, beta = jnp.where(mask, g, 0.0), jnp.where(mask, beta, 0.0)
    k = jnp.where(mask[..., None], k, 0.0)
    chunked = {"xla": la.gated_delta_chunked, "kernel": kernel.gated_delta}
    o, s = chunked[form](q, k, v, g, beta, state)
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


# ------------------------------------------ which carrier, and its gradient


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """``gated_delta_chunked`` chooses by ``jax.default_backend()``; the
    kernel it then calls must still be interpreted here.  Steered in the
    test, not through an option of the program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(kernel, "_interpret", lambda: True)
    monkeypatch.setattr(flash, "_warned", set())


def test_on_a_tpu_the_kernel_carries_it_and_its_gradient_is_the_xla_forms(
        as_on_a_tpu):
    *x, state = inputs(130, seed=11, resembling_keys=True)
    rng = np.random.default_rng(12)
    wo = jnp.asarray(rng.normal(size=(2, 130, H, DV)), jnp.float32)
    ws = jnp.asarray(rng.normal(size=state.shape), jnp.float32)

    def loss(form):
        def f(*operands):
            o, s = form(*operands)
            return jnp.sum(o * wo) + jnp.sum(s * ws)
        return f

    text = str(jax.make_jaxpr(la.gated_delta_chunked)(*x, state))
    assert "pallas_call" in text and "while" not in text
    value, grads = jax.value_and_grad(
        loss(la.gated_delta_chunked), argnums=tuple(range(6)))(*x, state)
    value_xla, grads_xla = jax.value_and_grad(
        loss(la._chunked_xla), argnums=tuple(range(6)))(*x, state)
    assert abs(float(value - value_xla)) < TOL * abs(float(value_xla))
    for got, want in zip(grads, grads_xla):
        assert got.shape == want.shape
        assert rel(got, want) < 1e-6         # the same program's output
    # from zeros where no state is given, as the training forward calls it
    o, s = la.gated_delta_chunked(*x)
    o_xla, s_xla = la._chunked_xla(*x, jnp.zeros_like(state))
    assert rel(o, o_xla) < TOL and rel(s, s_xla) < TOL


@pytest.mark.parametrize("tokens", [1024, 1600, 2400, 3584])
def test_the_kernel_takes_the_cells_buckets(tokens):
    q, v = (1, tokens, 30, 96), (1, tokens, 30, 192)
    assert kernel.supports(q, v) and kernel.refusal(q, v) == ""
    assert kernel._group(30, 96, 192) == 6


def test_a_refused_shape_takes_the_xla_form_and_says_why_once(as_on_a_tpu):
    """No silent fallback: the reason, once a reason."""
    assert "VMEM" in kernel.refusal((1, 64, 2, 4096), (1, 64, 2, 4096))
    assert not kernel.supports((1, 64, 2, 4096), (1, 64, 2, 4096))
    *x, state = inputs(70, seed=13)
    with pytest.warns(UserWarning, match="a chunk of 32 tokens"):
        o, s = la.gated_delta_chunked(*x, state, chunk=32)
    o_xla, s_xla = la._chunked_xla(*x, state, 32)
    assert bool(jnp.all(o == o_xla)) and bool(jnp.all(s == s_xla))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        la.gated_delta_chunked(*x, state, chunk=32)
