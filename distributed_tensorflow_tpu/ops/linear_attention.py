"""Gated delta rule — the token mixer of a linear-attention layer, in its two
forms (Yang et al., "Gated Delta Networks", 2024).

Per head the layer keeps a matrix state ``S`` [Dv, Dk] in float32 and, for
token ``t`` with a unit key ``k``, a query ``q``, a value ``v``, a decay
``alpha = exp(g)`` (``g <= 0``) and a step ``beta``::

    S_t = alpha_t * S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

- :func:`gated_delta_step` is that line for one token a row: the decode
  form (``linear_attention.step`` in the HLO).
- :func:`gated_delta_chunked` is the same recurrence over chunks of
  ``CHUNK`` tokens: inside a chunk everything is matrix products (the WY
  form: ``S_t = gamma_t S_0 + sum_i (gamma_t / gamma_i) u_i k_i^T`` with the
  pseudo-values ``u`` solving a unit lower-triangular system), and a
  ``lax.scan`` carries the state from chunk to chunk
  (``linear_attention.scan``).  Prefill and the full forward use it.

A token with ``g = 0`` and ``beta = 0`` changes nothing: that is how a
caller masks padding and idle lanes (the token's own output is then
meaningless but finite, an all-zero key included).

The core runs in float32 at ``HIGHEST`` matmul precision whatever the
activations' type: the triangular solve amplifies what a rounded key puts
into it, and the state is read back thousands of tokens later.

What runs where.  The chunked form is one algorithm on two carriers, chosen
by what the code observes (no option): on a TPU, for shapes the kernel
takes, ONE Pallas call a layer (``ops/pallas/gated_delta.py``: a chunk's
products, the inverse of its triangular system and the state's walk in
VMEM; nothing the size of the sequence but the output goes back to HBM);
elsewhere, so on the CPU and in every test's reference column, the XLA
form below, which is also what the kernel path's backward pass
differentiates.  As XLA lowers it for a v5e the XLA form is sixteen
fusions, XLA's blockwise inverse for the solve and a ``while`` of a turn a
chunk over float32 intermediates of ``[N, B, H, 64, 64..288]``: a few
percent of a layer's OPERATIONS but, at 3,584 tokens, 5.9 ms of device
time a layer call (1.6 times the layer's MLP) for the traffic of those
intermediates (PERF.md, PR 51).  The one-token step is XLA's on every
backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..utils import profiling
from .pallas import gated_delta as kernel
from .pallas.flash_attention import _warn_once

CHUNK = 64
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_mm = functools.partial(jnp.einsum, precision=_HI,
                        preferred_element_type=F32)


def l2_normalize(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, in float32; zero
    stays zero."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def causal_conv(x: jax.Array, taps: jax.Array,
                tail: jax.Array | None = None) -> jax.Array:
    """Causal depthwise convolution over time, no bias: ``y_t[c] = sum_j
    taps[j, c] * x_{t - (K-1) + j}[c]``.  ``x`` [B, T, C]; ``taps`` [K, C];
    ``tail`` [B, K-1, C] holds the inputs before ``x`` (zeros when None)."""
    K = taps.shape[0]
    B, T, C = x.shape
    if tail is None:
        tail = jnp.zeros((B, K - 1, C), x.dtype)
    seq = jnp.concatenate([tail.astype(F32), x.astype(F32)], axis=1)
    taps = taps.astype(F32)
    return sum(seq[:, j:j + T] * taps[j] for j in range(K))


def conv_tail(x: jax.Array, lengths: jax.Array, width: int) -> jax.Array:
    """The last ``width`` inputs before position ``lengths[b]`` of each row
    of ``x`` [B, T, C], oldest first; zeros stand before position 0."""
    pos = lengths[:, None] - width + jnp.arange(width)[None, :]     # [B, W]
    rows = jnp.take_along_axis(x, jnp.clip(pos, 0, x.shape[1] - 1)[..., None],
                               axis=1)
    return jnp.where((pos >= 0)[..., None], rows, jnp.zeros_like(rows))


def _solve_unit_lower(a: jax.Array, rhs: jax.Array) -> jax.Array:
    """``(I + a)^-1 rhs`` for strictly lower-triangular ``a`` [..., C, C]
    by forward substitution (XLA's triangular solve).  Not the product
    ``(I - a)(I + a^2)(I + a^4)...``: with keys that resemble each other
    the powers of ``a`` reach 1e18 before they cancel."""
    eye = jnp.eye(a.shape[-1], dtype=a.dtype)
    return jax.scipy.linalg.solve_triangular(eye + a, rhs, lower=True,
                                             unit_diagonal=True)


def gated_delta_step(q, k, v, g, beta, state):
    """One token a row.  ``q``, ``k`` [B, H, Dk]; ``v`` [B, H, Dv]; ``g``,
    ``beta`` [B, H]; ``state`` [B, H, Dv, Dk] float32.  Returns
    (``o`` [B, H, Dv] float32, the new state)."""
    with profiling.region("linear_attention.step"):
        q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)
        decayed = state * jnp.exp(g.astype(F32))[..., None, None]
        u = beta.astype(F32)[..., None] * (
            v - _mm("bhvk,bhk->bhv", decayed, k))
        state = decayed + u[..., :, None] * k[..., None, :]
        return _mm("bhvk,bhk->bhv", state, q), state


def gated_delta_recurrent(q, k, v, g, beta, state=None):
    """:func:`gated_delta_step` token by token over [B, T, H, *] inputs —
    the definition, for tests and short sequences."""
    B, T, H, Dk = q.shape
    if state is None:
        state = jnp.zeros((B, H, v.shape[-1], Dk), F32)

    def body(s, xs):
        o, s = gated_delta_step(*xs, s)
        return s, o

    state, o = jax.lax.scan(
        body, state, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def gated_delta_chunked(q, k, v, g, beta, state=None, chunk: int = CHUNK):
    """The recurrence over ``T`` tokens in chunks.  ``q``, ``k`` [B, T, H,
    Dk]; ``v`` [B, T, H, Dv]; ``g`` (log decay) and ``beta`` [B, T, H];
    ``state`` [B, H, Dv, Dk] float32 or None for zeros.  Returns (``o``
    [B, T, H, Dv] float32, the state after token T-1).  On a TPU the Pallas
    kernel carries it (the module's text; its gradient is the XLA form's);
    a shape the kernel refuses takes the XLA form and says so once."""
    if state is None:
        state = jnp.zeros((q.shape[0], q.shape[2], v.shape[-1], q.shape[-1]),
                          F32)
    if jax.default_backend() == "tpu":
        refused = kernel.refusal(q.shape, v.shape, chunk)
        if not refused:
            with profiling.region("linear_attention.scan"):
                return _chunked_kernel(q, k, v, g, beta, state)
        _warn_once(f"gated-delta: {refused}",
                   "gated_delta_chunked: the XLA form takes the place of "
                   f"the Pallas kernel: {refused}")
    return _chunked_xla(q, k, v, g, beta, state, chunk)


@jax.custom_vjp
def _chunked_kernel(q, k, v, g, beta, state):
    return kernel.gated_delta(q, k, v, g, beta, state)


def _chunked_kernel_fwd(*operands):
    return _chunked_kernel(*operands), operands


def _chunked_kernel_bwd(operands, cotangents):
    """The XLA form's gradient (it recomputes that form's forward)."""
    return jax.vjp(_chunked_xla, *operands)[1](cotangents)


_chunked_kernel.defvjp(_chunked_kernel_fwd, _chunked_kernel_bwd)


def _chunked_xla(q, k, v, g, beta, state, chunk: int = CHUNK):
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    pad = -T % chunk
    N = (T + pad) // chunk

    def chunks(x):                      # [B, T, H, *] -> [N, B, H, C, *]
        x = x.astype(F32)
        if pad:                         # padded tokens change nothing
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(B, N, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    with profiling.region("linear_attention.scan"):
        q, k, v = chunks(q), chunks(k), chunks(v)
        g, beta = chunks(g), chunks(beta)                  # [N, B, H, C]
        cum = jnp.cumsum(g, axis=-1)
        gamma = jnp.exp(cum)[..., None]                    # decay from chunk start
        to_end = jnp.exp(cum[..., -1:] - cum)[..., None]   # ... to chunk end
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        diff = cum[..., :, None] - cum[..., None, :]
        decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))  # gamma_i/gamma_j, j<=i
        kk = _mm("...ik,...jk->...ij", k, k)
        a = jnp.where(jnp.tril(lower, -1), beta[..., None] * decay * kk, 0.0)
        solved = _solve_unit_lower(a, beta[..., None] * jnp.concatenate(
            [v, gamma * k], axis=-1))
        u0, w = solved[..., :Dv], solved[..., Dv:]
        attn = _mm("...ik,...jk->...ij", q, k) * decay
        q_in, k_out = gamma * q, to_end * k
        end = jnp.exp(cum[..., -1])[..., None, None]

        def body(s, xs):
            u0, w, attn, q_in, k_out, end = xs
            u = u0 - _mm("bhck,bhvk->bhcv", w, s)
            o = _mm("bhck,bhvk->bhcv", q_in, s) \
                + _mm("bhij,bhjv->bhiv", attn, u)
            return end * s + _mm("bhcv,bhck->bhvk", u, k_out), o

        state, o = jax.lax.scan(body, state,
                                (u0, w, attn, q_in, k_out, end))
        o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)      # [B, N, C, H, Dv]
        return o.reshape(B, N * chunk, H, Dv)[:, :T], state
