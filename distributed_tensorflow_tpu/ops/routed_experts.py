"""Routed experts without dropped tokens — the decoder's sparse MLP.

Beside :mod:`.moe` (``MoeMlp``: softmax gate, a capacity that DROPS what
overflows it, one-hot ``[G, S, E, C]`` dispatch, gelu experts, an auxiliary
loss; the ``bert_moe`` family's layer) this is the layer of the routed
decoders: every token goes to its ``k`` experts whatever the imbalance.

- :func:`route` — ``score="sigmoid"``: ``s = sigmoid(logits)``; the ``k``
  largest of ``s + bias`` are chosen (``bias``: a selection bias that steers
  the CHOICE and never enters the weights); the weights are ``s`` at the
  chosen, divided by their sum, times ``scale``.  ``score="softmax"``: the
  ``k`` largest LOGITS are chosen and the weights are a softmax over those
  ``k`` alone; no bias, no scale.
- :func:`routed_experts` — the (token, expert) pairs sorted by expert, three
  grouped matrix products over the stacked kernels ``[E, in, out]`` (gate,
  up, down: ``act(x Wg) * (x Wu)) Wd``, ``act`` SiLU or ReLU), and the rows
  put back in token order and summed under their weights.  One function for
  a prefill of thousands of tokens and for a decode step of a few lanes.
  Rows of a token that is not ``live`` are sorted behind the last group and
  belong to no expert: they read no kernel and come back zero.  Also returns
  how many pairs each expert got, ``[E]`` int32.
- :func:`grouped_matmul` — ``lhs[rows of group g] @ rhs[g]``.  On a TPU the
  Pallas grouped matmul that ships with JAX (``megablox.gmm``): its grid
  visits a row tile once for each group that has rows in it and skips a group
  that has none, so a decode step whose 64 pairs touch 41 of 64 experts reads
  41 experts' kernels, each once.  Elsewhere ``jax.lax.ragged_dot`` (the CPU
  tests compare the two in interpret mode).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: Row tile of the grouped product: the (token, expert) pairs are padded up
#: to a multiple of it, a whole tile where there are fewer.
ROW_TILE = 512
_SUBLANES = 16


ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}
SCORES = ("sigmoid", "softmax")


def route(logits: jax.Array, bias: jax.Array | None, k: int,
          scale: float = 1.0,
          score: str = "sigmoid") -> tuple[jax.Array, jax.Array]:
    """``logits`` [T, E] float32 -> (chosen experts [T, k] int32, their
    weights [T, k] float32)."""
    if score == "softmax":
        if bias is not None or scale != 1.0:
            raise ValueError(
                "route: a selection bias and a scale are the sigmoid "
                f"score's; score='softmax' got bias={bias is not None} and "
                f"scale={scale}")
        top, chosen = jax.lax.top_k(logits.astype(jnp.float32), k)
        return chosen.astype(jnp.int32), jax.nn.softmax(top, axis=-1)
    if score != "sigmoid":
        raise ValueError(f"Unknown score {score!r}; one of {SCORES}")
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), weights * scale


def _tile(n: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is at most
    ``cap``; ``n`` itself where it is small or has none."""
    if n <= cap:
        return n
    for t in range(cap - cap % 128, 0, -128):
        if n % t == 0:
            return t
    return n


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, kernel: bool | None = None,
                   interpret: bool = False) -> jax.Array:
    """``lhs`` [M, K] sorted by group, ``rhs`` [G, K, N], ``group_sizes``
    [G] int32 -> [M, N] in ``lhs``'s type.  Rows past ``sum(group_sizes)``
    belong to no group; what comes back for them is unspecified.  ``M`` is
    a multiple of 16, and of :data:`ROW_TILE` where it is larger."""
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    if not kernel:
        return jax.lax.ragged_dot(lhs, rhs, group_sizes).astype(lhs.dtype)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    m, k = lhs.shape
    n = rhs.shape[-1]
    # Measured on a v5e at [64, 2048, 1536] kernels (PERF.md, PR 35): the
    # whole contraction in one tile beats half of it by 15-20% at 32,768
    # rows, output tiles of 512 do as well as wider ones at 64 rows, and row
    # tiles of 1,024 are slower than 512.
    tiling = (min(m, ROW_TILE), _tile(k, 2048), _tile(n, 512))
    return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
               tiling=tiling, interpret=interpret)


def routed_experts(x: jax.Array, chosen: jax.Array, weights: jax.Array,
                   gate: jax.Array, up: jax.Array, down: jax.Array,
                   live: jax.Array | None = None, activation: str = "silu",
                   **grouped) -> tuple[jax.Array, jax.Array]:
    """``x`` [T, H]; ``chosen`` / ``weights`` [T, k] from :func:`route`;
    ``gate`` / ``up`` [E, H, I] and ``down`` [E, I, H]; ``live`` [T] bool
    (absent: every token is).  Returns (y [T, H] in ``x``'s type, pairs an
    expert got [E] int32)."""
    T, k = chosen.shape
    E = gate.shape[0]
    pairs = T * k
    rows = -(-pairs // _SUBLANES) * _SUBLANES
    if rows > ROW_TILE:
        rows = -(-pairs // ROW_TILE) * ROW_TILE
    expert = chosen.reshape(pairs)
    if live is not None:
        expert = jnp.where(jnp.repeat(live, k), expert, E)
    # E sorts behind every expert: dead and padding rows end up last.
    expert = jnp.pad(expert, (0, rows - pairs), constant_values=E)
    order = jnp.argsort(expert, stable=True)
    counts = jnp.zeros((E,), jnp.int32).at[expert].add(1, mode="drop")
    xs = jnp.take(x, jnp.minimum(order // k, T - 1), axis=0)
    h = ACTIVATIONS[activation](grouped_matmul(xs, gate, counts, **grouped)) \
        * grouped_matmul(xs, up, counts, **grouped)
    ys = grouped_matmul(h, down, counts, **grouped)
    ys = jnp.where((jnp.arange(rows) < jnp.sum(counts))[:, None], ys, 0)
    back = jnp.argsort(order)[:pairs]
    y = jnp.take(ys, back, axis=0).reshape(T, k, -1).astype(jnp.float32)
    y = jnp.sum(y * weights[..., None], axis=1)
    return y.astype(x.dtype), counts
