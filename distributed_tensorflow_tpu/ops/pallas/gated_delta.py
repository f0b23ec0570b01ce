"""The chunked gated delta rule as one Pallas call a layer.

``ops/linear_attention.gated_delta_chunked`` in its XLA form writes every
chunk's intermediates (the pseudo-values ``u0`` and ``w``, the in-chunk
scores, the decayed queries and keys, the solve's right-hand side: each
``[N, B, H, 64, 64..288]`` float32) to HBM once and reads them once or
twice, then walks the chunks in a ``while`` of four small products a turn.
Here a grid step holds one chunk of a group of heads in VMEM: it reads the
chunk's ``q``, ``k``, ``v`` tiles and its decays, forms the scores, the
inverse of the chunk's unit triangular system and the pseudo-values, reads
and moves on the heads' states (float32, a VMEM scratch that lives from the
sequence's first chunk to its last) and writes the chunk's output.  Nothing
the size of the sequence but the output goes back to HBM, and the operands
and the output are heads-major, ``[B, H, T, *]``: a layout XLA gives their
producers and their reader for nothing, so no copy stands either side of
the call.

Precision is the XLA form's: float32 operands, float32 accumulation, every
product at ``HIGHEST``, asked for by name (a Mosaic product of float32
operands at the default precision is the product of their bfloat16
roundings).  The inverse is by forward substitution inside diagonal blocks
of ``_BASE`` and block merges above them (``T21 = -T22 A21 T11``), never
the product ``(I - a)(I + a^2)...`` (``linear_attention._solve_unit_lower``
says why).

Two things shaped the body (PERF.md, PR 51).  The MXU takes a ROW a cycle
whatever the row's width, ``HIGHEST`` is six passes of rows, and a head's
chain of products and substitution steps is serial: so a grid step holds
several heads and every array of the body has the heads LEADING; an
operation is traced once and Mosaic unrolls it over the heads, which lays
their chains side by side for the scheduler to fill one head's waits with
another's rows.  And every equation of the body is traced and lowered to
Mosaic's MLIR in every program that calls it, whatever the compile cache
holds (about 3 ms an equation a program on the benchmark's host): so the
body is kept SHORT.  Masks are factors, not ``jnp.where``s; ``u0`` and
``w`` are one product; the inverse's merges are one loop of three turns;
the products are Mosaic's own ``HIGHEST``, one equation each.  (Faster
bodies were built and measured: the products split into bfloat16 parts by
the kernel and laid out to cost fewer rows, 1.54 ms a layer call at 3,584
tokens with the heads' steps traced in turn and 1.74 with the heads
leading, where this one takes 2.48; they cost the cell 46 and 7 s
of warm set-up in tracing and lowering, against a gain in tokens/s that
had stopped growing with the kernel's speed.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _gspmd_hazard

#: Tokens a chunk: the kernel's constant (the XLA form's default too).
CHUNK = 64
#: The diagonal blocks inverted by substitution; merged by products above
#: (8 and 16 read the same on a v5e: PERF.md, PR 51).
_BASE = 8
#: Heads a grid step holds at most (a divisor of the head count is taken).
#: More heads fill more of one head's waits, and Mosaic's own compile time
#: grows faster than they do: six of 30 compiled in 1.2 s a call, ten in
#: 4.0, fifteen in 6.2 for a twelfth fewer bundles a head (on a body that
#: split its products into bfloat16 parts: PERF.md, PR 51).
_HEADS = 8
#: VMEM a grid step may plan for (a v5e core has 128 MiB, a v7x's 64).
_VMEM = 40 << 20
F32 = jnp.float32
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(a, b, dims):
    """``a`` times ``b`` a head, float32 to float32's precision
    (``HIGHEST``: six passes of bfloat16-exact parts through the MXU; at
    the default precision Mosaic multiplies the operands' bfloat16
    roundings).  ``a``, ``b`` [G, *, *]; ``dims`` names the summed axes of
    one head's two matrices."""
    (ca,), (cb,) = dims
    return jax.lax.dot_general(
        a, b, (((ca + 1,), (cb + 1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=F32)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def refusal(q_shape, v_shape, chunk: int = CHUNK) -> str:
    """Why the kernel does not take ``q`` [B, T, H, Dk] and ``v`` [B, T, H,
    Dv] ("" where it does).  Widths that fill no lane tile are fine (VMEM
    pads a tile's lanes); refused are another chunk than the kernel's, a
    state that leaves no room in VMEM for even one head's chunk beside it,
    and a multi-chip jit outside ``shard_map``, which cannot partition a
    Mosaic call."""
    if chunk != CHUNK:
        return f"a chunk of {chunk} tokens (the kernel's is {CHUNK})"
    if not _group(q_shape[2], q_shape[-1], v_shape[-1]):
        return (f"a state of {v_shape[-1]} x {q_shape[-1]} a head leaves no "
                f"room for a chunk beside it in {_VMEM >> 20} MiB of VMEM")
    if _gspmd_hazard():
        return "a multi-chip jit outside shard_map"
    return ""


def supports(q_shape, v_shape, chunk: int = CHUNK) -> bool:
    return not refusal(q_shape, v_shape, chunk)


def _vmem_bytes(G: int, Dk: int, Dv: int) -> int:
    """What a grid step of ``G`` heads holds: the q, k, v and o tiles twice
    (the pipeline's two buffers), the state coming in and going out twice
    and once as the scratch, all float32 in whole lanes."""
    lanes = lambda n: -(-n // 128) * 128                # noqa: E731
    tiles = 2 * CHUNK * (2 * lanes(Dk) + 2 * lanes(Dv))
    return 4 * G * (tiles + 5 * Dv * lanes(Dk))


def _group(H: int, Dk: int, Dv: int) -> int:
    """Heads a grid step holds: the largest divisor of ``H`` up to
    ``_HEADS`` whose tiles and states fit ``_VMEM``; 0 where none does."""
    return max((g for g in range(1, _HEADS + 1)
                if H % g == 0 and _vmem_bytes(g, Dk, Dv) <= _VMEM),
               default=0)


def _mask(keep):
    """A mask as a factor: the kernel zeroes by a product (every masked
    value is finite), which is one operation to trace where ``jnp.where``
    is a nested program."""
    return keep.astype(F32)


def _inverse_t(at):
    """The TRANSPOSE of ``(I + a)^-1`` a head from ``at``, ``a``'s
    transpose (strictly upper-triangular, [G, C, C]).  The diagonal blocks'
    inverses are built side by side in ``y`` [G, m, C] (``y[c, i]`` is
    entry ``(i, c)`` of ``i``'s block), column by column: ``L = L_0 L_1
    ...`` with ``L_j = I + a[:, j] e_j^T``, so ``L^-1 = ... (I - a[:, 1]
    e_1^T)(I - a[:, 0] e_0^T)``; a step takes row ``j`` of ``at``'s blocks,
    a sublane broadcast, times column ``j`` of every block so far, a gather
    along the lanes.  Above the blocks, level by level in ONE loop (a
    level's products are traced once): with the halves of a block of 2 m
    inverted its upper right is ``-T11^T A21^T T22^T``."""
    G, C, m = at.shape[0], at.shape[1], _BASE
    ii = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 1)
    jj = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 2)
    lane = jax.lax.broadcasted_iota(jnp.int32, (G * m, C), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (G * m, C), 0)
    cols = sum(_mask(lane // m == b).reshape(G, m, C)
               * at[:, b * m:(b + 1) * m] for b in range(C // m))   # [G, m, C]
    y = _mask(lane % m == sub % m)              # the heads' rows, one array
    for j in range(m - 1):
        pivot = jnp.take_along_axis(y, lane // m * m + j, axis=1)
        y = y - (cols[:, j:j + 1] * pivot.reshape(G, m, C)).reshape(G * m, C)
    x = _mask(ii // m == jj // m) * jnp.concatenate(
        [y.reshape(G, m, C)] * (C // m), axis=1)

    def merge(level, x):
        shift = level + (m.bit_length() - 1)    # blocks of m << level
        above = _mask((jj >> shift) == (ii >> shift) + 1) \
            * _mask((jj >> (shift + 1)) == (ii >> (shift + 1))) * at
        return x - _dot(x, _dot(above, x, _NN), _NN)

    levels = (C // m).bit_length() - 1
    return jax.lax.fori_loop(0, levels, merge, x)


def _kernel(cum_ref, beta_ref, q_ref, k_ref, v_ref, s_ref, o_ref, out_ref,
            state):
    """A chunk of the grid step's ``G`` heads, every array with the heads
    leading: ``q``, ``k`` [G, C, Dk]; ``v`` [G, C, Dv]; ``cum``, ``beta``
    [G, 1, C]; the states [G, Dv, Dk].  An operation is traced once and
    Mosaic unrolls it over the heads, so the scheduler finds the heads'
    chains side by side.  The chunk's [C, C] matrices are held TRANSPOSED
    (keys down, queries across): that is how the decays and ``beta`` come,
    as rows, and a product that sums over a chunk's tokens then finds both
    operands with the tokens down."""
    n = pl.program_id(2)
    C = CHUNK
    Dv = v_ref.shape[-1]

    @pl.when(n == 0)
    def _():
        state[...] = s_ref[0]

    ii = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 1)
    jj = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 2)
    eye, upper = _mask(ii == jj), _mask(ii < jj)

    def column(row):                    # [G, 1, C] -> [G, C, 1]
        return jnp.sum(eye * row, axis=2, keepdims=True)

    q, k = q_ref[0], k_ref[0]
    cum = cum_ref[0, :, pl.ds(n, 1), :]
    beta = beta_ref[0, :, pl.ds(n, 1), :]
    beta_col, cum_col = column(beta), column(cum)
    last = jnp.sum(_mask(jj[:, :1] == C - 1) * cum, axis=2,
                   keepdims=True)                           # [G, 1, 1]
    gamma = jnp.exp(cum_col)                                # from chunk start
    # gamma_j / gamma_i at [i, j], j >= i: the decay between two tokens
    # (the exponent zeroed below the diagonal, where it would overflow)
    grow = jnp.exp((upper + eye) * (cum - cum_col))
    # [k k^T | k q^T]: 64 rows fill the MXU's 128 lanes
    scores = _dot(k, jnp.concatenate([k, q], axis=1), _NT)  # [G, C, 2C]
    at = upper * beta * grow * scores[:, :, :C]
    attn_t = (upper + eye) * grow * scores[:, :, C:]
    # u0 and w share the inverse: one product over [beta v | beta gamma k]
    solved = _dot(_inverse_t(at), jnp.concatenate(
        [beta_col * v_ref[0], (beta_col * gamma) * k], axis=2), _TN)
    u0, w = solved[:, :, :Dv], solved[:, :, Dv:]
    s = state[...]
    read = _dot(jnp.concatenate([w, gamma * q], axis=1), s, _NT)
    u = u0 - read[:, :C]
    o_ref[0] = read[:, C:] + _dot(attn_t, u, _TN)
    state[...] = jnp.exp(last) * s + _dot(
        u, jnp.exp(last - cum_col) * k, _TN)

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        out_ref[0] = state[...]


# Traced once a shape, whichever layers call it (``paged_attention``'s note).
@functools.partial(jax.jit, static_argnames=("interpret",))
def _gated_delta(q, k, v, g, beta, state, *, interpret: bool):
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    C = CHUNK
    N = -(-T // C)
    G = _group(H, Dk, Dv)

    def heads_major(x):                 # [B, T, H, *] -> [B, H, N * C, *]
        x = jnp.moveaxis(x.astype(F32), 2, 1)
        return jnp.pad(x, [(0, 0), (0, 0), (0, N * C - T)]
                       + [(0, 0)] * (x.ndim - 3))   # padding changes nothing

    q, k, v = heads_major(q), heads_major(k), heads_major(v)
    g, beta = (heads_major(x).reshape(B, H, N, C) for x in (g, beta))
    cum = jnp.cumsum(g, axis=-1)
    rows = pl.BlockSpec((1, G, N, C), lambda b, h, n: (b, h, 0, 0))
    tile = lambda d: pl.BlockSpec((1, G, C, d),         # noqa: E731
                                  lambda b, h, n: (b, h, n, 0))
    held = pl.BlockSpec((1, G, Dv, Dk), lambda b, h, n: (b, h, 0, 0))
    o, state = pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct((B, H, N * C, Dv), F32),
                   jax.ShapeDtypeStruct((B, H, Dv, Dk), F32)),
        grid=(B, H // G, N),
        in_specs=[rows, rows, tile(Dk), tile(Dk), tile(Dv), held],
        out_specs=(tile(Dv), held),
        scratch_shapes=[pltpu.VMEM((G, Dv, Dk), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(G, Dk, Dv) + (16 << 20)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="gated_delta",
    )(cum, beta, q, k, v, state)
    return jnp.moveaxis(o[:, :, :T], 1, 2), state


def gated_delta(q, k, v, g, beta, state):
    """``linear_attention.gated_delta_chunked``'s arguments and results
    (``state`` given): ``q``, ``k`` [B, T, H, Dk]; ``v`` [B, T, H, Dv];
    ``g``, ``beta`` [B, T, H]; ``state`` [B, H, Dv, Dk] float32."""
    return _gated_delta(q, k, v, g, beta, state, interpret=_interpret())
