"""Paged attention for ONE query a lane: the decode step's read of a K/V
pool, as a Pallas TPU kernel over the pool's flat rows.

The serving tier holds a layer's keys and values as pages of ``page``
tokens, a token's kv heads side by side in one flat row
(``[pages + 1, page, G * D]``, the last page the sentinel's zeros:
``models.gpt.init_kv_pool``), and a lane's pages by its row of a page
table.  The plain form (``models.gpt.gather_pages`` +
``GptBlock._attend_rows``) gathers EVERY entry of the table side by side a
layer a step and masks what a lane does not hold; this kernel leaves the
pools in HBM and, lane by lane, copies only the pages that can hold a valid
row into VMEM, several in flight, a chunk ahead of the one it scores:

* a lane walks the pages ``0 .. n - 1`` of its table, ``n`` one past the
  last HELD page (entry below the sentinel) at or before its position's
  page: a full layer's pages up to ``positions // page``, a ring's the
  same until the lane has gone round and then the ring whole.  A page past
  that is never read, an idle lane (nothing held) costs no copy and gives
  zeros;
* scores in float32 from products in the compute type, the mask by
  position (a ring's also by ``behind < window``: ``decode_step_paged``'s
  ``valid``), a running maximum and sum in float32, weights cast to the
  compute type for the product with the values, float32 accumulation, one
  division at the end: no step at a lower precision than ``_attend_rows``
  takes;
* grouped heads: a query head is widened to the whole row, zero outside
  its kv head's D lanes, as ``_attend_rows`` does, so the two products are
  plain matmuls over ``[tokens, G * D]`` and no row is ever re-laid out;
* a head that DIVIDES 128 (64: two kv heads a lane tile) goes through the
  same body: the flat row is presented as ``G * D / 128`` kv "heads" of
  128 and a query head widened to 128, zeros in its neighbours' part of
  the tile, so a score is ``q . k`` exactly; the head's own D lanes of the
  128-wide context are taken after the call (:func:`_widen` /
  :func:`_own_part`).

Rows of a lane's own pages past its position, left by a former owner,
count under a weight of exactly zero; the rows of a chunk past the walk
are zeroed in VMEM, not copied, so nothing a lane does not own reaches its
sums, NaN and inf there included.

"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Bytes of keys (and as many of values) a chunk holds in VMEM, twice over
#: (the chunk being scored and the one in flight): a chunk is as many pages
#: as fit, in whole lanes of 128 tokens, between one such and ``_CHUNK_MAX``
#: tokens.  On the chip (PERF.md, PR 45) 2 MiB and 1,024 tokens read a
#: tenth faster than 1 MiB and 512 where pages are small or lanes few (the
#: window cell's 16 KB pages, chat's four live lanes) and the same elsewhere.
_CHUNK_BYTES = 2 << 20
_CHUNK_MAX = 1024


def _interpret() -> bool:
    """Compiled by Mosaic on a TPU; elsewhere the TPU interpreter, which
    runs the copies, their semaphores and the dynamic trip counts as they
    are written (tests only: the program's CPU path is the plain form)."""
    return jax.default_backend() != "tpu"


def supports(pool: jax.Array, head_dim: int) -> bool:
    """Whether the kernel can walk ``pool``: a page is whole sublane tiles
    of its type (16 rows of bfloat16, 8 of float32; a float8 page of 16 is
    half a tile), a kv head whole lane tiles of 128, or a whole part of
    one (64, 32: a head that divides 128) where the flat row is whole
    tiles."""
    tile = {4: 8, 2: 16}.get(pool.dtype.itemsize)
    return (tile is not None and pool.ndim == 3 and pool.shape[1] % tile == 0
            and 128 % pool.shape[1] == 0
            and (head_dim % 128 == 0 or 128 % head_dim == 0)
            and pool.shape[2] % head_dim == 0 and pool.shape[2] % 128 == 0)


def pages_walked(page_table, positions, sentinel: int, page: int):
    """``n`` [B]: one past the last held page at or before a lane's
    position's page (0 for a lane that holds nothing); NumPy or JAX, the
    host counts ``attn_pages_read`` by it.  A ring that has gone round has
    every page at or before that."""
    xp = jnp if isinstance(page_table, jax.Array) else np
    idx = xp.arange(page_table.shape[1])[None, :]
    walked = (page_table < sentinel) & (idx <= positions[:, None] // page)
    return xp.max(xp.where(walked, idx + 1, 0), axis=1).astype(xp.int32)


def _chunk_pages(page: int, row_bytes: int) -> int:
    """Pages a chunk: whole lanes of 128 tokens."""
    tokens = max(128, min(_CHUNK_MAX, _CHUNK_BYTES // row_bytes // 128 * 128))
    return tokens // page


def _kernel(n_ref, table_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, acc_ref, *, pages: int, kv_heads: int,
            window: int, scale: float):
    B, H, D = q_ref.shape
    page, row = k_hbm.shape[1], k_hbm.shape[2]
    MP = table_ref.shape[1]
    sentinel = k_hbm.shape[0] - 1
    T = pages * page
    compute = q_ref.dtype
    lowest = jnp.finfo(jnp.float32).min
    # own[h, c]: lane c of the flat row belongs to query head h's kv head.
    own = (lax.broadcasted_iota(jnp.int32, (H, row), 0) // (H // kv_heads)
           == lax.broadcasted_iota(jnp.int32, (H, row), 1) // D)

    def copies(b, c, slot, j):
        """The two copies of page ``j`` of lane ``b``'s chunk ``c``."""
        phys = jnp.minimum(table_ref[b, jnp.minimum(c * pages + j, MP - 1)],
                           sentinel)
        rows = pl.ds(pl.multiple_of(j * page, page), page)
        return (pltpu.make_async_copy(k_hbm.at[phys], k_buf.at[slot, rows],
                                      sems.at[slot, 0]),
                pltpu.make_async_copy(v_hbm.at[phys], v_buf.at[slot, rows],
                                      sems.at[slot, 1]))

    def walked(b, c):
        """The pages of lane ``b``'s chunk ``c`` that its walk reaches."""
        return jnp.clip(n_ref[b] - c * pages, 0, pages)

    def start(b, c, slot):
        def one(j, carry):
            for copy in copies(b, c, slot, j):
                copy.start()
            return carry
        lax.fori_loop(0, walked(b, c), one, 0)

    def land(b, c, slot):
        """Wait for the chunk's copies; the values' rows past the walk were
        not copied and hold whatever the buffer did: zeros instead, since a
        weight of zero does not silence a NaN."""
        def one(j, carry):
            for copy in copies(b, c, slot, j):
                copy.wait()
            return carry

        def blank(j, carry):
            v_buf[slot, pl.ds(pl.multiple_of(j * page, page), page), :] = (
                jnp.zeros((page, row), v_buf.dtype))
            return carry
        count = walked(b, c)
        lax.fori_loop(0, count, one, 0)
        lax.fori_loop(count, pages, blank, 0)

    def lane_after(b):
        """The next lane after ``b`` that walks a page, or B."""
        return lax.while_loop(
            lambda i: (i < B) & (n_ref[jnp.minimum(i, B - 1)] == 0),
            lambda i: i + 1, b + 1)

    def valid_rows(b, c):
        """[1, T]: the chunk's rows that count for lane ``b``."""
        n, pos = n_ref[b], pos_ref[b]
        s = c * T + lax.broadcasted_iota(jnp.int32, (1, T), 1)
        if window:
            d = pos % (MP * page) - s
            behind = jnp.where(d < 0, d + MP * page, d)
            valid = (behind < window) & (behind <= pos)
        else:
            valid = s <= pos
        valid &= s < n * page

        def allocated():
            # A hole in the walk (a sentinel entry before the last held
            # page) read the sentinel's zeros, and does not count.
            lanes = lax.broadcasted_iota(jnp.int32, (1, T), 1) // page
            held = jnp.zeros((1, T), jnp.int32)
            for j in range(pages):
                entry = table_ref[b, jnp.minimum(c * pages + j, MP - 1)]
                held = jnp.where(lanes == j, (entry < sentinel).astype(
                    jnp.int32), held)
            return held

        # (A conditional may not yield a vector of booleans: whole numbers.)
        holes = n_ref[B + b] < n
        return valid & (lax.cond(
            holes, allocated, lambda: jnp.ones((1, T), jnp.int32)) > 0)

    def idle(b, slot):
        o_ref[b] = jnp.zeros((H, D), o_ref.dtype)
        return slot

    def seated(b, slot):
        chunks = (n_ref[b] + pages - 1) // pages
        wide = jnp.where(own, jnp.concatenate([q_ref[b]] * kv_heads, axis=1),
                         jnp.zeros((), compute))
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def chunk(c, carry):
            m, l, slot = carry
            last = c + 1 == chunks

            @pl.when(jnp.logical_not(last))
            def _():
                start(b, c + 1, 1 - slot)

            @pl.when(last)
            def _():
                after = lane_after(b)

                @pl.when(after < B)
                def _():
                    start(after, 0, 1 - slot)

            land(b, c, slot)
            valid = valid_rows(b, c)
            s = lax.dot_general(
                wide, k_buf[slot].astype(compute), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale       # [H, T]
            s = jnp.where(valid, s, lowest)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l = alpha * l + p.sum(axis=1, keepdims=True)
            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p.astype(compute), v_buf[slot].astype(compute),
                preferred_element_type=jnp.float32)               # [H, row]
            return m_new, l, 1 - slot

        m, l, slot = lax.fori_loop(
            0, chunks, chunk,
            (jnp.full((H, 1), -jnp.inf, jnp.float32),
             jnp.zeros((H, 1), jnp.float32), slot))
        # A head's own D lanes of the wide context; a head with no valid
        # row gives zeros.
        mine = jnp.where(own, acc_ref[...], 0.0)
        ctx = mine[:, :D]
        for g in range(1, kv_heads):
            ctx = ctx + mine[:, g * D:(g + 1) * D]
        o_ref[b] = (ctx / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)
        return slot

    first = lane_after(-1)

    @pl.when(first < B)
    def _():
        start(first, 0, 0)

    # Lane by lane, the buffer slot the lane's first chunk lands in carried
    # along; a lane that walks nothing (an idle one) gives zeros and costs
    # neither a copy nor a product.
    lax.fori_loop(
        0, B, lambda b, slot: lax.cond(n_ref[b] > 0, seated, idle, b, slot),
        jnp.int32(0))


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    page_table: jax.Array, positions: jax.Array, *,
                    window: int = 0) -> jax.Array:
    """Attention of ``q`` [B, H, D], one query a lane at ``positions`` [B],
    over the rows the lane holds of ``k_pool`` / ``v_pool``
    [pages + 1, page, G * D] by ``page_table`` [B, MP] (the pools' last
    page is the sentinel's): [B, H, D], what ``_attend_rows`` gives over
    ``gather_pages`` of both under ``decode_step_paged``'s mask.  With
    ``window`` the table is a lane's RING of MP pages and a row counts
    while it is less than ``window`` behind the lane's position.
    """
    if not supports(k_pool, q.shape[-1]) or k_pool.shape != v_pool.shape:
        raise ValueError(f"paged_attention cannot walk pools {k_pool.shape} "
                         f"{k_pool.dtype} at a head of {q.shape[-1]}")
    return _paged_attention(
        q, k_pool, v_pool, page_table.astype(jnp.int32),
        positions.astype(jnp.int32), window=window,
        pages=_chunk_pages(k_pool.shape[1],
                           k_pool.shape[2] * k_pool.dtype.itemsize),
        interpret=_interpret())


def _tile_part(heads: int, kv_heads: int, head_dim: int) -> jax.Array:
    """[H]: which ``head_dim`` lanes of its lane tile of 128 a query head's
    kv head lies at (kv head ``g`` is part ``g % (128 // head_dim)`` of
    tile ``g // (128 // head_dim)`` of the flat row)."""
    return (jnp.arange(heads) // (heads // kv_heads)) % (128 // head_dim)


def _widen(q: jax.Array, kv_heads: int) -> jax.Array:
    """``q`` [B, H, D] with D a whole part of 128 -> [B, H, 128]: each
    query head in its kv head's D lanes of the lane tile that head shares
    with its neighbours, zeros in the rest."""
    B, H, D = q.shape
    own = _tile_part(H, kv_heads, D)[:, None] == jnp.arange(128 // D)
    return jnp.where(own[None, :, :, None], q[:, :, None, :],
                     jnp.zeros((), q.dtype)).reshape(B, H, 128)


def _own_part(ctx: jax.Array, kv_heads: int, head_dim: int) -> jax.Array:
    """The head's own ``head_dim`` lanes of a 128-wide context
    [B, H, 128] (:func:`_widen`'s placement)."""
    B, H, _ = ctx.shape
    return jnp.take_along_axis(
        ctx.reshape(B, H, 128 // head_dim, head_dim),
        _tile_part(H, kv_heads, head_dim)[None, :, None, None],
        axis=2)[:, :, 0]


# Traced ONCE a shape, whichever layers call it: a step program of 48
# layers holds one kernel and 48 calls of it (traced a layer at a time, an
# earlier form of the kernel took the looped step's trace from 1.8 to 15.6 s
# at twelve layers: set-up time, PERF.md PR 45).
@functools.partial(jax.jit, static_argnames=("window", "pages", "interpret"))
def _paged_attention(q, k_pool, v_pool, page_table, positions, *,
                     window: int, pages: int, interpret: bool):
    head_dim = q.shape[-1]
    page, row = k_pool.shape[1], k_pool.shape[2]
    if head_dim < 128:
        # Two (or four) kv heads a lane tile: the kernel sees heads of 128.
        q = _widen(q, row // head_dim)
    B, H, D = q.shape
    sentinel = k_pool.shape[0] - 1
    n = pages_walked(page_table, positions, sentinel, page)
    held = jnp.sum((page_table < sentinel) & (
        jnp.arange(page_table.shape[1])[None, :] < n[:, None]), axis=1)
    T = pages * page
    buffers = 4 * T * row * k_pool.dtype.itemsize
    ctx = pl.pallas_call(
        functools.partial(_kernel, pages=pages, kv_heads=row // D,
                          window=window, scale=1.0 / head_dim ** 0.5),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[pl.BlockSpec((B, H, D), lambda i, *_: (0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((B, H, D), lambda i, *_: (0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, T, row), k_pool.dtype),
                            pltpu.VMEM((2, T, row), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((H, row), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=buffers + (24 << 20)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_attention",
    )(jnp.concatenate([n, held.astype(jnp.int32)]), page_table, positions,
      q, k_pool, v_pool)
    if head_dim < 128:
        ctx = _own_part(ctx, row // head_dim, head_dim)
    return ctx
