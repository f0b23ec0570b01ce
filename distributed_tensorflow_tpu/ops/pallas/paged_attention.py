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

A LATENT layer's decode read (``GptBlock.latent_decode_step_paged``, the
absorbed form) is a second body over the same walk (:class:`_Walk`):
:func:`latent_paged_attention`.  Its row is one latent all heads share and
one rotated key, in two pools; the latents of a page are copied ONCE and
serve as keys and as values, the rotated keys lie two tokens a row of 128
lanes (:func:`key_rows`) so that the chip holds their pool as it is
indexed, and the heads (20: no whole sublane tile) are padded in the
wrapper.  A body of its own and no flag in the K/V one: the K/V kernel
handed the same pool twice would copy it twice.
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


def _walk_counts(page_table, positions, sentinel: int, page: int):
    """[2 B] int32, what a kernel is told of the lanes' walks beside the
    table: :func:`pages_walked`, and behind it how many of those pages a
    lane holds (fewer where its walk has a hole)."""
    n = pages_walked(page_table, positions, sentinel, page)
    held = jnp.sum((page_table < sentinel) & (
        jnp.arange(page_table.shape[1])[None, :] < n[:, None]), axis=1)
    return jnp.concatenate([n, held.astype(jnp.int32)])


def _chunk_pages(page: int, row_bytes: int) -> int:
    """Pages a chunk: whole lanes of 128 tokens."""
    tokens = max(128, min(_CHUNK_MAX, _CHUNK_BYTES // row_bytes // 128 * 128))
    return tokens // page


class _Walk:
    """What the two kernels share: which pages of its table a lane's chunk
    reaches, their copies out of ``pools`` (HBM, [pages + 1, rows, width])
    into ``bufs`` (VMEM, [2, chunk rows, width]: the chunk being scored
    and the one in flight) under ``sems`` [2, pools], and which of a
    chunk's token rows count.  ``values`` are the buffers whose rows enter
    a weighted sum, so are zeroed past the walk."""

    def __init__(self, n_ref, table_ref, pos_ref, pools, bufs, sems, *,
                 pages: int, page: int, values):
        self.n_ref, self.table_ref, self.pos_ref = n_ref, table_ref, pos_ref
        self.pools, self.bufs, self.sems = pools, bufs, sems
        self.pages, self.page, self.values = pages, page, values
        self.B, self.MP = table_ref.shape
        self.sentinel = pools[0].shape[0] - 1

    def copies(self, b, c, slot, j):
        """The copies of page ``j`` of lane ``b``'s chunk ``c``, one a
        pool."""
        phys = jnp.minimum(
            self.table_ref[b, jnp.minimum(c * self.pages + j, self.MP - 1)],
            self.sentinel)
        rows = {}    # one slice a page height: the K/V pools share theirs
        for pool in self.pools:
            n = pool.shape[1]
            if n not in rows:
                rows[n] = pl.ds(pl.multiple_of(j * n, n), n)
        return tuple(
            pltpu.make_async_copy(pool.at[phys],
                                  buf.at[slot, rows[pool.shape[1]]],
                                  self.sems.at[slot, i])
            for i, (pool, buf) in enumerate(zip(self.pools, self.bufs)))

    def walked(self, b, c):
        """The pages of lane ``b``'s chunk ``c`` that its walk reaches."""
        return jnp.clip(self.n_ref[b] - c * self.pages, 0, self.pages)

    def start(self, b, c, slot):
        def one(j, carry):
            for copy in self.copies(b, c, slot, j):
                copy.start()
            return carry
        lax.fori_loop(0, self.walked(b, c), one, 0)

    def land(self, b, c, slot):
        """Wait for the chunk's copies; the values' rows past the walk were
        not copied and hold whatever the buffer did: zeros instead, since a
        weight of zero does not silence a NaN."""
        def one(j, carry):
            for copy in self.copies(b, c, slot, j):
                copy.wait()
            return carry

        def blank(j, carry):
            for buf in self.values:
                n = buf.shape[1] // self.pages
                buf[slot, pl.ds(pl.multiple_of(j * n, n), n), :] = (
                    jnp.zeros((n, buf.shape[2]), buf.dtype))
            return carry
        count = self.walked(b, c)
        lax.fori_loop(0, count, one, 0)
        lax.fori_loop(count, self.pages, blank, 0)

    def lane_after(self, b):
        """The next lane after ``b`` that walks a page, or B."""
        B = self.B
        return lax.while_loop(
            lambda i: (i < B) & (self.n_ref[jnp.minimum(i, B - 1)] == 0),
            lambda i: i + 1, b + 1)

    def ahead(self, b, c, chunks, slot):
        """While chunk ``c`` of lane ``b`` is scored, the next one's copies
        (the lane's, or the first of the next lane that walks a page) are
        in flight into the other slot."""
        last = c + 1 == chunks

        @pl.when(jnp.logical_not(last))
        def _():
            self.start(b, c + 1, 1 - slot)

        @pl.when(last)
        def _():
            after = self.lane_after(b)

            @pl.when(after < self.B)
            def _():
                self.start(after, 0, 1 - slot)

    def valid_rows(self, b, c, window: int = 0):
        """[1, T]: the chunk's rows that count for lane ``b``."""
        pages, page, MP, B = self.pages, self.page, self.MP, self.B
        T = pages * page
        n, pos = self.n_ref[b], self.pos_ref[b]
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
                entry = self.table_ref[b, jnp.minimum(c * pages + j, MP - 1)]
                held = jnp.where(lanes == j, (entry < self.sentinel).astype(
                    jnp.int32), held)
            return held

        # (A conditional may not yield a vector of booleans: whole numbers.)
        holes = self.n_ref[B + b] < n
        return valid & (lax.cond(
            holes, allocated, lambda: jnp.ones((1, T), jnp.int32)) > 0)

    def lanes(self, seated, idle):
        """Lane by lane, the buffer slot the lane's first chunk lands in
        carried along; a lane that walks nothing (an idle one) costs
        neither a copy nor a product."""
        first = self.lane_after(-1)

        @pl.when(first < self.B)
        def _():
            self.start(first, 0, 0)

        lax.fori_loop(
            0, self.B, lambda b, slot: lax.cond(
                self.n_ref[b] > 0, seated, idle, b, slot), jnp.int32(0))


def _running(m, l, s, valid):
    """A chunk's scores ``s`` [H, T] float32 into the running maximum and
    sum: (the new maximum, the new sum, what the sums so far shrink by,
    the chunk's weights [H, T], zero where a row does not count)."""
    s = jnp.where(valid, s, jnp.finfo(jnp.float32).min)
    m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    return m_new, alpha * l + p.sum(axis=1, keepdims=True), alpha, p


def _kernel(n_ref, table_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, acc_ref, *, pages: int, kv_heads: int,
            window: int, scale: float):
    B, H, D = q_ref.shape
    page, row = k_hbm.shape[1], k_hbm.shape[2]
    compute = q_ref.dtype
    walk = _Walk(n_ref, table_ref, pos_ref, (k_hbm, v_hbm), (k_buf, v_buf),
                 sems, pages=pages, page=page, values=(v_buf,))
    # own[h, c]: lane c of the flat row belongs to query head h's kv head.
    own = (lax.broadcasted_iota(jnp.int32, (H, row), 0) // (H // kv_heads)
           == lax.broadcasted_iota(jnp.int32, (H, row), 1) // D)

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
            walk.ahead(b, c, chunks, slot)
            walk.land(b, c, slot)
            valid = walk.valid_rows(b, c, window)
            s = lax.dot_general(
                wide, k_buf[slot].astype(compute), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale       # [H, T]
            m, l, alpha, p = _running(m, l, s, valid)
            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p.astype(compute), v_buf[slot].astype(compute),
                preferred_element_type=jnp.float32)               # [H, row]
            return m, l, 1 - slot

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

    walk.lanes(seated, idle)


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
    counts = _walk_counts(page_table, positions, k_pool.shape[0] - 1, page)
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
    )(counts, page_table, positions, q, k_pool, v_pool)
    if head_dim < 128:
        ctx = _own_part(ctx, row // head_dim, head_dim)
    return ctx


# ------------------------------------------------- one latent row a token


def key_rows(page: int, rope: int) -> int:
    """Rows a PAGE of a latent layer's rotated keys is held in
    (``models.gpt.init_kv_pool``): ``rope`` entries a token fill no lane
    tile of 128 and the chip then lays the pool out with the pages
    minor-most, so a page holds ``128 // rope`` tokens a row where that
    comes out even, token ``o`` of a page in row ``o % rows`` at lanes
    ``o // rows * rope``: the page's first ``rows`` tokens side by side
    with its next.  ``page`` rows where it does not (tiny test shapes)."""
    per = 128 // rope if 128 % rope == 0 else 1
    return page // per if page % per == 0 else page


def pack_keys(keys: jax.Array, rows: int) -> jax.Array:
    """Rotated keys by page ``[..., page, rope]`` as a pool holds them,
    ``[..., rows, page // rows * rope]`` (:func:`key_rows`)."""
    *lead, page, rope = keys.shape
    if rows == page:
        return keys
    return jnp.swapaxes(keys.reshape(*lead, page // rows, rows, rope),
                        -3, -2).reshape(*lead, rows, page // rows * rope)


def unpack_keys(packed: jax.Array, rope: int) -> jax.Array:
    """:func:`pack_keys` undone: ``[..., rows, width]`` -> ``[..., page,
    rope]``."""
    *lead, rows, width = packed.shape
    if width == rope:
        return packed
    return jnp.swapaxes(packed.reshape(*lead, rows, width // rope, rope),
                        -3, -2).reshape(*lead, rows * (width // rope), rope)


def supports_latent(latent_pool, key_pool) -> bool:
    """Whether the latent kernel can walk a layer's two pools: latents in
    pages of whole sublane tiles (bfloat16 or float32) and whole lane
    tiles, and the rotated keys packed (:func:`key_rows`) into rows of 128
    that make whole 8-row tiles of the chip's memory a page."""
    tile = {4: 8, 2: 16}.get(latent_pool.dtype.itemsize)
    return (tile is not None and latent_pool.dtype == key_pool.dtype
            and latent_pool.ndim == key_pool.ndim == 3
            and latent_pool.shape[0] == key_pool.shape[0]
            and latent_pool.shape[1] % tile == 0
            and 128 % latent_pool.shape[1] == 0
            and latent_pool.shape[2] % 128 == 0
            and key_pool.shape[2] == 128 and key_pool.shape[1] % 8 == 0
            and latent_pool.shape[1] % key_pool.shape[1] == 0
            and 128 % (latent_pool.shape[1] // key_pool.shape[1]) == 0)


def _latent_kernel(n_ref, table_ref, pos_ref, q_ref, r_ref, lat_hbm, key_hbm,
                   o_ref, lat_buf, key_buf, sems, acc_ref, *, pages: int,
                   scale: float):
    B, H, C = q_ref.shape
    page, rows = lat_hbm.shape[1], key_hbm.shape[1]
    parts, T = page // rows, pages * page
    compute = q_ref.dtype
    contract = (((1,), (1,)), ((), ()))
    # The latents are the values too: ONE copy a page serves both.
    walk = _Walk(n_ref, table_ref, pos_ref, (lat_hbm, key_hbm),
                 (lat_buf, key_buf), sems, pages=pages, page=page,
                 values=(lat_buf,))
    part = lax.broadcasted_iota(jnp.int32, (1, 1, 128), 2) // (128 // parts)

    def rotated_keys(slot):
        """[T, 128] in the tokens' order: a page's ``rows`` rows once a
        part, each keeping its part's lanes (whole 8-row tiles of float32
        move, no lane does); the query's rotated part lies in every part
        of ``r_ref``."""
        held = key_buf[slot].astype(jnp.float32).reshape(pages, rows, 128)
        return jnp.concatenate(
            [jnp.where(part == i, held, 0.0) for i in range(parts)],
            axis=1).reshape(T, 128).astype(compute)

    def idle(b, slot):
        o_ref[b] = jnp.zeros((H, C), o_ref.dtype)
        return slot

    def seated(b, slot):
        chunks = (n_ref[b] + pages - 1) // pages
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def chunk(c, carry):
            m, l, slot = carry
            walk.ahead(b, c, chunks, slot)
            walk.land(b, c, slot)
            valid = walk.valid_rows(b, c)
            latents = lat_buf[slot].astype(compute)               # [T, C]
            s = (lax.dot_general(q_ref[b], latents, contract,
                                 preferred_element_type=jnp.float32)
                 + lax.dot_general(r_ref[b], rotated_keys(slot), contract,
                                   preferred_element_type=jnp.float32)
                 ) * scale                                        # [H, T]
            m, l, alpha, p = _running(m, l, s, valid)
            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p.astype(compute), latents,
                preferred_element_type=jnp.float32)               # [H, C]
            return m, l, 1 - slot

        m, l, slot = lax.fori_loop(
            0, chunks, chunk,
            (jnp.full((H, 1), -jnp.inf, jnp.float32),
             jnp.zeros((H, 1), jnp.float32), slot))
        o_ref[b] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)
        return slot

    walk.lanes(seated, idle)


def latent_paged_attention(q_latent: jax.Array, q_rotated: jax.Array,
                           latent_pool: jax.Array, key_pool: jax.Array,
                           page_table: jax.Array, positions: jax.Array, *,
                           scale: float) -> jax.Array:
    """The ABSORBED form's attention over the rows a lane holds: the
    weighted mean of its cached latents [B, H, C], the weights a softmax of
    ``(q_latent . latent + q_rotated . rotated key) * scale`` over every
    cached token at or before ``positions`` [B].  ``q_latent`` [B, H, C] is
    the query's un-rotated part folded into the latents' space,
    ``q_rotated`` [B, H, rope] its rotated part; ``latent_pool``
    [pages + 1, page, C] and ``key_pool`` [pages + 1, page / 2, 128]
    (:func:`pack_keys`) the layer's pools, their last page the sentinel's.
    What ``GptBlock.latent_decode_step_paged``'s plain form gives over
    ``gather_pages`` of both; a page is copied once and its latents serve
    as keys and as values."""
    if not supports_latent(latent_pool, key_pool):
        raise ValueError(
            f"latent_paged_attention cannot walk pools {latent_pool.shape} "
            f"{latent_pool.dtype} and {key_pool.shape} {key_pool.dtype}")
    row_bytes = (latent_pool.shape[2] + 128 * key_pool.shape[1]
                 // latent_pool.shape[1]) * latent_pool.dtype.itemsize
    return _latent_paged_attention(
        q_latent, q_rotated, latent_pool, key_pool,
        page_table.astype(jnp.int32), positions.astype(jnp.int32),
        scale=float(scale),
        pages=_chunk_pages(latent_pool.shape[1], row_bytes),
        interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("scale", "pages", "interpret"))
def _latent_paged_attention(q_latent, q_rotated, latent_pool, key_pool,
                            page_table, positions, *, scale: float,
                            pages: int, interpret: bool):
    B, heads, C = q_latent.shape
    page, rows = latent_pool.shape[1], key_pool.shape[1]
    # Heads in whole sublane tiles (20 -> 24: zero queries, dropped below),
    # the rotated part once a part of the keys' row.
    H = -(-heads // 8) * 8
    pad = ((0, 0), (0, H - heads), (0, 0))
    q_latent = jnp.pad(q_latent, pad)
    q_rotated = jnp.pad(jnp.concatenate([q_rotated] * (page // rows), axis=-1),
                        pad)
    counts = _walk_counts(page_table, positions, latent_pool.shape[0] - 1,
                          page)
    T = pages * page
    buffers = 2 * (T * C + pages * rows * 128) * latent_pool.dtype.itemsize
    whole = lambda *shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, *_: (0,) * len(shape))
    mean = pl.pallas_call(
        functools.partial(_latent_kernel, pages=pages, scale=scale),
        out_shape=jax.ShapeDtypeStruct((B, H, C), q_latent.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[whole(B, H, C), whole(B, H, 128),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole(B, H, C),
            scratch_shapes=[pltpu.VMEM((2, T, C), latent_pool.dtype),
                            pltpu.VMEM((2, pages * rows, 128),
                                       key_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((H, C), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=buffers + (24 << 20)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="latent_paged_attention",
    )(counts, page_table, positions, q_latent, q_rotated, latent_pool,
      key_pool)
    return mean[:, :heads]
