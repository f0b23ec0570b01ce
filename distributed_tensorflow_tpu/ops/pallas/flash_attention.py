"""Flash attention as a Pallas TPU kernel — blockwise online-softmax in VMEM.

The reference has no attention op at all (its model is a 784→100→10 MLP,
reference ``distributed.py:65-87``); this kernel backs the framework's
transformer stack where XLA's fused attention is not enough: O(S) memory in
sequence length (no [S, S] score materialization in HBM), fp32 accumulation,
MXU-shaped block matmuls.

Layout/grid design (pallas_guide.md idioms):
- inputs [B, S, H, D] are viewed as [B*H, S, D]; grid = (B*H, S/bq, S/bk) with
  the K-block dimension innermost — TPU grids execute sequentially over the
  last dimension, so the VMEM scratch accumulators (m, l, acc) carry the
  running softmax state across K blocks of one (head, Q-block) pair;
- the output block is written once, on the last K step;
- scores/stats stay entirely in VMEM; fp32 throughout
  (``preferred_element_type``) regardless of input dtype.

Differentiation: ``jax.custom_vjp``; the backward pass is blockwise pallas
too (FlashAttention-2 style, O(S) HBM, no [S, S] scores in either direction).
The forward saves the per-row logsumexp; ONE kernel (``_dqkv_kernel``: grid
over K blocks, scanning Q) rebuilds a tile's p and ds once and accumulates dk,
dv and, in a whole-row VMEM scratch, dq.  Where that dq row (S x D in whole
lanes, float32) exceeds ``_DQ_ROW_BYTES`` two kernels do it, dk/dv and dq
(grid over Q blocks, scanning K), each rebuilding every tile: the row's
bytes choose, nothing else.  The only dense fallback is the top-level one
in :func:`flash_attention`, which routes forward and backward through XLA.

On non-TPU backends the kernels run in interpreter mode, so CPU CI covers
them.

Several chips: GSPMD cannot partition a Mosaic call.  Where the program is
traced under :func:`ambient_mesh` (the sync step builders do that) and the
mesh's only axes of size > 1 are batch axes, :func:`flash_attention` maps
the kernel over dimension 0 with ``shard_map``; any other multi-chip jit
outside a shard_map gets the dense XLA formulation.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

_NEG = -1e30
_LANE = 128


def _pick_block(s: int, preferred: int | None = None,
                window: int = 0) -> int:
    """Largest power-of-two divisor of ``s`` capped at ``preferred``.

    The default cap is SEQUENCE-DEPENDENT (measured on the v5e rig,
    causal bf16 fwd+bwd, D=128): 512 for short rows — at S=2048 it beats
    the kernels' original 128 by ~1.2-1.3x and 1024 is a wash (19.3 vs
    19.7 ms) — but 1024 for S >= 4096, where fewer grid steps carrying
    the online-softmax state win outright: 20.3 -> 19.1 ms at S=4096 and
    27.2 -> 23.7 ms (−13%) at S=8192 (r4 sweep).  The 1024² fp32 score
    block costs 4 MiB of VMEM, which compiles with margin on this
    generation.

    SLIDING-WINDOW kernels keep the 512 cap regardless of S: the band
    spans ``ceil((window-1)/block)+1`` K blocks, so a block wider than
    the window inflates the keys actually fetched (2x1024 vs 3x512 for
    window=1024) — the r4 sweep measured the 1024 block REGRESSING the
    windowed rows (S=32768 w=1024: 58.8 -> 60.6 ms) while winning the
    full-causal ones.
    """
    if preferred is None:
        preferred = 512 if window else (1024 if s >= 4096 else 512)
    b = 1
    while s % (b * 2) == 0 and b * 2 <= preferred:
        b *= 2
    return b


def _layout_ok(s: int) -> bool:
    """True when the [*, S] row arrays (mask/lse/delta) can be sliced per
    block on compiled Mosaic: single-block rows slice statically, multi-block
    rows need 128-lane-aligned offsets."""
    b = _pick_block(s)
    return b == s or b % _LANE == 0


def _band_nb(window: int, block: int) -> int:
    """K blocks a q block's sliding-window band spans (block_q == block_k):
    the range [q_lo - window + 1, q_lo + block - 1] covers the diagonal block
    plus ceil((window - 1) / block) older ones."""
    return (window + block - 2) // block + 1


def _row_slice(ref, i, block: int, n: int):
    """``ref[0, 0, i*block : i*block+block]`` with a STATIC offset when the
    grid dimension has a single step — Mosaic cannot prove alignment of a
    dynamic minor-dim offset even when i is identically zero."""
    if n == 1:
        return ref[0, 0, :block]
    return ref[0, 0, pl.ds(i * block, block)]


def _block_valid(logits_shape, mask_blk, *, causal, iq, ik, block_q, block_k,
                 q_offset=0, k_offset=0, window=0):
    """Validity mask for one [bq, bk] score block (padding + causal + window).

    ``q_offset``/``k_offset`` shift the causal position grid — 0 for the
    monolithic kernels, the chunk's (possibly dynamic) global position for
    the ring chunk kernels.  ``window`` > 0 (causal only) restricts each
    query to its ``window`` most recent keys: ``q_pos - k_pos < window``."""
    valid = jnp.ones(logits_shape, dtype=jnp.bool_)
    if mask_blk is not None:
        valid = valid & (mask_blk[None, :] != 0)
    if causal:
        q_pos = (q_offset + iq * block_q
                 + jax.lax.broadcasted_iota(jnp.int32, logits_shape, 0))
        k_pos = (k_offset + ik * block_k
                 + jax.lax.broadcasted_iota(jnp.int32, logits_shape, 1))
        valid = valid & (q_pos >= k_pos)
        if window:
            valid = valid & (q_pos - k_pos < window)
    return valid


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, m_scr, l_scr,
            acc_scr, *, scale: float, causal: bool, block_q: int,
            block_k: int, nq: int, nkb: int, skip_empty: bool = False,
            window: int = 0, band: int = 0):
    iq = pl.program_id(1)
    nk = pl.num_programs(2)
    if band:
        # Banded grid (sliding window): the K dimension iterates only the
        # ``band`` blocks that can intersect this q block's window — grid
        # step j maps to true K block iq - (band-1) + j; the BlockSpec
        # index_map clips negatives to 0 (junk block, masked/skipped below).
        ik = iq - (band - 1) + pl.program_id(2)
    else:
        ik = pl.program_id(2)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # [bq, D]
        k = k_ref[0].astype(jnp.float32)                  # [bk, D]
        logits = jax.lax.dot_general(                     # [bq, bk]
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

        ik_c = jnp.clip(ik, 0, nkb - 1) if band else ik   # safe slicing
        mask_blk = (None if mask_ref is None
                    else _row_slice(mask_ref, ik_c, block_k, nkb))
        valid = _block_valid(logits.shape, mask_blk, causal=causal,
                             iq=iq, ik=ik,
                             block_q=block_q, block_k=block_k, window=window)
        if band:
            # Interpreter path computes out-of-range band steps (clipped junk
            # block) and masks them away; compiled TPU skips them entirely.
            valid = valid & (ik >= 0)
        logits = jnp.where(valid, logits, _NEG)

        m_prev = m_scr[:, :1]                             # [bq, 1]
        blk_max = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, blk_max)
        # `valid` multiply kills exp(0)=1 rows while everything seen is masked.
        p = jnp.exp(logits - m_new) * valid.astype(jnp.float32)
        corr = jnp.exp(m_prev - m_new)                    # [bq, 1]
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(                         # [bq, D]
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if band and skip_empty:
        # Banded grid already restricts to the window; only the left edge's
        # clipped (negative-index) steps remain to skip.
        pl.when(ik >= 0)(_compute)
    elif skip_empty:
        # Causal: skip K blocks entirely above the diagonal — their every
        # element is masked, so running them is pure wasted MXU work (~2x at
        # large S).  With a sliding window (full grid), also skip blocks
        # entirely below the band.  Compiled TPU only: the CPU interpreter
        # can't lower a dynamic pl.when condition.
        cond = ik * block_k < (iq + 1) * block_q
        if window:
            cond &= (ik + 1) * block_k > iq * block_q - window + 1
        pl.when(cond)(_compute)
    else:
        _compute()

    @pl.when(pl.program_id(2) == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[:, :1], 1e-30)          # fully-masked rows -> 0
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        # Per-row logsumexp of the scaled scores: the backward pass
        # reconstitutes p = exp(s - L) from it blockwise.  Stored [BH, 1, S]
        # full-row (the mask-block trick: Mosaic wants the last two block
        # dims (8, 128)-tileable or whole-array); each Q block writes its
        # segment.
        if nq == 1:
            lse_ref[0, 0, :block_q] = m_scr[:, 0] + jnp.log(l[:, 0])
        else:
            lse_ref[0, 0, pl.ds(iq * block_q, block_q)] = (
                m_scr[:, 0] + jnp.log(l[:, 0]))


def _to_bh(x):
    """[B, S, H, D] -> [B*H, S, D]"""
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _from_bh(x, B, H):
    BH, S, D = x.shape
    return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _mask_input(kv_mask):
    return kv_mask.astype(jnp.int32)[:, None, :]


def _mask_spec(S, H):
    # Mask is per-batch (not per-head): block row = bh // H.  The block spans
    # the full sequence — Mosaic tiling wants the minor block dim divisible by
    # 128 or equal to the array dim, and block_k is neither for short/odd S —
    # and the kernels slice their K/Q block out themselves.
    return pl.BlockSpec((1, 1, S), lambda bh, i, j, H=H: (bh // H, 0, 0),
                        memory_space=pltpu.VMEM)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


_warned: set = set()


def _warn_once(key: str, msg: str) -> None:
    if key not in _warned:
        _warned.add(key)
        import warnings
        warnings.warn(msg, stacklevel=3)


def _inside_shard_map() -> bool:
    """True when tracing under shard_map (named axes bound): the kernel then
    sees per-device local arrays and lowers per-device.

    The axis env has no public accessor; this is its location in the pinned
    JAX (pyproject.toml).  If it moves, this raises — a quiet "not in
    shard_map" would turn every kernel off on several chips."""
    from jax._src import core
    return bool(core.get_axis_env().axis_names())


def _gspmd_hazard() -> bool:
    """Compiled Mosaic kernels cannot be auto-partitioned by GSPMD: under a
    multi-device jit *outside* shard_map the lowering raises.  (Interpreter
    mode lowers to plain partitionable HLO, so CPU CI is unaffected.)"""
    hazard = (jax.default_backend() == "tpu" and jax.device_count() > 1
              and not _inside_shard_map())
    if hazard:
        _warn_once(
            "gspmd-hazard",
            "pallas kernel requested under a multi-chip jit outside "
            "shard_map: GSPMD cannot partition Mosaic calls, using the "
            "dense XLA formulation instead (flash_attention keeps its "
            "kernel under a parallel/sync.py step built for a mesh whose "
            "only non-trivial axes are batch axes; otherwise wrap the op in "
            "shard_map, as the ring attention path does)")
    return hazard


def _flash_forward(q, k, v, kv_mask, *, causal: bool, window: int = 0):
    B, S, H, D = q.shape
    block_q = _pick_block(S, window=window)
    block_k = _pick_block(S, window=window)
    scale = 1.0 / float(D) ** 0.5

    qt, kt, vt = _to_bh(q), _to_bh(k), _to_bh(v)

    nq, nkb = S // block_q, S // block_k
    # Sliding window: restrict the K grid dimension to the blocks that can
    # intersect the band — the win over masking alone is that skipped
    # blocks are never even FETCHED into VMEM, so HBM traffic (the long-S
    # bottleneck) is O(S * window) too, not just the MXU work.
    band = 0
    if causal and window:
        nb = _band_nb(window, block_k)
        if nb < nkb:
            band = nb

    if band:
        grid = (B * H, nq, band)
        kv_idx = (lambda bh, iq, j, nb=band, hi=nkb - 1:
                  (bh, jnp.clip(iq - (nb - 1) + j, 0, hi), 0))
    else:
        grid = (B * H, nq, nkb)
        kv_idx = lambda bh, iq, ik: (bh, ik, 0)
    q_spec = pl.BlockSpec((1, block_q, D), lambda bh, iq, ik: (bh, iq, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, block_k, D), kv_idx,
                           memory_space=pltpu.VMEM)

    in_specs = [q_spec, kv_spec, kv_spec]
    inputs = [qt, kt, vt]
    if kv_mask is not None:
        in_specs.append(_mask_spec(S, H))
        inputs.append(_mask_input(kv_mask))

    interpret = _interpret()
    opts = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
                nq=nq, nkb=nkb,
                skip_empty=causal and not interpret, window=window, band=band)
    kernel = functools.partial(_kernel, **opts)
    if kv_mask is None:
        kernel = _insert_none_mask(kernel, pos=3)

    out, lse = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
                   jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32)],
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, block_q, D),
                                lambda bh, iq, ik: (bh, iq, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, 1, S), lambda bh, iq, ik: (bh, 0, 0),
                                memory_space=pltpu.VMEM)],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANE), jnp.float32),   # running max m
            pltpu.VMEM((block_q, _LANE), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, D), jnp.float32),       # output accumulator
        ],
        interpret=interpret,
    )(*inputs)
    return _from_bh(out, B, H), lse


# ---------------------------------------------------------------------------
# Blockwise backward (FlashAttention-2): p is reconstituted from the saved
# logsumexp; dk/dv accumulate over Q blocks, dq over K blocks.

def _insert_none_mask(kernel, pos: int):
    """Adapt a mask-taking kernel to a call with no mask input: pallas passes
    refs positionally, so splice ``None`` in where ``mask_ref`` would be."""
    def wrapped(*refs):
        return kernel(*refs[:pos], None, *refs[pos:])
    return wrapped


def _bwd_block(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, *,
               scale, causal, block_q, block_k, iq, ik, nq, nkb,
               q_offset=0, k_offset=0, window=0):
    """Shared per-block math for one [bq, bk] tile; returns the 5-tuple
    ``(p, ds, do, q_scaled, k)`` (the fp32 block operands are reused by the
    callers' accumulation matmuls).

    ``q_offset``/``k_offset`` shift the causal position grid — 0 for the
    monolithic backward, the chunk's dynamic global position for the ring
    chunk kernels."""
    q = q_ref[0].astype(jnp.float32) * scale              # [bq, D]
    k = k_ref[0].astype(jnp.float32)                      # [bk, D]
    logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    ik_c = jnp.clip(ik, 0, nkb - 1)
    iq_c = jnp.clip(iq, 0, nq - 1)
    mask_blk = (None if mask_ref is None
                else _row_slice(mask_ref, ik_c, block_k, nkb))
    valid = _block_valid(logits.shape, mask_blk, causal=causal, iq=iq, ik=ik,
                         block_q=block_q, block_k=block_k,
                         q_offset=q_offset, k_offset=k_offset, window=window)
    # Banded grids hand in out-of-range block indices at the edges (their
    # BlockSpec clips the fetch; the interpreter computes-and-masks here,
    # compiled TPU skips the body via the callers' pl.when guard).
    valid = valid & (ik == ik_c) & (iq == iq_c)
    lse_blk = _row_slice(lse_ref, iq_c, block_q, nq)      # [bq]
    delta_blk = _row_slice(delta_ref, iq_c, block_q, nq)  # [bq]
    # Mask BEFORE the exp: a fully-masked row has L ~ _NEG, and a raw finite
    # logit minus that would overflow exp to inf (inf * 0 = NaN).  With the
    # where, masked entries give exp(_NEG - L) ∈ {0, 1}, and the valid
    # multiply zeroes the residue.
    logits = jnp.where(valid, logits, _NEG)
    p = jnp.exp(logits - lse_blk[:, None]) * valid.astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)                    # [bq, D]
    v = v_ref[0].astype(jnp.float32)                      # [bk, D]
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_blk[:, None])                    # [bq, bk]
    return p, ds, do, q, k


def _causal_guard(compute, *, skip_empty, iq, ik, block_q, block_k,
                  window=0):
    """Skip [bq, bk] tiles entirely above the causal diagonal (all-masked:
    p and ds are identically zero there) — same ~2x MXU saving as the
    forward's guard — and, with a sliding window, tiles entirely below the
    band.  Compiled TPU only; the CPU interpreter can't lower a dynamic
    pl.when condition."""
    if skip_empty:
        cond = ik * block_k < (iq + 1) * block_q
        if window:
            cond &= (ik + 1) * block_k > iq * block_q - window + 1
        pl.when(cond)(compute)
    else:
        compute()


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                block_q, block_k, nq, nkb, skip_empty, window=0, band=0):
    ik = pl.program_id(1)
    if band:
        # Banded grid: K block ik receives gradients from q blocks
        # [ik, ik + band - 1] only (its window's queries); step j maps to
        # true q block ik + j, clipped by the BlockSpec at the top edge.
        iq = ik + pl.program_id(2)
    else:
        iq = pl.program_id(2)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        p, ds, do, q, _ = _bwd_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            iq=iq, ik=ik, nq=nq, nkb=nkb, window=window)
        # dv += p^T do ; dk += ds^T (q*scale) (q was pre-scaled in _bwd_block)
        dv_scr[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    if band and skip_empty:
        pl.when(iq <= nq - 1)(_compute)
    else:
        _causal_guard(_compute, skip_empty=skip_empty, iq=iq, ik=ik,
                      block_q=block_q, block_k=block_k, window=window)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _emit():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
               dq_ref, dq_scr, *, scale, causal, block_q, block_k, nq, nkb,
               skip_empty, window=0, band=0):
    iq = pl.program_id(1)
    nk = pl.num_programs(2)
    if band:
        ik = iq - (band - 1) + pl.program_id(2)
    else:
        ik = pl.program_id(2)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        _, ds, _, _, k = _bwd_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            iq=iq, ik=ik, nq=nq, nkb=nkb, window=window)
        # dq += ds k * scale  (ds is the gradient wrt the SCALED logits, and
        # logits = scale * q k^T, so d/dq = scale * ds k).
        dq_scr[:] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if band and skip_empty:
        pl.when(ik >= 0)(_compute)
    else:
        _causal_guard(_compute, skip_empty=skip_empty, iq=iq, ik=ik,
                      block_q=block_q, block_k=block_k, window=window)

    @pl.when(pl.program_id(2) == nk - 1)
    def _emit():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


# The largest dq row ([S, D] in float32, D in whole lanes: what VMEM holds)
# that the one backward kernel keeps for a whole (batch, head) walk; a longer
# row takes the two kernels above.  4 MiB is S 8,192 at D 128.  Beside the
# row Mosaic holds its output block twice (in the array's type), and all of
# that comes ON TOP of what ``_dkv_kernel`` needs at the same blocks (13 of
# this generation's 16 MiB of scoped VMEM at blocks of 1,024), so the call
# asks for the default limit plus the row's bytes: 28 MiB at most of 128.
_DQ_ROW_BYTES = 4 * 1024 * 1024
_SCOPED_VMEM_BYTES = 16 * 1024 * 1024


def _dqkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                 dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *, scale,
                 causal, block_q, block_k, nq, nkb, skip_empty, window=0,
                 band=0):
    """All three gradients from ONE rebuild of a tile's ``p`` and ``ds``.

    ``_dkv_kernel``'s walk (grid (BH, nk, nq|band), q innermost) with the dq
    row of the whole (batch, head) in scratch beside dk/dv: a tile adds
    ``scale * ds k`` to its q block's rows, and the row is written out once,
    at the walk's last step.  For one q block the K blocks still arrive in
    ascending order (the outer grid dimension), so every sum runs in the
    order the two kernels run it."""
    ik = pl.program_id(1)
    iq = ik + pl.program_id(2) if band else pl.program_id(2)
    first = pl.program_id(2) == 0
    last = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(first & (ik == 0))
    def _init_row():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(first)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        p, ds, do, q, k = _bwd_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            iq=iq, ik=ik, nq=nq, nkb=nkb, window=window)
        dv_scr[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        # A band's top edge hands in q blocks past the last (skipped on the
        # chip, all-masked zeros under the interpreter): clip the rows.
        iq_c = jnp.clip(iq, 0, nq - 1) if band else iq
        rows = (slice(None) if nq == 1 else
                pl.ds(pl.multiple_of(iq_c * block_q, block_q), block_q))
        dq_scr[rows, :] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if band and skip_empty:
        pl.when(iq <= nq - 1)(_compute)
    else:
        _causal_guard(_compute, skip_empty=skip_empty, iq=iq, ik=ik,
                      block_q=block_q, block_k=block_k, window=window)

    @pl.when(last)
    def _emit():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(last & (ik == nkb - 1))
    def _emit_row():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_backward(q, k, v, kv_mask, o, lse, g, *, causal: bool,
                    window: int = 0):
    B, S, H, D = q.shape
    block_q = _pick_block(S, window=window)
    block_k = _pick_block(S, window=window)
    scale = 1.0 / float(D) ** 0.5

    qt, kt, vt = _to_bh(q), _to_bh(k), _to_bh(v)
    ot, dot_ = _to_bh(o), _to_bh(g)
    # delta_i = sum_d do_id * o_id — the softmax-jacobian row term.
    # [BH, 1, S] full-row layout, like lse (see _flash_forward).
    delta = jnp.sum(dot_.astype(jnp.float32) * ot.astype(jnp.float32),
                    -1)[:, None, :]

    interpret = _interpret()
    nq, nkb = S // block_q, S // block_k
    band = 0
    if causal and window:
        nb = _band_nb(window, block_k)
        if nb < nkb:
            band = nb
    opts = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
                nq=nq, nkb=nkb,
                skip_empty=causal and not interpret, window=window, band=band)

    def build(kernel_fn, *, q_minor: bool):
        """in_specs/inputs/kernel shared by both backward calls.

        ``q_minor``: q blocks indexed by the innermost grid dim (the dk/dv
        call, grid (BH, nk, nq|band)); otherwise by the middle dim (the dq
        call, grid (BH, nq, nk|band)).  In band mode the innermost dim
        iterates only the window's blocks; its index_map derives the true
        block from the outer index and clips at the edges (the kernels skip
        or mask the clipped steps).
        """
        if band and q_minor:        # dkv: j -> q block ik + j
            q_idx = (lambda bh, i, j, hi=nq - 1:
                     (bh, jnp.clip(i + j, 0, hi), 0))
        elif band:                  # dq: j -> k block iq - (band-1) + j
            q_idx = lambda bh, i, j: (bh, i, 0)
        else:
            q_idx = ((lambda bh, i, j: (bh, j, 0)) if q_minor
                     else (lambda bh, i, j: (bh, i, 0)))
        if band and q_minor:
            k_idx = lambda bh, i, j: (bh, i, 0)
        elif band:
            k_idx = (lambda bh, i, j, nb=band, hi=nkb - 1:
                     (bh, jnp.clip(i - (nb - 1) + j, 0, hi), 0))
        else:
            k_idx = ((lambda bh, i, j: (bh, i, 0)) if q_minor
                     else (lambda bh, i, j: (bh, j, 0)))
        q_spec = pl.BlockSpec((1, block_q, D), q_idx,
                              memory_space=pltpu.VMEM)
        k_spec = pl.BlockSpec((1, block_k, D), k_idx,
                              memory_space=pltpu.VMEM)
        row_spec = pl.BlockSpec((1, 1, S), lambda bh, i, j: (bh, 0, 0),
                                memory_space=pltpu.VMEM)
        in_specs = [q_spec, k_spec, k_spec, q_spec, row_spec, row_spec]
        inputs = [qt, kt, vt, dot_, lse, delta]
        kernel = functools.partial(kernel_fn, **opts)
        if kv_mask is not None:
            in_specs.append(_mask_spec(S, H))
            inputs.append(_mask_input(kv_mask))
        else:
            kernel = _insert_none_mask(kernel, pos=6)
        return kernel, in_specs, inputs

    # dk/dv: grid (BH, nk, nq) — Q innermost, accumulated in VMEM scratch.
    dkv = dict(
        out_shape=[jax.ShapeDtypeStruct((B * H, S, D), k.dtype),
                   jax.ShapeDtypeStruct((B * H, S, D), v.dtype)],
        out_specs=[pl.BlockSpec((1, block_k, D),
                                lambda bh, ik, iq: (bh, ik, 0),
                                memory_space=pltpu.VMEM)] * 2,
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32)] * 2)
    dq_shape = jax.ShapeDtypeStruct((B * H, S, D), q.dtype)

    row = S * -(-D // _LANE) * _LANE        # a dq row's elements in VMEM
    if row * 4 <= _DQ_ROW_BYTES:
        # The dq row fits beside them: one walk feeds all three gradients.
        kernel, in_specs, inputs = build(_dqkv_kernel, q_minor=True)
        dq, dk, dv = pl.pallas_call(
            kernel,
            out_shape=[dq_shape] + dkv["out_shape"],
            grid=(B * H, nkb, band or nq),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, S, D), lambda bh, ik, iq: (bh, 0, 0),
                                    memory_space=pltpu.VMEM)]
            + dkv["out_specs"],
            scratch_shapes=[pltpu.VMEM((S, D), jnp.float32)]
            + dkv["scratch_shapes"],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_SCOPED_VMEM_BYTES
                + row * (4 + 2 * q.dtype.itemsize)),
            interpret=interpret,
        )(*inputs)
    else:
        kernel, in_specs, inputs = build(_dkv_kernel, q_minor=True)
        dk, dv = pl.pallas_call(
            kernel, grid=(B * H, nkb, band or nq), in_specs=in_specs,
            interpret=interpret, **dkv)(*inputs)

        # dq: grid (BH, nq, nk) — K innermost.
        kernel, in_specs, inputs = build(_dq_kernel, q_minor=False)
        dq = pl.pallas_call(
            kernel,
            out_shape=dq_shape,
            grid=(B * H, nq, band or nkb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block_q, D),
                                   lambda bh, iq, ik: (bh, iq, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            interpret=interpret,
        )(*inputs)

    return (_from_bh(dq, B, H), _from_bh(dk, B, H), _from_bh(dv, B, H))


# ---------------------------------------------------------------------------
# Chunked variant: fold ONE K/V chunk into running online-softmax state.
# This is the building block ring attention (parallel/ring.py) runs per hop:
# carry (m, l, acc) travels outside, so the [Sq, Sk] scores of each hop stay
# in VMEM blocks instead of materializing per-hop logits in HBM.

def _chunk_tile_guard(compute, offs_ref, *, skip_empty, iq, ik,
                      block_q, block_k, window=0):
    """Skip tiles entirely above the causal diagonal — and, with a sliding
    window, entirely below the band — with the chunk's dynamic global
    offsets folded in (scalar prefetch): a tile contributes iff its lowest
    q position can see its first k position.  Compiled TPU only (the
    interpreter can't lower a dynamic pl.when)."""
    if skip_empty:
        cond = (offs_ref[1] + ik * block_k
                < offs_ref[0] + (iq + 1) * block_q)
        if window:
            cond &= (offs_ref[1] + (ik + 1) * block_k
                     > offs_ref[0] + iq * block_q - window + 1)
        pl.when(cond)(compute)
    else:
        compute()


def _chunk_kernel(offs_ref, q_ref, k_ref, v_ref, mask_ref, m_in_ref, l_in_ref,
                  acc_in_ref, m_out_ref, l_out_ref, acc_out_ref,
                  m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k,
                  nq, nkb, skip_empty, window=0):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        # Seed the scratch from the incoming running state (not neutral
        # values): the chunk continues an online softmax already in flight.
        m_scr[:] = jnp.broadcast_to(
            _row_slice(m_in_ref, iq, block_q, nq)[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(
            _row_slice(l_in_ref, iq, block_q, nq)[:, None], l_scr.shape)
        acc_scr[:] = acc_in_ref[0]

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        mask_blk = (None if mask_ref is None
                    else _row_slice(mask_ref, ik, block_k, nkb))
        # Global positions: the chunk's place in the ring is dynamic
        # (axis_index at runtime), so offsets arrive via scalar prefetch.
        valid = _block_valid(logits.shape, mask_blk, causal=causal,
                             iq=iq, ik=ik, block_q=block_q, block_k=block_k,
                             q_offset=offs_ref[0], k_offset=offs_ref[1],
                             window=window)
        logits = jnp.where(valid, logits, _NEG)

        m_prev = m_scr[:, :1]
        blk_max = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, blk_max)
        p = jnp.exp(logits - m_new) * valid.astype(jnp.float32)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v_ref[0].astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    _chunk_tile_guard(_compute, offs_ref, skip_empty=skip_empty, iq=iq, ik=ik,
                      block_q=block_q, block_k=block_k, window=window)

    @pl.when(ik == nk - 1)
    def _emit():
        if nq == 1:
            m_out_ref[0, 0, :block_q] = m_scr[:, 0]
            l_out_ref[0, 0, :block_q] = l_scr[:, 0]
        else:
            m_out_ref[0, 0, pl.ds(iq * block_q, block_q)] = m_scr[:, 0]
            l_out_ref[0, 0, pl.ds(iq * block_q, block_q)] = l_scr[:, 0]
        acc_out_ref[0] = acc_scr[:]


def flash_attention_chunk(
    q: jax.Array,          # [B, Sq, H, D]
    k: jax.Array,          # [B, Sk, H, D]
    v: jax.Array,          # [B, Sk, H, D]
    kv_mask: jax.Array | None,   # [B, Sk]; nonzero = attend
    m: jax.Array,          # [B, H, Sq] fp32 running max
    l: jax.Array,          # [B, H, Sq] fp32 running sum
    acc: jax.Array,        # [B, H, Sq, D] fp32 running (pre-divide) output
    *,
    q_offset: jax.Array | int,   # global position of q[:, 0] (dynamic ok)
    k_offset: jax.Array | int,   # global position of k[:, 0] (dynamic ok)
    causal: bool = False,
    window: int = 0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fold one K/V chunk into ``(m, l, acc)``; returns the updated state.

    Finalize with ``acc / max(l, eps)`` after the last chunk.  Shapes follow
    ring attention's carry layout; offsets may be traced scalars (ring
    position is only known at runtime).
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    block_q = _pick_block(Sq)
    block_k = _pick_block(Sk)
    scale = 1.0 / float(D) ** 0.5

    qt = _to_bh(q)
    kt, vt = _to_bh(k), _to_bh(v)
    m3 = m.reshape(B * H, 1, Sq)
    l3 = l.reshape(B * H, 1, Sq)
    acct = acc.reshape(B * H, Sq, D)
    offs = jnp.asarray(
        jnp.stack([jnp.asarray(q_offset, jnp.int32),
                   jnp.asarray(k_offset, jnp.int32)]))

    q_spec = pl.BlockSpec((1, block_q, D), lambda bh, iq, ik, s: (bh, iq, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, block_k, D), lambda bh, iq, ik, s: (bh, ik, 0),
                           memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, 1, Sq), lambda bh, iq, ik, s: (bh, 0, 0),
                            memory_space=pltpu.VMEM)
    acc_spec = pl.BlockSpec((1, block_q, D), lambda bh, iq, ik, s: (bh, iq, 0),
                            memory_space=pltpu.VMEM)

    in_specs = [q_spec, kv_spec, kv_spec]
    inputs = [qt, kt, vt]
    kernel = functools.partial(_chunk_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               nq=Sq // block_q, nkb=Sk // block_k,
                               skip_empty=causal and not _interpret(),
                               window=window)
    if kv_mask is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, Sk), lambda bh, iq, ik, s, H=H: (bh // H, 0, 0),
            memory_space=pltpu.VMEM))
        inputs.append(_mask_input(kv_mask))
    else:
        kernel = _insert_none_mask(kernel, pos=4)  # after offs_ref + q/k/v
    in_specs += [row_spec, row_spec, acc_spec]
    inputs += [m3, l3, acct]

    m_o, l_o, acc_o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, Sq // block_q, Sk // block_k),
            in_specs=in_specs,
            out_specs=[row_spec, row_spec, acc_spec],
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANE), jnp.float32),
                pltpu.VMEM((block_q, _LANE), jnp.float32),
                pltpu.VMEM((block_q, D), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B * H, 1, Sq), jnp.float32),
                   jax.ShapeDtypeStruct((B * H, 1, Sq), jnp.float32),
                   jax.ShapeDtypeStruct((B * H, Sq, D), jnp.float32)],
        interpret=_interpret(),
    )(offs, *inputs)
    return (m_o.reshape(B, H, Sq), l_o.reshape(B, H, Sq),
            acc_o.reshape(B, H, Sq, D))


def _chunk_dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, mask_ref, dq_ref, dq_scr, *, scale, causal,
                     block_q, block_k, nq, nkb, skip_empty, window=0):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        _, ds, _, _, k = _bwd_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            iq=iq, ik=ik, nq=nq, nkb=nkb,
            q_offset=offs_ref[0], k_offset=offs_ref[1], window=window)
        dq_scr[:] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _chunk_tile_guard(_compute, offs_ref, skip_empty=skip_empty, iq=iq, ik=ik,
                      block_q=block_q, block_k=block_k, window=window)

    @pl.when(ik == nk - 1)
    def _emit():
        dq_ref[0] = dq_scr[:]


def _chunk_dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, mask_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                      scale, causal, block_q, block_k, nq, nkb, skip_empty,
                      window=0):
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        p, ds, do, q, _ = _bwd_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            iq=iq, ik=ik, nq=nq, nkb=nkb,
            q_offset=offs_ref[0], k_offset=offs_ref[1], window=window)
        dv_scr[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    _chunk_tile_guard(_compute, offs_ref, skip_empty=skip_empty, iq=iq, ik=ik,
                      block_q=block_q, block_k=block_k, window=window)

    @pl.when(iq == nq - 1)
    def _emit():
        dk_ref[0] = dk_scr[:]
        dv_ref[0] = dv_scr[:]


def _chunk_bwd_call(kernel_fn, *, q, k, v, do, lse, delta, kv_mask,
                    q_offset, k_offset, causal, q_major, out_shapes,
                    out_specs_fn, scratch_shapes, window=0):
    """Shared driver for the two chunk backward kernels (ring hops)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    block_q = _pick_block(Sq)
    block_k = _pick_block(Sk)
    scale = 1.0 / float(D) ** 0.5

    qt, kt, vt, dot_ = _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(do)
    lse3 = lse.reshape(B * H, 1, Sq)
    delta3 = delta.reshape(B * H, 1, Sq)
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(k_offset, jnp.int32)])

    # q_major=True: grid (BH, nk, nq), q indexed by the innermost dim.
    q_idx = ((lambda bh, i, j, s: (bh, j, 0)) if q_major
             else (lambda bh, i, j, s: (bh, i, 0)))
    k_idx = ((lambda bh, i, j, s: (bh, i, 0)) if q_major
             else (lambda bh, i, j, s: (bh, j, 0)))
    q_spec = pl.BlockSpec((1, block_q, D), q_idx, memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, block_k, D), k_idx, memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, 1, Sq), lambda bh, i, j, s: (bh, 0, 0),
                            memory_space=pltpu.VMEM)
    in_specs = [q_spec, k_spec, k_spec, q_spec, row_spec, row_spec]
    inputs = [qt, kt, vt, dot_, lse3, delta3]
    kernel = functools.partial(kernel_fn, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               nq=Sq // block_q, nkb=Sk // block_k,
                               skip_empty=causal and not _interpret(),
                               window=window)
    if kv_mask is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, Sk), lambda bh, i, j, s, H=H: (bh // H, 0, 0),
            memory_space=pltpu.VMEM))
        inputs.append(_mask_input(kv_mask))
    else:
        kernel = _insert_none_mask(kernel, pos=7)  # offs + q/k/v/do/lse/delta
    grid = ((B * H, Sk // block_k, Sq // block_q) if q_major
            else (B * H, Sq // block_q, Sk // block_k))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs_fn(block_q, block_k, D),
            scratch_shapes=scratch_shapes(block_q, block_k, D)),
        out_shape=out_shapes,
        interpret=_interpret(),
    )(offs, *inputs)


def flash_attention_chunk_dq(q, k, v, kv_mask, do, lse, delta, *,
                             q_offset, k_offset, causal=False, window=0):
    """dq partial for local q rows against ONE K/V chunk (fp32, [B,H,Sq,D] —
    the ring's accumulator layout; sum over chunks outside)."""
    B, Sq, H, D = q.shape
    out = _chunk_bwd_call(
        _chunk_dq_kernel, q=q, k=k, v=v, do=do, lse=lse, delta=delta,
        kv_mask=kv_mask, q_offset=q_offset, k_offset=k_offset, causal=causal,
        window=window, q_major=False,
        out_shapes=jax.ShapeDtypeStruct((B * H, Sq, D), jnp.float32),
        out_specs_fn=lambda bq, bk, D_: pl.BlockSpec(
            (1, bq, D_), lambda bh, i, j, s: (bh, i, 0),
            memory_space=pltpu.VMEM),
        scratch_shapes=lambda bq, bk, D_: [pltpu.VMEM((bq, D_), jnp.float32)])
    return out.reshape(B, H, Sq, D)


def flash_attention_chunk_dkv(q, k, v, kv_mask, do, lse, delta, *,
                              q_offset, k_offset, causal=False, window=0):
    """(dk, dv) partials for ONE K/V chunk from the local q rows (fp32,
    [B,H,Sk,D] — travels the ring with the chunk; sum over devices)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    dk, dv = _chunk_bwd_call(
        _chunk_dkv_kernel, q=q, k=k, v=v, do=do, lse=lse, delta=delta,
        kv_mask=kv_mask, q_offset=q_offset, k_offset=k_offset, causal=causal,
        window=window, q_major=True,
        out_shapes=[jax.ShapeDtypeStruct((B * H, Sk, D), jnp.float32)] * 2,
        out_specs_fn=lambda bq, bk, D_: [pl.BlockSpec(
            (1, bk, D_), lambda bh, i, j, s: (bh, i, 0),
            memory_space=pltpu.VMEM)] * 2,
        scratch_shapes=lambda bq, bk, D_: [
            pltpu.VMEM((bk, D_), jnp.float32)] * 2)
    return dk.reshape(B, H, Sk, D), dv.reshape(B, H, Sk, D)


def _dense_reference(q, k, v, kv_mask, *, causal: bool, window: int = 0):
    """fp32 dense attention — the fallback/rematerialization target.

    Delegates to the xla backend of :func:`..attention.dot_product_attention`
    (one definition of the masked-softmax semantics, not two to keep in sync).
    """
    from ..attention import dot_product_attention
    return dot_product_attention(q, k, v, kv_mask=kv_mask, causal=causal,
                                 window=window, backend="xla")


def _flash_vjp(forward, backward):
    """The differentiable kernel call over one pair of forward / backward
    implementations (``causal`` and ``window`` are static)."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
    def _flash(q, k, v, kv_mask, causal, window):
        out, _ = forward(q, k, v, kv_mask, causal=causal, window=window)
        return out

    def _flash_fwd(q, k, v, kv_mask, causal, window):
        out, lse = forward(q, k, v, kv_mask, causal=causal, window=window)
        return out, (q, k, v, kv_mask, out, lse)

    def _flash_bwd(causal, window, residuals, g):
        q, k, v, kv_mask, o, lse = residuals
        dq, dk, dv = backward(q, k, v, kv_mask, o, lse, g, causal=causal,
                              window=window)
        return dq, dk, dv, None

    _flash.defvjp(_flash_fwd, _flash_bwd)
    return _flash


_flash = _flash_vjp(_flash_forward, _flash_backward)

# The same kernels behind a jit boundary, for the body of the shard_map in
# :func:`_flash_over_batch_axes` ONLY: a 24-layer step calls one shape 72
# times, and the boundary has each kernel body traced and lowered once a
# program instead (2.4 s of a 406M step's set-up, the compiled program
# unchanged).  Outside a shard_map the boundary is NOT neutral — it keeps the
# layout transposes around the kernel from fusing with their neighbours (96
# more copies in the one-chip 406M step) — so :func:`flash_attention` calls
# ``_flash`` there.
_flash_per_device = _flash_vjp(
    jax.jit(_flash_forward, static_argnames=("causal", "window")),
    jax.jit(_flash_backward, static_argnames=("causal", "window")))


_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "flash_attention_mesh", default=None)


@contextlib.contextmanager
def ambient_mesh(mesh):
    """Name the ``Mesh`` a program is being traced for, so that
    :func:`flash_attention` can keep its kernel on several chips.

    The sync step builders (``parallel/sync.py``) trace their bodies under
    this.  It is a variable of this module and not ``jax.set_mesh``: the
    explicit ``shard_map(mesh=...)`` below wants the concrete ``Mesh``, and
    JAX's own ambient mesh is part of how everything else in the program is
    traced and keyed, which has to stay as it is for one-device programs."""
    token = _MESH.set(mesh)
    try:
        yield
    finally:
        _MESH.reset(token)


def _batch_axes(batch: int):
    """``(mesh, axes)`` when the kernel call can be mapped over the ambient
    mesh's batch axes, else None: a mesh is ambient, it spans several
    devices, every axis of size > 1 is one that ``batch_sharding`` puts on
    dimension 0, their product divides ``batch``, and the trace is not
    inside a shard_map already (there the kernel is per device as it is)."""
    mesh = _MESH.get()
    if mesh is None or mesh.size == 1 or _inside_shard_map():
        return None
    from ...parallel.mesh import batch_sharding  # parallel/ imports this
    dim0 = batch_sharding(mesh).spec[0]
    axes = dim0 if isinstance(dim0, tuple) else (dim0,)
    wide = {a for a, n in mesh.shape.items() if n > 1}
    if not wide <= set(axes) or batch % mesh.size:
        return None
    return mesh, axes


def _flash_over_batch_axes(mesh, axes, q, k, v, kv_mask, causal, window):
    """``_flash`` on each device's rows: GSPMD cannot partition a Mosaic
    call, so the call site says how — dimension 0 over the batch axes,
    nothing to communicate.  The custom VJP sits inside the map, so the
    backward kernels are per device too; everything around the call
    (projections, the gradient all-reduce) stays GSPMD's to place."""
    rows = P(axes)
    return jax.shard_map(
        lambda q, k, v, kv_mask: _flash_per_device(
            q, k, v, kv_mask, causal, window),
        mesh=mesh, in_specs=(rows, rows, rows, None if kv_mask is None
                             else rows),
        out_specs=rows, check_vma=False)(q, k, v, kv_mask)


def flash_attention(
    q: jax.Array,                        # [B, S, H, D]
    k: jax.Array,
    v: jax.Array,
    kv_mask: jax.Array | None = None,    # [B, S]; nonzero = attend
    *,
    causal: bool = False,
    window: int = 0,
) -> jax.Array:
    """Blockwise flash attention; differentiable (blockwise pallas VJP).

    ``window`` > 0 (requires ``causal``) restricts each query to its
    ``window`` most recent keys (sliding-window attention); whole blocks
    outside the band are skipped, so compiled cost is O(S * window)."""
    if window and not causal:
        raise ValueError("window > 0 requires causal=True")
    if q.shape[1] % 8 or not _layout_ok(q.shape[1]):
        # No Mosaic-tileable block decomposition — dense is the better
        # program (and the only compilable one: multi-block rows need
        # 128-aligned block offsets for the mask/lse slices).
        return _dense_reference(q, k, v, kv_mask, causal=causal,
                                window=window)
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        # Interpreter mode is a CPU-CI affordance; on other accelerators it
        # would silently run orders of magnitude slow — dense XLA is the
        # right program there.
        return _dense_reference(q, k, v, kv_mask, causal=causal,
                                window=window)
    mapped = _batch_axes(q.shape[0])
    if mapped is not None:
        return _flash_over_batch_axes(*mapped, q, k, v, kv_mask, causal,
                                      window)
    if _gspmd_hazard():
        # Multi-chip jit outside shard_map, and no mesh whose batch axes the
        # call could be mapped over (none ambient, or one with a model, seq,
        # pipe or expert axis): GSPMD cannot partition the Mosaic call —
        # dense XLA partitions fine.  (The ring path wraps its chunk kernels
        # in shard_map and keeps pallas on multi-chip.)
        return _dense_reference(q, k, v, kv_mask, causal=causal,
                                window=window)
    return _flash(q, k, v, kv_mask, causal, window)
