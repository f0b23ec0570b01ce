"""Weight-only int8 quantization — HBM bandwidth relief for inference.

TPU decode is memory-bound: every generated token re-reads the full weight
set, so at bf16 the decode rate is capped by HBM bytes/step.  Storing weights
as **per-channel symmetric int8** halves those bytes; the dequantize
(``q * scale``) runs inside the jitted step, where XLA fuses it into the
consuming matmul — weights stay int8 in HBM, compute stays bf16 on the MXU.
(The reference had no quantization story at all; its inference was the same
float graph as training, reference ``distributed.py:78-84``.)

Representation: :func:`quantize_tree` maps each eligible weight leaf to a
``{"q": int8, "s": float32}`` dict (scale per output channel and per small
fused-projection axis — see :func:`quantize_leaf`); small or integer leaves
pass through unchanged.  :func:`dequantize_tree` restores a compute-dtype
tree with identical structure to the original params.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

_QKEYS = frozenset({"q", "s"})


def resolve_kv_dtype(name: str):
    """KV-cache dtype from its CLI spelling — ONE mapping shared by the
    decode entry points (``models/gpt._decode_setup``) and the serving
    engine, so "float8" always means ``float8_e4m3fn`` everywhere.
    ``""`` means "the compute dtype" and maps to None (caller default)."""
    table = {"": None, "bfloat16": jnp.bfloat16,
             "float8": jnp.float8_e4m3fn}
    if name not in table:
        raise ValueError(
            f"kv_dtype must be '', 'bfloat16' or 'float8', got {name!r}")
    return table[name]


def validate_quantize(name: str) -> str:
    """Weight-storage mode from its CLI spelling — ONE validation shared
    by the decode entry points, the speculative paths, and the serving
    engine (they must reject the same strings the same way)."""
    if name not in ("", "int8"):
        raise ValueError(f"quantize must be '' or 'int8', got {name!r}")
    return name


def prepare_inference_tree(params: Any, quantize: str,
                           consume: bool = False) -> Any:
    """Host param tree -> the tree an inference path should CARRY across
    dispatches: per-channel int8 + scales under ``quantize="int8"``
    (half the HBM weight bytes), the original tree otherwise.  Pair with
    :func:`load_inference_tree` inside the jitted consumer.  ``consume``:
    as :func:`quantize_tree` takes it."""
    validate_quantize(quantize)
    if quantize != "int8":
        return params
    return quantize_tree(params, consume=consume)


def load_inference_tree(tree: Any, quantize: str, dtype) -> Any:
    """Inverse of :func:`prepare_inference_tree`, called INSIDE the jitted
    step so XLA fuses the dequant multiply into the consuming matmuls —
    the shared weight-loading recipe of ``generate_cached``, the
    speculative decoders, and the serving engine."""
    if quantize == "int8":
        return dequantize_tree(tree, dtype)
    return tree


def _is_qleaf(x: Any) -> bool:
    return isinstance(x, dict) and frozenset(x.keys()) == _QKEYS


@jax.jit
def quantize_leaf(w: jax.Array) -> dict:
    """Per-channel symmetric int8: ``w ≈ q * s`` with |q| <= 127.  One
    compiled program a shape, so that a leaf of hundreds of megabytes (an
    embedding, a layer's stacked expert kernels) is read once and written
    as int8 with no float32 copy of it in between.

    Scales vary along the LAST axis plus any small inner axes (size <= 4,
    e.g. the fused-projection axis of GPT's qkv ``[hidden, 3, H, D]`` —
    Q/K/V get distinct scales instead of sharing one); all other axes —
    the contraction dims of the kernels here, including both contraction
    axes of ``DenseGeneral(axis=(-2, -1))``'s ``[H, D, out]`` kernels —
    are reduced, keeping the scale tensor tiny next to the int8 payload.
    Dequant is exact elementwise regardless of grouping, so granularity
    trades only scale bytes for fidelity.
    """
    w32 = w.astype(jnp.float32)
    reduce_axes = tuple(i for i in range(w.ndim - 1)
                        if not (0 < i and w.shape[i] <= 4))
    amax = jnp.max(jnp.abs(w32), axis=reduce_axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "s": scale}


def quantize_tree(params: Any, *, min_size: int = 4096,
                  consume: bool = False) -> Any:
    """Quantize every float leaf with >= ``min_size`` elements.

    Small leaves (biases, LayerNorm gains) carry negligible bytes and the
    most precision sensitivity — they stay in their original dtype.

    ``consume``: a device leaf is DELETED once its int8 form is made, so
    the float tree and the int8 tree never lie whole side by side in the
    device's memory (a float tree of more than two thirds of it could not
    be quantized there otherwise).  The caller's tree is then dead: its
    quantized leaves raise on any further use.
    """
    def leaf(w):
        if (not hasattr(w, "dtype")
                or not jnp.issubdtype(w.dtype, jnp.floating)
                or w.ndim < 2 or w.size < min_size):
            return w
        q = quantize_leaf(w)
        if consume and isinstance(w, jax.Array):
            jax.block_until_ready(q)
            w.delete()
        return q
    return jax.tree.map(leaf, params)


def dequantize_tree(qparams: Any, dtype=jnp.bfloat16) -> Any:
    """Rebuild a compute-dtype tree; called INSIDE the jitted consumer so
    XLA fuses the multiply into the matmul and HBM holds only int8."""
    def leaf(x):
        if _is_qleaf(x):
            return (x["q"].astype(jnp.float32) * x["s"]).astype(dtype)
        return x
    return jax.tree.map(leaf, qparams, is_leaf=_is_qleaf)


def quantized_bytes(qparams: Any) -> int:
    """Total parameter bytes as stored (int8 + scales + passthrough)."""
    total = 0
    for leaf in jax.tree.leaves(qparams):
        total += leaf.size * leaf.dtype.itemsize
    return total
