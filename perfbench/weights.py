"""Weights from ``--seed``, by leaf name: the benchmark's input, made by the
benchmark.  The harness fills the program's parameter tree from here and the
plain reference asks for the same leaves by the same names, so the two sides
hold the same numbers without either taking anything the other has made.

A leaf's values depend on ``(seed, layer index, name within the layer)``.
Kernels are normal / sqrt(fan_in), embeddings and biases normal x a scale
from the configuration's ``init``, norm scales 1 and norm biases 0.  Values
are drawn in float32 and rounded to the type the parameters are held in.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def shapes(model: dict) -> tuple[dict, dict]:
    """``(per-layer leaves, top-level leaves)``: name -> shape, as
    ``models/gpt.py`` lays them out (the harness checks that it still does)."""
    h, heads = model["hidden_size"], model["num_heads"]
    kv = model.get("kv_heads") or heads
    d, inter, vocab = h // heads, model["intermediate_size"], model["vocab_size"]
    layer, top = {}, {}
    norm_bias = model["norm"] == "layernorm"
    for ln in ("ln_attn", "ln_mlp"):
        layer[f"{ln}/scale"] = (h,)
        if norm_bias:
            layer[f"{ln}/bias"] = (h,)
    if kv == heads:
        layer["qkv/kernel"], layer["qkv/bias"] = (h, 3, heads, d), (3, heads, d)
    else:
        layer["q_proj/kernel"], layer["q_proj/bias"] = (h, heads, d), (heads, d)
        layer["kv_proj/kernel"] = (h, 2, kv, d)
        layer["kv_proj/bias"] = (2, kv, d)
    layer["out/kernel"], layer["out/bias"] = (heads, d, h), (h,)
    layer["mlp_in/kernel"], layer["mlp_out/kernel"] = (h, inter), (inter, h)
    if model["activation"] == "swiglu":
        layer["mlp_gate/kernel"] = (h, inter)
    else:
        layer["mlp_in/bias"], layer["mlp_out/bias"] = (inter,), (h,)
    top["word_emb/embedding"] = (vocab, h)
    if model["pos_encoding"] != "rope":
        top["pos_emb/embedding"] = (model["max_position"], h)
    top["ln_final/scale"] = (h,)
    if norm_bias:
        top["ln_final/bias"] = (h,)
    top["lm_head/kernel"], top["lm_head/bias"] = (h, vocab), (vocab,)
    return layer, top




def leaf(key, name: str, shape, init: dict, dtype):
    part, what = name.rsplit("/", 1)
    if part.startswith("ln_"):
        return (jnp.ones if what == "scale" else jnp.zeros)(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    x = jax.random.normal(k, shape, jnp.float32)
    if what == "kernel":
        fan_in = shape[0] * shape[1] if part == "out" else shape[0]
        x = x * (fan_in ** -0.5)
    elif what == "embedding":
        x = x * init["embedding_std"]
    else:
        x = x * init["bias_std"]
    return x.astype(dtype)


def layer_leaves(seed_key, index, model: dict, init: dict, dtype) -> dict:
    """One layer's leaves; ``index`` may be traced, so one compiled program
    makes every layer."""
    key = jax.random.fold_in(seed_key, index + 1)
    return {n: leaf(key, n, s, init, dtype)
            for n, s in shapes(model)[0].items()}


def top_leaves(seed_key, model: dict, init: dict, dtype) -> dict:
    key = jax.random.fold_in(seed_key, 0)
    return {n: leaf(key, n, s, init, dtype)
            for n, s in shapes(model)[1].items()}


def nest(flat: dict) -> dict:
    out: dict = {}
    for name, value in flat.items():
        node = out
        *parents, last = name.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = value
    return out


class Maker:
    """The two compiled programs (one layer, the rest) that make a
    configuration's leaves on the device, built once per process."""

    def __init__(self, cfg: dict, dtype=None, sharding=None):
        model, init = cfg["model"], cfg["init"]
        self.num_layers = model["num_layers"]
        dtype = jnp.dtype(dtype or cfg["param_dtype"])
        kw = {} if sharding is None else {"out_shardings": sharding}
        self.layer = jax.jit(
            lambda s, i: layer_leaves(base_key_from(s), i, model, init,
                                      dtype), **kw)
        self.top = jax.jit(
            lambda s: top_leaves(base_key_from(s), model, init, dtype), **kw)


def program_tree(seed: int, maker: Maker) -> dict:
    """The whole tree in the program's nesting, made on the device layer by
    layer, in the type the parameters are held in."""
    make_top, make_layer = maker.top, maker.layer
    halves = seed_halves(seed)
    flat = dict(make_top(halves))
    for i in range(maker.num_layers):
        for n, v in make_layer(halves, jnp.int32(i)).items():
            flat[f"layer{i}/{n}"] = v
    return nest(flat)


def seed_halves(seed: int):
    seed = int(seed)
    return jnp.asarray([seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF],
                       jnp.int32)


def base_key_from(halves):
    """The seed's key from the traced pair ``seed_halves`` gives (``--seed``
    may pass 2**31, so it is folded in as two 31-bit halves); the seed
    is an argument of the compiled program and not a constant in it (a new
    seed must not compile anew)."""
    return jax.random.fold_in(jax.random.key(halves[0]), halves[1])
