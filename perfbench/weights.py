"""Weights from ``--seed``, by leaf name: the benchmark's input, made by the
benchmark.  The harness fills the program's parameter tree from here and the
plain reference asks for the same leaves by the same names, so the two sides
hold the same numbers without either taking anything the other has made.

Which leaves a configuration has, layer by layer, is its layout's to say
(``perfbench/layouts/``; the configuration's file names it under ``layout``,
and gets the dense decoder's where it names none).  A leaf's values depend
on ``(seed, layer index, name within the layer)`` and on nothing else.
Kernels are normal / sqrt(fan_in), embeddings and biases normal x a scale
from the configuration's ``init``, norm scales 1 and norm biases 0.  Values
are drawn in float32 and rounded to the type the parameters are held in.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from perfbench import spec
from perfbench.layouts import dense_decoder

DENSE_LAYOUT = "perfbench/layouts/dense_decoder.py"
RULES = {"fan_in", "constant", "uniform"}


def layout_file(cfg: dict) -> str:
    return cfg.get("layout", DENSE_LAYOUT)


def layout(cfg: dict):
    """The file that says which leaves a configuration has: the one its
    ``layout`` key names, or the dense decoder's."""
    return spec.named_module(cfg, "layout") if "layout" in cfg \
        else dense_decoder


def leaf(key, name: str, described, init: dict, dtype):
    """One leaf's values.  By default: ``ln_*`` scales 1 and biases 0, a
    ``kernel`` normal / sqrt(its first axis), an ``embedding`` and anything
    else (a bias) normal x the configuration's ``init`` scale.  A layout
    that gives the leaf as a dictionary overrules that with one of
    ``fan_in`` (a kernel normal / sqrt(fan_in)), ``constant`` (every entry
    that value) or ``uniform`` ([low, high))."""
    shape, rule = described, {}
    if isinstance(described, dict):
        rule = dict(described)
        shape = rule.pop("shape")
        if len(rule) > 1 or set(rule) - RULES:
            raise ValueError(f"leaf {name!r}: one of {sorted(RULES)} says "
                             f"how it is drawn, not {sorted(rule)}")
    part, _, what = name.rpartition("/")
    if "constant" in rule:
        return jnp.full(shape, rule["constant"], dtype)
    if not rule and part.startswith("ln_"):
        return (jnp.ones if what == "scale" else jnp.zeros)(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if "uniform" in rule:
        low, high = rule["uniform"]
        return jax.random.uniform(k, shape, jnp.float32, low,
                                  high).astype(dtype)
    x = jax.random.normal(k, shape, jnp.float32)
    if "fan_in" in rule or what == "kernel":
        x = x * (rule.get("fan_in", shape[0]) ** -0.5)
    elif what == "embedding":
        x = x * init["embedding_std"]
    else:
        x = x * init["bias_std"]
    return x.astype(dtype)


def layer_leaves(seed_key, index, model: dict, init: dict, dtype,
                 leaves: dict | None = None) -> dict:
    """One layer's leaves; ``index`` may be traced, so one compiled program
    makes every layer that has these ``leaves`` (a layout's
    ``layer(model, kind)``; the dense decoder's where none are given)."""
    key = jax.random.fold_in(seed_key, index + 1)
    if leaves is None:
        leaves = dense_decoder.layer(model)
    return {n: leaf(key, n, s, init, dtype) for n, s in leaves.items()}


def top_leaves(seed_key, model: dict, init: dict, dtype,
               leaves: dict | None = None) -> dict:
    key = jax.random.fold_in(seed_key, 0)
    if leaves is None:
        leaves = dense_decoder.top(model)
    return {n: leaf(key, n, s, init, dtype) for n, s in leaves.items()}


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
    """The compiled programs (one for each kind of layer, one for the rest)
    that make a configuration's leaves on the device, built once per
    process.  The seed and the layer's index are arguments of a program, so
    another seed, or another layer of the same kind, compiles nothing."""

    def __init__(self, cfg: dict, dtype=None, sharding=None):
        model, init = cfg["model"], cfg["init"]
        lay = layout(cfg)
        self.kinds = list(lay.kinds(model))
        self.num_layers = model["num_layers"]
        if len(self.kinds) != self.num_layers:
            raise ValueError(
                f"{layout_file(cfg)} gives {len(self.kinds)} layers a kind, "
                f"the configuration has {self.num_layers}")
        dtype = jnp.dtype(dtype or cfg["param_dtype"])
        kw = {} if sharding is None else {"out_shardings": sharding}

        def layer_program(leaves):
            return jax.jit(lambda s, i: layer_leaves(
                base_key_from(s), i, model, init, dtype, leaves), **kw)

        self._layer = {kind: layer_program(lay.layer(model, kind))
                       for kind in dict.fromkeys(self.kinds)}
        top = lay.top(model)
        self.top = jax.jit(lambda s: top_leaves(
            base_key_from(s), model, init, dtype, top), **kw)

    def layer(self, halves, i: int) -> dict:
        """Layer ``i``'s leaves, from the program of its kind."""
        return self._layer[self.kinds[i]](halves, jnp.int32(i))


def program_tree(seed: int, maker: Maker) -> dict:
    """The whole tree in the program's nesting, made on the device layer by
    layer, in the type the parameters are held in."""
    make_top, make_layer = maker.top, maker.layer
    halves = seed_halves(seed)
    flat = dict(make_top(halves))
    for i in range(maker.num_layers):
        for n, v in make_layer(halves, i).items():
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
