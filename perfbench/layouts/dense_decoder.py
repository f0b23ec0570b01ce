"""The layout a configuration gets when its file names none: a decoder whose
layers are all alike and carry the leaves of ``models/gpt.py``'s dense block
(the harness checks that the program's tree still has them).

A layout is a file of three functions of the configuration's ``model``:
``kinds`` (the kind of each layer, a list as long as ``num_layers``),
``layer`` (the leaves of a layer of one kind) and ``top`` (the leaves outside
the layers).  A leaf is ``name -> shape``, and is drawn by
``perfbench.weights.leaf``'s rules; one that those do not fit is
``name -> {"shape": shape, <how it is drawn>}`` (``weights.leaf`` lists the
ways).  No JAX.
"""

from __future__ import annotations

BLOCK = "block"


def kinds(model: dict) -> list[str]:
    return [BLOCK] * model["num_layers"]


def layer(model: dict, kind: str = BLOCK) -> dict:
    h, heads = model["hidden_size"], model["num_heads"]
    kv = model.get("kv_heads") or heads
    d, inter = h // heads, model["intermediate_size"]
    out = {}
    norm_bias = model["norm"] == "layernorm"
    for ln in ("ln_attn", "ln_mlp"):
        out[f"{ln}/scale"] = (h,)
        if norm_bias:
            out[f"{ln}/bias"] = (h,)
    if kv == heads:
        out["qkv/kernel"], out["qkv/bias"] = (h, 3, heads, d), (3, heads, d)
    else:
        out["q_proj/kernel"], out["q_proj/bias"] = (h, heads, d), (heads, d)
        out["kv_proj/kernel"] = (h, 2, kv, d)
        out["kv_proj/bias"] = (2, kv, d)
    # The one kernel here that contracts over two axes (heads x head size).
    out["out/kernel"] = {"shape": (heads, d, h), "fan_in": heads * d}
    out["out/bias"] = (h,)
    out["mlp_in/kernel"], out["mlp_out/kernel"] = (h, inter), (inter, h)
    if model["activation"] == "swiglu":
        out["mlp_gate/kernel"] = (h, inter)
    else:
        out["mlp_in/bias"], out["mlp_out/bias"] = (inter,), (h,)
    return out


def top(model: dict) -> dict:
    h, vocab = model["hidden_size"], model["vocab_size"]
    out = {"word_emb/embedding": (vocab, h)}
    if model["pos_encoding"] != "rope":
        out["pos_emb/embedding"] = (model["max_position"], h)
    out["ln_final/scale"] = (h,)
    if model["norm"] == "layernorm":
        out["ln_final/bias"] = (h,)
    out["lm_head/kernel"], out["lm_head/bias"] = (h, vocab), (vocab,)
    return out
