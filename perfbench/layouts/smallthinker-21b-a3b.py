"""The leaves of a decoder whose layers are full or sliding-window attention
by ``layer_kinds`` (the same leaves either way) around routed experts and
nothing else, as ``models/gpt.py`` lays them out for ``head_size`` and
``num_experts`` under ``router_score="softmax"``.  No JAX.

Every layer: the two block norms (``ln_attn``: the published
``input_layernorm``, which the attention AND the router read;
``ln_mlp``: ``post_attention_layernorm``, which the experts read);
grouped-query projections at a head size of its own (``q_proj`` [hidden,
heads, head], ``kv_proj`` [hidden, 2, kv heads, head], with the program's
biases, zero under the configuration's ``bias_std`` 0); the output
projection; the router's kernel (drawn like any kernel, normal /
sqrt(hidden): a token's 64 logits are then independent unit normals over a
unit-rms normed stream, so over many tokens every expert gets its share);
the experts' kernels stacked on a leading axis.  No selection bias (a
softmax-scored router has none), no shared expert, no dense layer, no norm a
head.

A layer's kind is ``"sparse.<mixer>"`` (``sparse.full_attention``,
``sparse.sliding_attention``): the mixer changes no leaf, and the reference
reads it off the name.
"""

from __future__ import annotations

SPARSE = "sparse"


def kinds(model: dict) -> list[str]:
    if model["first_dense_layers"] or model["num_shared_experts"]:
        raise ValueError("this layout has routed experts in every layer "
                         "and no shared one")
    return [f"{SPARSE}.{mixer}" for mixer in model["layer_kinds"]]


def layer(model: dict, kind: str) -> dict:
    if kind.split(".")[0] != SPARSE:
        raise ValueError(f"unknown kind of layer {kind!r}")
    h, heads, kv = model["hidden_size"], model["num_heads"], model["kv_heads"]
    d = model["head_size"]
    experts, width = model["num_experts"], model["expert_intermediate_size"]
    return {
        "ln_attn/scale": (h,), "ln_mlp/scale": (h,),
        "q_proj/kernel": (h, heads, d), "q_proj/bias": (heads, d),
        "kv_proj/kernel": (h, 2, kv, d), "kv_proj/bias": (2, kv, d),
        "out/kernel": {"shape": (heads, d, h), "fan_in": heads * d},
        "out/bias": (h,),
        "router/kernel": (h, experts),
        "experts_gate": {"shape": (experts, h, width), "fan_in": h},
        "experts_up": {"shape": (experts, h, width), "fan_in": h},
        "experts_down": {"shape": (experts, width, h), "fan_in": width}}


def top(model: dict) -> dict:
    h, vocab = model["hidden_size"], model["vocab_size"]
    return {"word_emb/embedding": (vocab, h), "ln_final/scale": (h,),
            "lm_head/kernel": (h, vocab), "lm_head/bias": (vocab,)}
