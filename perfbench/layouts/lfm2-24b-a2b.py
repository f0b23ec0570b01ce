"""The leaves of a decoder whose layers are gated short convolutions or
grouped-query attention by ``layer_kinds``, around a dense MLP in the first
``first_dense_layers`` layers and routed experts (none shared) in the rest,
as ``models/gpt.py`` lays them out for ``short_conv_kernel_dim``,
``qk_head_norm`` and ``num_experts``.  No JAX.

Every layer: the two block norms (``ln_attn``: the published
``operator_norm``; ``ln_mlp``: ``ffn_norm``).  A ``short_conv`` mixer: the in
projection to the three thirds ``[B | C | X]`` (``in_proj`` [hidden, 3 x
hidden], no bias), the depthwise taps (``conv_taps`` [taps, hidden], oldest
first, normal / sqrt(taps), no bias), the out projection (``out`` [hidden,
hidden], no bias).  A ``full_attention`` mixer: grouped-query projections
(``q_proj`` [hidden, heads, head], ``kv_proj`` [hidden, 2, kv heads, head],
with the program's biases, zero under the configuration's ``bias_std`` 0),
one norm scale of a head's size for q and one for k, the out projection.  A
``dense`` layer then has the gated MLP's three kernels; a ``sparse`` one the
router's kernel (drawn like any kernel, normal / sqrt(hidden): a token's 64
logits are then independent unit normals, so over many tokens every expert
gets its share), the selection bias (zero) and the experts' kernels stacked
on a leading axis.

A layer's kind is ``"<mlp>.<mixer>"`` (``dense.short_conv``,
``sparse.full_attention``, ``sparse.short_conv``); the reference reads both
parts off the name.
"""

from __future__ import annotations

DENSE, SPARSE = "dense", "sparse"
CONV, ATTENTION = "short_conv", "full_attention"


def kinds(model: dict) -> list[str]:
    first = model["first_dense_layers"]
    return [f"{DENSE if i < first else SPARSE}.{mixer}"
            for i, mixer in enumerate(model["layer_kinds"])]


def layer(model: dict, kind: str) -> dict:
    mlp, mixer = kind.split(".")
    h = model["hidden_size"]
    out = {"ln_attn/scale": (h,), "ln_mlp/scale": (h,)}
    if mixer == CONV:
        taps = model["short_conv_kernel_dim"]
        out.update({
            "in_proj/kernel": (h, 3 * h),
            "conv_taps": {"shape": (taps, h), "fan_in": taps},
            "out/kernel": (h, h)})
    elif mixer == ATTENTION:
        heads, kv = model["num_heads"], model["kv_heads"]
        d = h // heads
        out.update({
            "q_proj/kernel": (h, heads, d), "q_proj/bias": (heads, d),
            "kv_proj/kernel": (h, 2, kv, d), "kv_proj/bias": (2, kv, d),
            "q_norm/scale": {"shape": (d,), "constant": 1.0},
            "k_norm/scale": {"shape": (d,), "constant": 1.0},
            "out/kernel": {"shape": (heads, d, h), "fan_in": heads * d},
            "out/bias": (h,)})
    else:
        raise ValueError(f"unknown token mixer in {kind!r}")
    if mlp == DENSE:
        inter = model["intermediate_size"]
        out.update({"mlp_in/kernel": (h, inter),
                    "mlp_gate/kernel": (h, inter),
                    "mlp_out/kernel": (inter, h)})
        return out
    if mlp != SPARSE:
        raise ValueError(f"unknown kind of layer {kind!r}")
    experts, width = model["num_experts"], model["expert_intermediate_size"]
    out.update({
        "router/kernel": (h, experts),
        "router_bias": {"shape": (experts,), "constant": 0.0},
        "experts_gate": {"shape": (experts, h, width), "fan_in": h},
        "experts_up": {"shape": (experts, h, width), "fan_in": h},
        "experts_down": {"shape": (experts, width, h), "fan_in": width}})
    return out


def top(model: dict) -> dict:
    h, vocab = model["hidden_size"], model["vocab_size"]
    return {"word_emb/embedding": (vocab, h), "ln_final/scale": (h,),
            "lm_head/kernel": (h, vocab), "lm_head/bias": (vocab,)}
