"""The leaves of a decoder whose layers are sliding-window or full attention
by ``layer_kinds`` (the same leaves either way) around a dense MLP in the
first ``first_dense_layers`` layers and routed experts beside a shared one in
the rest, as ``models/gpt.py`` lays them out for ``head_size``,
``qk_head_norm``, ``attn_output_gate``, ``norm_placement="sandwich"`` and
``num_experts``.  No JAX.

Every layer: the four block norms (``ln_attn``, ``ln_attn_post``, ``ln_mlp``,
``ln_mlp_post``); grouped-query projections at a head size of its own
(``q_proj`` [hidden, heads, head], ``kv_proj`` [hidden, 2, kv heads, head],
with the program's biases, zero under the configuration's ``bias_std`` 0);
the output gate's projection (``gate_proj`` [hidden, heads, head], no bias);
one norm scale of a head's size for q and one for k; the output projection.
A ``dense`` layer then has the gated MLP's three kernels; a ``sparse`` one the
router's kernel (drawn like any kernel, normal / sqrt(hidden): a token's 128
logits are then independent unit normals, so over many tokens every expert
gets its share), the selection bias (zero), the experts' kernels stacked on a
leading axis, and the shared expert's three.

A layer's kind is ``"<mlp>.<mixer>"`` (``dense.sliding_attention``,
``sparse.full_attention``, ...): the mixer changes no leaf, and the
reference reads it off the name.
"""

from __future__ import annotations

DENSE, SPARSE = "dense", "sparse"


def kinds(model: dict) -> list[str]:
    first = model["first_dense_layers"]
    return [f"{DENSE if i < first else SPARSE}.{mixer}"
            for i, mixer in enumerate(model["layer_kinds"])]


def layer(model: dict, kind: str) -> dict:
    mlp = kind.split(".")[0]
    h, heads, kv = model["hidden_size"], model["num_heads"], model["kv_heads"]
    d = model["head_size"]
    out = {f"{ln}/scale": (h,) for ln in ("ln_attn", "ln_attn_post",
                                          "ln_mlp", "ln_mlp_post")}
    out.update({
        "q_proj/kernel": (h, heads, d), "q_proj/bias": (heads, d),
        "kv_proj/kernel": (h, 2, kv, d), "kv_proj/bias": (2, kv, d),
        "gate_proj/kernel": (h, heads, d),
        "q_norm/scale": {"shape": (d,), "constant": 1.0},
        "k_norm/scale": {"shape": (d,), "constant": 1.0},
        "out/kernel": {"shape": (heads, d, h), "fan_in": heads * d},
        "out/bias": (h,)})
    if mlp == DENSE:
        inter = model["intermediate_size"]
        out.update({"mlp_in/kernel": (h, inter),
                    "mlp_gate/kernel": (h, inter),
                    "mlp_out/kernel": (inter, h)})
        return out
    if mlp != SPARSE:
        raise ValueError(f"unknown kind of layer {kind!r}")
    experts, width = model["num_experts"], model["expert_intermediate_size"]
    shared = width * model["num_shared_experts"]
    out.update({
        "router/kernel": (h, experts),
        "router_bias": {"shape": (experts,), "constant": 0.0},
        "experts_gate": {"shape": (experts, h, width), "fan_in": h},
        "experts_up": {"shape": (experts, h, width), "fan_in": h},
        "experts_down": {"shape": (experts, width, h), "fan_in": width},
        "shared_in/kernel": (h, shared), "shared_gate/kernel": (h, shared),
        "shared_out/kernel": (shared, h)})
    return out


def top(model: dict) -> dict:
    h, vocab = model["hidden_size"], model["vocab_size"]
    return {"word_emb/embedding": (vocab, h), "ln_final/scale": (h,),
            "lm_head/kernel": (h, vocab), "lm_head/bias": (vocab,)}
