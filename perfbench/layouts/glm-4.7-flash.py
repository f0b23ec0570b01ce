"""The leaves of a decoder of latent-attention layers whose MLP is dense in
the first ``first_dense_layers`` layers and routed experts beside a shared
one in the rest, as ``models/gpt.py`` lays them out for ``latent_kv_rank``
and ``num_experts``.  No JAX.

Every layer: the two block norms; the query's two low-rank steps with the
norm between them (``q_a``, ``q_a_norm``, ``q_b``); the token's latent and
its one rotary key side by side (``kv_a``), the latent's norm
(``kv_a_norm``), the latent's expansion into a head's un-rotated key part
and value (``kv_b``); the output projection.  A ``dense`` layer then has
the gated MLP's three kernels; a ``sparse`` one the router's kernel (drawn
like any kernel, normal / sqrt(hidden): a token's 64 logits are then
independent unit normals, so over many tokens every expert gets its share
and the fourth score stands clear of the fifth as far as independent draws
let it), the selection bias (zero), the experts' kernels stacked on a leading
axis, and the shared expert's three.
"""

from __future__ import annotations

DENSE, SPARSE = "dense", "sparse"


def kinds(model: dict) -> list[str]:
    first = model["first_dense_layers"]
    return [DENSE if i < first else SPARSE
            for i in range(model["num_layers"])]


def layer(model: dict, kind: str) -> dict:
    h, heads = model["hidden_size"], model["num_heads"]
    q_rank, kv_rank = model["latent_q_rank"], model["latent_kv_rank"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    v = model["v_head_dim"]
    out = {
        "ln_attn/scale": (h,), "ln_mlp/scale": (h,),
        "q_a/kernel": (h, q_rank),
        "q_a_norm/scale": {"shape": (q_rank,), "constant": 1.0},
        "q_b/kernel": (q_rank, heads, nope + rope),
        "kv_a/kernel": (h, kv_rank + rope),
        "kv_a_norm/scale": {"shape": (kv_rank,), "constant": 1.0},
        "kv_b/kernel": (kv_rank, heads, nope + v),
        "out/kernel": {"shape": (heads, v, h), "fan_in": heads * v},
    }
    if kind == DENSE:
        inter = model["intermediate_size"]
        out.update({"mlp_in/kernel": (h, inter),
                    "mlp_gate/kernel": (h, inter),
                    "mlp_out/kernel": (inter, h)})
        return out
    if kind != SPARSE:
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
