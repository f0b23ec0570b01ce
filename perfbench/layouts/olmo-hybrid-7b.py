"""The leaves of a decoder whose layers are of two kinds, as
``models/gpt.py`` lays them out for ``layer_kinds``: full-attention layers
(the dense decoder's block plus a norm over the projected q and over the
projected k) and linear-attention layers (the gated delta rule: q, k, v and
output-gate projections, two 1-a-head projections for the decay and the
step, depthwise convolution taps, ``A_log``, ``dt_bias``, a per-head output
norm), both with the dense block's gated MLP and norms.  No JAX.

How the leaves that ``weights.leaf``'s four sorts do not fit are drawn is
chosen so that the recurrent state is ALIVE at every depth (``PERF.md``
section 4): with the norms on the sublayers' outputs the residual stream's
rms grows from 1 to about 6 over 16 layers, so the decay's projection is
drawn small (its logit has a spread of rms/6) and ``A_log`` and ``dt_bias``
put a head's decay over 64 tokens between about 0.1 and 0.8.
"""

from __future__ import annotations

import math

from perfbench.layouts import dense_decoder

FULL, LINEAR = "full_attention", "linear_attention"


def kinds(model: dict) -> list[str]:
    return list(model["layer_kinds"])


def layer(model: dict, kind: str) -> dict:
    h = model["hidden_size"]
    if kind == FULL:
        out = dense_decoder.layer(model)
        if model.get("qk_norm"):
            kv = model.get("kv_heads") or model["num_heads"]
            d = h // model["num_heads"]
            out["q_norm/scale"] = {"shape": (model["num_heads"] * d,),
                                   "constant": 1.0}
            out["k_norm/scale"] = {"shape": (kv * d,), "constant": 1.0}
        return out
    if kind != LINEAR:
        raise ValueError(f"unknown kind of layer {kind!r}")
    heads = model["linear_num_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    taps = model["linear_conv_kernel_dim"]
    inter = model["intermediate_size"]
    return {
        "ln_attn/scale": (h,), "ln_mlp/scale": (h,),
        "q_proj/kernel": (h, heads * dk), "k_proj/kernel": (h, heads * dk),
        "v_proj/kernel": (h, heads * dv), "g_proj/kernel": (h, heads * dv),
        # decay logit: spread rms(x) / 6; step logit: rms(x) / 2
        "a_proj/kernel": {"shape": (h, heads), "fan_in": 36 * h},
        "b_proj/kernel": {"shape": (h, heads), "fan_in": 4 * h},
        "conv_taps": {"shape": (taps, heads * (2 * dk + dv)),
                      "fan_in": taps},
        # -exp(A_log) * softplus(. + dt_bias) a token: A in [0.1, 0.4),
        # softplus(dt_bias) in [0.030, 0.079)
        "A_log": {"shape": (heads,),
                  "uniform": [math.log(0.1), math.log(0.4)]},
        "dt_bias": {"shape": (heads,), "uniform": [-3.5, -2.5]},
        "o_norm/scale": {"shape": (dv,), "constant": 1.0},
        "out/kernel": {"shape": (heads, dv, h), "fan_in": heads * dv},
        "mlp_in/kernel": (h, inter), "mlp_gate/kernel": (h, inter),
        "mlp_out/kernel": (inter, h),
    }


def top(model: dict) -> dict:
    h, vocab = model["hidden_size"], model["vocab_size"]
    return {"word_emb/embedding": (vocab, h), "ln_final/scale": (h,),
            "lm_head/kernel": (h, vocab), "lm_head/bias": (vocab,)}
