"""Operations and bytes a step NEEDS for a decoder whose layers are
full-attention and linear-attention (gated delta rule) in the order the
configuration's ``model.layer_kinds`` gives, with ``perfbench/costs.py``'s
signatures.  No JAX.

Needed work only, so that a roofline share from these cannot pass 100%:
both kinds' matrix products once, the lower triangle of the scores in the
full layers only, and for the rule its token-by-token form, 7 operations an
entry of a head's state a token (decay 1, S k 2, the rank-one update 2, S q
2), which is less than the chunked form spends.  The state is read and
written once a lane a step in decode and written once in prefill; a prefill
counts the prompt's own tokens and no head (the engine's prefill programs
compute no logits: the first token is the decode step's).
"""

from __future__ import annotations

FULL, LINEAR = "full_attention", "linear_attention"
STATE_BYTES = 4.0       # the state is float32


def dims(cfg: dict) -> dict:
    m = cfg["model"]
    h, heads = m["hidden_size"], m["num_heads"]
    kv = m.get("kv_heads") or heads
    d = h // heads
    lh, dk, dv = (m["linear_num_heads"], m["linear_key_head_dim"],
                  m["linear_value_head_dim"])
    mlp = 3 * h * m["intermediate_size"]
    full = h * heads * d + 2 * h * kv * d + heads * d * h + mlp
    linear = 2 * h * lh * dk + 2 * h * lh * dv + lh * dv * h + 2 * h * lh \
        + mlp
    n_full = list(m["layer_kinds"]).count(FULL)
    n_lin = list(m["layer_kinds"]).count(LINEAR)
    return {"n_full": n_full, "n_linear": n_lin, "H": h, "heads": heads,
            "kv": kv, "D": d, "full_params": full, "linear_params": linear,
            "block_params": n_full * full + n_lin * linear,
            "head_params": h * m["vocab_size"],
            "state_entries": lh * dv * dk,
            "tail_entries": (m["linear_conv_kernel_dim"] - 1)
            * lh * (2 * dk + dv)}


def _rule_flops(d: dict, tokens: float) -> float:
    return 7.0 * d["state_entries"] * d["n_linear"] * tokens


def decode_step(cfg: dict, context_lens: list[int],
                weight_bytes: float = 2.0, kv_bytes: float = 2.0) -> dict:
    """One decode step: every weight read once, each lane's K and V read
    once in the full layers, each lane's state read and written once in the
    linear ones (its convolution tail too)."""
    d = dims(cfg)
    lanes, ctx = len(context_lens), float(sum(context_lens))
    flops = 2.0 * (d["block_params"] + d["head_params"]) * lanes
    flops += 2.0 * 2.0 * d["n_full"] * ctx * d["heads"] * d["D"]
    flops += _rule_flops(d, lanes)
    nbytes = weight_bytes * (d["block_params"] + d["head_params"])
    nbytes += kv_bytes * 2.0 * d["n_full"] * ctx * d["kv"] * d["D"]
    nbytes += 2.0 * d["n_linear"] * lanes * (
        STATE_BYTES * d["state_entries"] + 2.0 * d["tail_entries"])
    return {"flops": flops, "bytes": nbytes}


def prefill(cfg: dict, prompt_len: int, weight_bytes: float = 2.0,
            kv_bytes: float = 2.0) -> dict:
    """One whole-prompt prefill: the blocks over every prompt token, K and
    V written once in the full layers, the state written once in the linear
    ones.  Only the full layers' scores grow with the square of the length."""
    d = dims(cfg)
    p = float(prompt_len)
    flops = 2.0 * d["block_params"] * p
    flops += 2.0 * 2.0 * d["n_full"] * p * (p / 2.0) * d["heads"] * d["D"]
    flops += _rule_flops(d, p)
    nbytes = weight_bytes * d["block_params"]
    nbytes += kv_bytes * 2.0 * d["n_full"] * p * d["kv"] * d["D"]
    nbytes += d["n_linear"] * (STATE_BYTES * d["state_entries"]
                               + 2.0 * d["tail_entries"])
    return {"flops": flops, "bytes": nbytes}


def train_step(cfg: dict, rows: int, seq: int, n_params: int) -> dict:
    """One optimizer step, as ``perfbench/costs.py`` counts a dense one:
    backward twice the forward; float32 parameters, gradients and Adam's
    slots moved ten times, the residual stream twice a layer and
    direction.  No cell trains this configuration yet."""
    d = dims(cfg)
    tokens = rows * seq
    layers = d["n_full"] + d["n_linear"]
    fwd = 2.0 * (d["block_params"] + d["head_params"]) * tokens
    fwd += 2.0 * 2.0 * d["n_full"] * tokens * (seq / 2.0) * d["H"]
    fwd += _rule_flops(d, tokens)
    nbytes = 10.0 * 4.0 * n_params + 4.0 * 2.0 * layers * tokens * d["H"]
    return {"flops": 3.0 * fwd, "bytes": nbytes}
