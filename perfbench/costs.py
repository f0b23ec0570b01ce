"""Operations and bytes a step NEEDS, from shapes alone, and the least time
the chip could take for them.  Kept with the benchmark so that no PR that
claims a gain can change the yardstick.  No JAX.

Only needed work counts: causal attention counts the lower triangle, a
prefill counts the prompt's own tokens (not the padding of its bucket), the
head counts the positions whose logits are used, recomputation counts
nothing.  So a roofline share from these cannot pass 100%.
"""

from __future__ import annotations


def dims(cfg: dict) -> dict:
    """The sizes the counts need, from a configuration file's ``model``."""
    m = cfg["model"]
    h, heads = m["hidden_size"], m["num_heads"]
    kv = m.get("kv_heads") or heads
    d = h // heads
    gated = m.get("activation") == "swiglu"
    per_layer = (h * heads * d + 2 * h * kv * d + heads * d * h
                 + (3 if gated else 2) * h * m["intermediate_size"])
    return {"L": m["num_layers"], "H": h, "heads": heads, "kv": kv, "D": d,
            "V": m["vocab_size"], "layer_params": per_layer,
            "block_params": per_layer * m["num_layers"],
            "head_params": h * m["vocab_size"]}


def train_step(cfg: dict, rows: int, seq: int, n_params: int) -> dict:
    """One optimizer step over ``rows`` x ``seq`` tokens.  Forward matmuls
    2 x params x tokens (blocks and head), causal attention 2 x 2 x L x T x
    S/2 x H; backward twice the forward.  Bytes: f32 parameters read by
    forward and backward, gradients written and read, Adam's two slots and
    the parameters read and written by the update (10 param-sized f32
    transfers), and the residual stream written and read once per layer
    and direction in bf16."""
    d = dims(cfg)
    tokens = rows * seq
    fwd = 2.0 * (d["block_params"] + d["head_params"]) * tokens
    fwd += 2.0 * 2.0 * d["L"] * tokens * (seq / 2.0) * d["H"]
    nbytes = 10.0 * 4.0 * n_params + 4.0 * 2.0 * d["L"] * tokens * d["H"]
    return {"flops": 3.0 * fwd, "bytes": nbytes}


def decode_step(cfg: dict, context_lens: list[int],
                weight_bytes: float = 2.0, kv_bytes: float = 2.0) -> dict:
    """One decode step for lanes holding ``context_lens`` cached tokens:
    every weight read once, each lane's K and V read once."""
    d = dims(cfg)
    lanes, ctx = len(context_lens), float(sum(context_lens))
    flops = 2.0 * (d["block_params"] + d["head_params"]) * lanes
    flops += 2.0 * 2.0 * d["L"] * ctx * d["heads"] * d["D"]
    nbytes = weight_bytes * (d["block_params"] + d["head_params"])
    nbytes += kv_bytes * 2.0 * d["L"] * ctx * d["kv"] * d["D"]
    return {"flops": flops, "bytes": nbytes}


def prefill(cfg: dict, prompt_len: int, weight_bytes: float = 2.0,
            kv_bytes: float = 2.0) -> dict:
    """One whole-prompt prefill: the blocks over every prompt token, the
    head over the last position only, K and V written once."""
    d = dims(cfg)
    p = float(prompt_len)
    flops = 2.0 * d["block_params"] * p + 2.0 * d["head_params"]
    flops += 2.0 * 2.0 * d["L"] * p * (p / 2.0) * d["heads"] * d["D"]
    nbytes = weight_bytes * (d["block_params"] + d["head_params"])
    nbytes += kv_bytes * 2.0 * d["L"] * p * d["kv"] * d["D"]
    return {"flops": flops, "bytes": nbytes}


def least_time(cost: dict, peaks: dict, chips: int = 1) -> dict:
    """The larger of operations over peak FLOP/s and bytes over peak
    bytes/s, and which of the two it is."""
    t_flops = cost["flops"] / (peaks["bf16_flops"] * chips)
    t_bytes = cost["bytes"] / (peaks["hbm_bytes_per_s"] * chips)
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
