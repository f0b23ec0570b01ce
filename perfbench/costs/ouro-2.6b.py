"""Operations and bytes a step NEEDS for a decoder whose stack of layers is
applied ``R = loop_steps`` times over the same weights
(``perfbench/configs/ouro-2.6b.json``), with ``perfbench/costs.py``'s
signatures for the two serving programs.  No JAX.

The convention, so that a roofline share from these cannot pass 100%:

- **Decode step** of ``lanes`` lanes: every block weight ``R`` times (48
  layers are 4.93 GB in bfloat16: what step ``t`` read cannot stay on the
  chip until step ``t + 1`` reads it again), the head once; every held
  token's ``R x L`` rows of keys and of values read once, and each lane's
  new rows written.  Operations: 2 x (``R`` x block parameters + head) a
  lane, and scores and weighted values over the held rows, 2 x 2 x heads x
  head size a held token an application.  Norm scales, the zero biases and
  the exit gate's 2,048 weights count nothing.
- **Prefill** of ``p`` tokens: 2 x ``R`` x block parameters a token; the
  scores' lower triangle ``R x L`` times; the block weights read ``R``
  times; the rows written once; no head (the engine's prefill programs
  compute no logits).  Of the LAST application (step ``R``, layer ``L``)
  only its keys and values: what else it computes would feed the logits
  alone, and the compiler drops it.

No ``train_step``: no cell trains this configuration (at 16 bytes a
parameter one chip holds 9 of its 48 layers).
"""

from __future__ import annotations


def dims(cfg: dict) -> dict:
    m = cfg["model"]
    h, heads = m["hidden_size"], m["num_heads"]
    if m.get("kv_heads"):
        raise ValueError("these counts are of ungrouped heads")
    return {"L": m["num_layers"], "R": m["loop_steps"], "H": h,
            "heads": heads, "D": h // heads, "kv_params": 2 * h * h,
            "layer_params": 4 * h * h + 3 * h * m["intermediate_size"],
            "head_params": h * m["vocab_size"]}


def decode_step(cfg: dict, context_lens: list[int],
                weight_bytes: float = 2.0, kv_bytes: float = 2.0) -> dict:
    d = dims(cfg)
    lanes, ctx = len(context_lens), float(sum(context_lens))
    apps = d["R"] * d["L"]
    flops = 2.0 * (apps * d["layer_params"] + d["head_params"]) * lanes
    flops += 2.0 * 2.0 * apps * ctx * d["H"]
    nbytes = weight_bytes * (apps * d["layer_params"] + d["head_params"])
    nbytes += kv_bytes * 2.0 * apps * (ctx + lanes) * d["H"]
    return {"flops": flops, "bytes": nbytes}


def prefill(cfg: dict, prompt_len: int, weight_bytes: float = 2.0,
            kv_bytes: float = 2.0) -> dict:
    d = dims(cfg)
    p = float(prompt_len)
    apps = d["R"] * d["L"]
    # Whole applications, and the last one's key and value projections.
    params = (apps - 1) * d["layer_params"] + d["kv_params"]
    flops = 2.0 * params * p
    flops += 2.0 * 2.0 * (apps - 1) * p * (p / 2.0) * d["H"]
    nbytes = weight_bytes * params
    nbytes += kv_bytes * 2.0 * apps * p * d["H"]
    return {"flops": flops, "bytes": nbytes}
