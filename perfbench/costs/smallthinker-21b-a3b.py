"""Operations and bytes a step NEEDS for a decoder of full and sliding-window
attention layers (grouped-query heads) around routed experts in every layer,
the router reading ahead of the attention
(``perfbench/configs/smallthinker-21b-a3b.json``), with ``perfbench/
costs.py``'s signatures for the two serving programs.  No JAX.

The LEAST work, whatever implements it, so that a roofline share from these
cannot pass 100%:

- **Decode step** of ``lanes`` lanes: every weight outside the routed
  experts once (attention's projections, the routers), the head once; in
  each layer the routed experts' kernels of ``E (1 - (1 - k/E)^lanes)``
  experts, the EXPECTED number that get a token when ``lanes`` tokens choose
  ``k`` of ``E`` evenly (64 experts, 6 a token: 50.7 at 16 lanes), each
  once.  A FULL layer reads every cached token's keys and values once; a
  SLIDING layer reads ``min(context, sliding_window)`` of them, whatever ring
  or table the program walks (a program that reads a page more a lane, or
  the whole context, reads more than it needs and its share says so); each
  lane's new row written.  Operations: 2 x the ACTIVE parameters a lane and
  the scores, 2 x 2 x heads x head size a row attended.  Where the route is
  computed (before the attention or after it) changes neither count.
- **Prefill** of ``p`` tokens: 2 x the active parameters a token; the scores
  at 2 x 2 x heads x head size a (query, key) pair, a full layer the lower
  triangle ``p^2 / 2``, a sliding layer the BAND (``p w - w^2 / 2`` pairs
  for ``p >= w``); ALL experts' kernels once (6 p pairs over 64 experts
  touch every one from a few dozen tokens on); a full layer's rows written
  once, a sliding layer's last ``min(p, w)``; no head (the engine's prefill
  programs compute no logits).  And of the LAST layer only its rows (the
  key/value projection): what else it computes would feed the logits alone,
  and the compiler drops it; in the benchmark's cut that layer is a SLIDING
  one, so every full layer is scored whole.

No ``train_step``: no cell trains this configuration (ROADMAP R1).
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def dims(cfg: dict) -> dict:
    m = cfg["model"]
    h, heads, kv, d = (m["hidden_size"], m["num_heads"], m["kv_heads"],
                       m["head_size"])
    rows = 2 * h * kv * d                    # the key and value projections
    if m["first_dense_layers"] or m["num_shared_experts"]:
        raise ValueError("these counts have routed experts in every layer "
                         "and no shared one")
    return {"L": m["num_layers"], "H": h, "heads": heads, "kv": kv, "D": d,
            "kinds": list(m["layer_kinds"]), "window": m["sliding_window"],
            "attn_params": 2 * h * heads * d + rows,      # q, out; k and v
            "row_params": rows,
            "expert_params": 3 * h * m["expert_intermediate_size"],
            "experts": m["num_experts"],
            "per_token": m["experts_per_token"],
            "router_params": h * m["num_experts"],
            "head_params": h * m["vocab_size"]}


def experts_touched(experts: int, per_token: int, lanes: int) -> float:
    """Expected experts of one layer that get at least one of ``lanes``
    tokens, each choosing ``per_token`` distinct ones evenly."""
    return experts * (1.0 - (1.0 - per_token / experts) ** lanes)


def _outside_experts(d: dict, layers=None) -> float:
    """Parameters every token passes, the routed experts apart, over the
    model's layers or over ``layers`` of them."""
    layers = d["L"] if layers is None else layers
    return layers * (d["attn_params"] + d["router_params"])


def active_params(d: dict) -> float:
    """Block parameters ONE token is multiplied with."""
    return _outside_experts(d) + d["L"] * d["per_token"] * d["expert_params"]


def rows_attended(d: dict, context: float) -> float:
    """Cached rows ONE token at ``context`` cached tokens needs over all
    layers: the whole context a full layer, the window a sliding one."""
    return sum(min(context, d["window"]) if kind == SLIDING else context
               for kind in d["kinds"])


def band_pairs(p: float, window: float) -> float:
    """(query, key) pairs of ``p`` tokens with ``0 <= q - k < window``."""
    if p <= window:
        return p * p / 2.0
    return p * window - window * window / 2.0


def decode_step(cfg: dict, context_lens: list[int],
                weight_bytes: float = 2.0, kv_bytes: float = 2.0) -> dict:
    d = dims(cfg)
    lanes = len(context_lens)
    rows = float(sum(rows_attended(d, c) for c in context_lens))
    touched = experts_touched(d["experts"], d["per_token"], lanes)
    flops = 2.0 * (active_params(d) + d["head_params"]) * lanes
    flops += 2.0 * 2.0 * d["heads"] * d["D"] * rows
    nbytes = weight_bytes * (
        _outside_experts(d) + d["head_params"]
        + d["L"] * touched * d["expert_params"])
    nbytes += kv_bytes * 2.0 * d["kv"] * d["D"] * (rows + lanes * d["L"])
    return {"flops": flops, "bytes": nbytes}


def prefill(cfg: dict, prompt_len: int, weight_bytes: float = 2.0,
            kv_bytes: float = 2.0) -> dict:
    d = dims(cfg)
    p = float(prompt_len)
    whole = d["L"] - 1          # of the last layer its rows only
    outside = _outside_experts(d, whole) + d["row_params"]
    flops = 2.0 * p * (outside
                       + whole * d["per_token"] * d["expert_params"])
    pairs = sum(band_pairs(p, d["window"]) if kind == SLIDING
                else p * p / 2.0 for kind in d["kinds"][:-1])
    flops += 2.0 * 2.0 * d["heads"] * d["D"] * pairs
    nbytes = weight_bytes * (
        outside + whole * d["experts"] * d["expert_params"])
    written = sum(min(p, d["window"]) if kind == SLIDING else p
                  for kind in d["kinds"])
    nbytes += kv_bytes * 2.0 * d["kv"] * d["D"] * written
    return {"flops": flops, "bytes": nbytes}
