"""Operations and bytes a step NEEDS for a decoder of gated short-convolution
and grouped-query attention layers around routed experts
(``perfbench/configs/lfm2-24b-a2b.json``), with ``perfbench/costs.py``'s
signatures for the two serving programs.  No JAX.

The LEAST work, whatever implements it, so that a roofline share from these
cannot pass 100%:

- **Decode step** of ``lanes`` lanes: every weight outside the routed
  experts once (the convolution layers' in and out projections and taps, the
  attention layers' projections, the dense layer's MLP, the routers), the
  head once; in each sparse layer the routed experts' kernels of ``E (1 - (1
  - k/E)^lanes)`` experts, the EXPECTED number that get a token when
  ``lanes`` tokens choose ``k`` of ``E`` evenly (64 experts, 4 a token: 55.9
  at 32 lanes, 63.0 at 64), each once.  A CONVOLUTION layer reads the
  ``taps - 1`` rows a lane keeps and writes one, whatever the context; an
  ATTENTION layer reads each lane's HELD tokens' keys and values once (a
  program that gathers a table whole reads more than it needs and its share
  says so) and writes the lane's new row.  Operations: 2 x the ACTIVE
  parameters a lane and the scores, 2 x 2 x heads x head size a row
  attended.
- **Prefill** of ``p`` tokens: 2 x the active parameters a token; the scores
  at 2 x 2 x heads x head size a (query, key) pair over the lower triangle
  ``p^2 / 2`` of each attention layer; ALL experts' kernels once (4 p pairs
  over 64 experts touch every one from a few dozen tokens on); an attention
  layer's rows written once, a convolution layer's tail; no head (the
  engine's prefill programs compute no logits).  And of the LAST layer only
  its cache entry: what else it computes would feed the logits alone, and
  the compiler drops it; in the benchmark's cut that layer is a
  convolution, whose tail needs the ``B`` and ``X`` thirds of its in
  projection at ``taps - 1`` positions.

No ``train_step``: no cell trains this configuration (ROADMAP R1, R11).
"""

from __future__ import annotations

CONV = "short_conv"


def dims(cfg: dict) -> dict:
    m = cfg["model"]
    h, heads, kv = m["hidden_size"], m["num_heads"], m["kv_heads"]
    d = m.get("head_size") or h // heads
    kinds = list(m["layer_kinds"])
    n_dense = min(m["first_dense_layers"], m["num_layers"])
    return {"L": m["num_layers"], "n_dense": n_dense,
            "n_sparse": m["num_layers"] - n_dense, "H": h, "heads": heads,
            "kv": kv, "D": d, "kinds": kinds, "n_conv": kinds.count(CONV),
            "n_attn": len(kinds) - kinds.count(CONV),
            "taps": m["short_conv_kernel_dim"],
            # in [h, 3h], out [h, h], the taps
            "conv_params": 4 * h * h + m["short_conv_kernel_dim"] * h,
            # q and out; k and v
            "attn_params": 2 * h * heads * d + 2 * h * kv * d,
            "dense_mlp_params": 3 * h * m["intermediate_size"],
            "expert_params": 3 * h * m["expert_intermediate_size"],
            "experts": m["num_experts"],
            "per_token": m["experts_per_token"],
            "router_params": h * m["num_experts"],
            "head_params": h * m["vocab_size"]}


def experts_touched(experts: int, per_token: int, lanes: int) -> float:
    """Expected experts of one layer that get at least one of ``lanes``
    tokens, each choosing ``per_token`` distinct ones evenly."""
    return experts * (1.0 - (1.0 - per_token / experts) ** lanes)


def _outside_experts(d: dict, kinds: list, n_dense: int) -> float:
    """Parameters every token passes in layers of ``kinds``, the first
    ``n_dense`` of them dense, the routed experts apart."""
    mixers = sum(d["conv_params"] if k == CONV else d["attn_params"]
                 for k in kinds)
    return (mixers + n_dense * d["dense_mlp_params"]
            + (len(kinds) - n_dense) * d["router_params"])


def active_params(d: dict) -> float:
    """Block parameters ONE token is multiplied with."""
    return _outside_experts(d, d["kinds"], d["n_dense"]) \
        + d["n_sparse"] * d["per_token"] * d["expert_params"]


def decode_step(cfg: dict, context_lens: list[int],
                weight_bytes: float = 2.0, kv_bytes: float = 2.0) -> dict:
    d = dims(cfg)
    lanes = len(context_lens)
    rows = float(sum(context_lens)) * d["n_attn"]
    touched = experts_touched(d["experts"], d["per_token"], lanes)
    flops = 2.0 * (active_params(d) + d["head_params"]) * lanes
    flops += 2.0 * 2.0 * d["heads"] * d["D"] * rows
    nbytes = weight_bytes * (
        _outside_experts(d, d["kinds"], d["n_dense"]) + d["head_params"]
        + d["n_sparse"] * touched * d["expert_params"])
    # keys and values of every held token, and the lanes' new rows
    nbytes += kv_bytes * 2.0 * d["kv"] * d["D"] * (
        rows + lanes * d["n_attn"])
    # a convolution layer's tail: taps - 1 rows read, one written, a lane
    nbytes += kv_bytes * d["H"] * d["taps"] * lanes * d["n_conv"]
    return {"flops": flops, "bytes": nbytes}


def prefill(cfg: dict, prompt_len: int, weight_bytes: float = 2.0,
            kv_bytes: float = 2.0) -> dict:
    d = dims(cfg)
    p = float(prompt_len)
    # Whole layers: all but the last, which is sparse if any layer is.
    whole = d["kinds"][:-1]
    n_sparse = max(0, d["n_sparse"] - 1)
    n_dense = len(whole) - n_sparse
    outside = _outside_experts(d, whole, n_dense)
    flops = 2.0 * p * (outside
                       + n_sparse * d["per_token"] * d["expert_params"])
    pairs = sum(p * p / 2.0 for kind in whole if kind != CONV)
    flops += 2.0 * 2.0 * d["heads"] * d["D"] * pairs
    nbytes = weight_bytes * (
        outside + n_sparse * d["experts"] * d["expert_params"])
    # The last layer's cache entry alone.
    keep = d["taps"] - 1
    if d["kinds"][-1] == CONV:
        last = 2.0 * d["H"] * d["H"]           # the B and X thirds
        flops += 2.0 * keep * last
    else:
        last = 2.0 * d["H"] * d["kv"] * d["D"]
        flops += 2.0 * p * last
    nbytes += weight_bytes * last
    nbytes += kv_bytes * 2.0 * d["kv"] * d["D"] * p * d["n_attn"]
    nbytes += kv_bytes * d["H"] * keep * d["n_conv"]
    return {"flops": flops, "bytes": nbytes}
