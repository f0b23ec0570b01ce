"""Operations and bytes a step NEEDS for a decoder of latent-attention
layers with routed experts (``perfbench/configs/glm-4.7-flash.json``), with
``perfbench/costs.py``'s signatures for the two serving programs.  No JAX.

The convention, so that a roofline share from these cannot pass 100%:

- **Decode step** of ``lanes`` lanes: every weight outside the routed
  experts once, the head once; in each sparse layer the routed experts'
  kernels of ``E (1 - (1 - k/E)^lanes)`` experts, the EXPECTED number that
  get a token when ``lanes`` tokens choose ``k`` of ``E`` evenly (64
  experts, 4 a token: 14.6 at 4 lanes, 25.8 at 8, 41.2 at 16), each once;
  the shared expert once; every cached token's row
  (``latent_kv_rank + qk_rope_head_dim`` entries) read once a layer and each
  lane's new row written.  Operations: 2 x the ACTIVE parameters a lane
  (attention, router, shared expert, ``k`` experts, head) and the absorbed
  scores, 2 x heads x (row + latent) a cached token a layer.  A step that
  touches fewer experts than expected (routing that clumps) needs less than
  this says; one that touches more needs more: the chip run reports the
  counter's mean beside the expectation.
- **Prefill** of ``p`` tokens: 2 x the active parameters a token; the scores'
  lower triangle at (nope + rope) + v operations a head a pair; ALL experts'
  kernels once (4 p pairs over 64 experts touch every one from a few hundred
  tokens on); the rows written once; no head (the engine's prefill programs
  compute no logits).  And of the LAST layer only its rows (the latent's
  projection): what else it computes would feed the logits alone, and the
  compiler drops it.

No ``train_step``: no cell trains this configuration, and training through
the grouped product is not in the program (ROADMAP R1).
"""

from __future__ import annotations


def dims(cfg: dict) -> dict:
    m = cfg["model"]
    h, heads = m["hidden_size"], m["num_heads"]
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    q_rank, kv_rank = m["latent_q_rank"], m["latent_kv_rank"]
    attn = (h * q_rank + q_rank * heads * (nope + rope) + h * (kv_rank + rope)
            + kv_rank * heads * (nope + v) + heads * v * h)
    expert = 3 * h * m["expert_intermediate_size"]
    n_dense = min(m["first_dense_layers"], m["num_layers"])
    return {"L": m["num_layers"], "n_dense": n_dense,
            "n_sparse": m["num_layers"] - n_dense, "H": h, "heads": heads,
            "row": kv_rank + rope, "latent": kv_rank, "qk": nope + rope,
            "v": v, "attn_params": attn, "row_params": h * (kv_rank + rope),
            "dense_mlp_params": 3 * h * m["intermediate_size"],
            "expert_params": expert, "experts": m["num_experts"],
            "per_token": m["experts_per_token"],
            "shared_params": expert * m["num_shared_experts"],
            "router_params": h * m["num_experts"],
            "head_params": h * m["vocab_size"]}


def experts_touched(experts: int, per_token: int, lanes: int) -> float:
    """Expected experts of one layer that get at least one of ``lanes``
    tokens, each choosing ``per_token`` distinct ones evenly."""
    return experts * (1.0 - (1.0 - per_token / experts) ** lanes)


def _outside_experts(d: dict, n_dense=None, n_sparse=None) -> float:
    """Parameters every token passes, the routed experts apart, over the
    model's layers or over ``n_dense`` + ``n_sparse`` of them."""
    n_dense = d["n_dense"] if n_dense is None else n_dense
    n_sparse = d["n_sparse"] if n_sparse is None else n_sparse
    return ((n_dense + n_sparse) * d["attn_params"]
            + n_dense * d["dense_mlp_params"]
            + n_sparse * (d["shared_params"] + d["router_params"]))


def active_params(d: dict) -> float:
    """Block parameters ONE token is multiplied with."""
    return _outside_experts(d) \
        + d["n_sparse"] * d["per_token"] * d["expert_params"]


def decode_step(cfg: dict, context_lens: list[int],
                weight_bytes: float = 2.0, kv_bytes: float = 2.0) -> dict:
    d = dims(cfg)
    lanes, ctx = len(context_lens), float(sum(context_lens))
    touched = experts_touched(d["experts"], d["per_token"], lanes)
    flops = 2.0 * (active_params(d) + d["head_params"]) * lanes
    flops += 2.0 * d["heads"] * (d["row"] + d["latent"]) * ctx * d["L"]
    nbytes = weight_bytes * (
        _outside_experts(d) + d["head_params"]
        + d["n_sparse"] * touched * d["expert_params"])
    nbytes += kv_bytes * d["row"] * (ctx + lanes) * d["L"]
    return {"flops": flops, "bytes": nbytes}


def prefill(cfg: dict, prompt_len: int, weight_bytes: float = 2.0,
            kv_bytes: float = 2.0) -> dict:
    d = dims(cfg)
    p = float(prompt_len)
    # Whole layers: all but the last, which is sparse if any layer is.
    n_sparse = max(0, d["n_sparse"] - 1)
    n_dense = d["L"] - 1 - n_sparse
    outside = _outside_experts(d, n_dense, n_sparse) + d["row_params"]
    flops = 2.0 * p * (outside
                       + n_sparse * d["per_token"] * d["expert_params"])
    flops += 2.0 * (d["qk"] + d["v"]) * d["heads"] * p * (p / 2.0) \
        * (d["L"] - 1)
    nbytes = weight_bytes * (
        outside + n_sparse * d["experts"] * d["expert_params"])
    nbytes += kv_bytes * d["row"] * p * d["L"]
    return {"flops": flops, "bytes": nbytes}
